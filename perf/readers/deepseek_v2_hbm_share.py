"""Share of the chip's memory bandwidth DeepSeek-V2's decode steps needed:
the bytes they must move (perf/lib/deepseek_v2_cost.py: the weights outside
the routed experts, the experts TOUCHED, the head, the latent cache of the
live rows at their lengths — from the program's ``moe.*`` / ``mla.*``
counters) over the whole of ``engine.decode_chunk_s`` times the published
bandwidth.  Counters and histogram both cover the whole process.  A program
without the counters gives ``None``."""

from perf.lib import deepseek_v2_cost, peaks


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    need = ("moe.decode_layers", "moe.experts_touched", "mla.context_tokens",
            "engine.decode_chunk_s")
    if any(not snap.get(k) for k in need):
        return None
    config = obs["config"]
    seconds = snap["engine.decode_chunk_s"]["sum"]
    steps = (snap["moe.decode_layers"]["value"]
             / deepseek_v2_cost.expert_layers(config))
    if not seconds or not steps:
        return None
    moved = deepseek_v2_cost.decode_bytes(
        config, steps, snap["moe.experts_touched"]["value"],
        snap["mla.context_tokens"]["value"])
    peak = peaks.peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (seconds * peak)
