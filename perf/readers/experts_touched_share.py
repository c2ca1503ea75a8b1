"""Share of the experts a chip HOLDS that a decode step's expert layers
touched: the program's gauges ``moe.experts_touched`` over
``moe.decode_layers`` (``progen_tpu.observe.metrics``' process registry,
cumulative since the engine was built) times the experts the cell's
configuration holds — its ``experts_held``, or every one of its
``num_experts`` where it names no share.  One entry for every family with
held experts: the divisor comes from the configuration, not from a file a
family.  A program that has no such gauge, a denominator of zero, or a
configuration without experts gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    touched = snap.get("moe.experts_touched")
    layers = snap.get("moe.decode_layers")
    config = obs["config"]
    held = config.get("experts_held") or config.get("num_experts")
    if not touched or not layers or not layers.get("value") or not held:
        return None
    return touched["value"] / (layers["value"] * held)
