"""Multi-process disaggregated serving: wire format, router policy,
stage supervision, and REAL 2-process clusters (spawned workers, pattern
of ``tests/_multihost_worker.py``) asserted token-identical to the
single-process engine — greedy AND sampled, dense and paged — plus
chaos (kill a prefill worker mid-run: replay or typed shed, never a
raise, never token divergence on survivors)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from progen_tpu.decode.engine import FAILED_FAULT, Request, ServingEngine
from progen_tpu.decode.handoff import (
    FrameCorrupt,
    FrameDesync,
    _flatten_state,
    deserialize_handle,
    pack_frame,
    request_from_wire,
    request_to_wire,
    serialize_handle,
    unpack_frame,
)
from progen_tpu.models import ProGenConfig
from progen_tpu.observe.transport import TransportCounters
from progen_tpu.resilience.supervise import StageSupervisor
from progen_tpu.serve.router import Router
from progen_tpu.serve.worker import build_engine_from_spec, make_spec

pytestmark = pytest.mark.multiproc

# depth=2 keeps the per-layer cache LISTS (the interesting flatten case)
# while halving single-core compile wall — tier-1 runs on one CPU under a
# hard wall-clock budget, and every engine here is built in a subprocess
CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=2, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)
ENGINE_KW = dict(num_slots=4, chunk_size=4, max_len=24, prefill_batch=2,
                 handoff_depth=2)
VARIANT_KW = {
    "dense": {},
    "paged": dict(paged=True, page_size=4, num_pages=32),
}


def _spec(variant="dense"):
    return make_spec(CFG, mixed_precision=False, init_seed=7,
                     engine={**ENGINE_KW, **VARIANT_KW[variant]})


def _requests(n=4, start=0):
    """Mixed greedy (odd uid) and sampled (even uid) requests."""
    return [
        Request(uid=i, tokens=[1 + i, 2, 3], max_new_tokens=6,
                top_k=(None if i % 2 else 8),
                temperature=(0.0 if i % 2 else 1.0), seed=100 + i)
        for i in range(start, start + n)
    ]


_REFERENCE_CACHE: dict = {}


def _run_reference(variant="dense", n=4):
    """Single-process disagg engine: the token-identity oracle.
    Memoized per (variant, n) — determinism makes the rerun identical,
    and each build costs real single-core compile wall."""
    key = (variant, n)
    if key not in _REFERENCE_CACHE:
        eng = build_engine_from_spec(_spec(variant))
        for r in _requests(n):
            eng.submit(r)
        done = eng.run_until_idle()
        _REFERENCE_CACHE[key] = {
            c.uid: [int(t) for t in c.tokens] for c in done if c.ok}
    return _REFERENCE_CACHE[key]


# ----------------------------------------------------------- wire round-trips


@pytest.mark.parametrize("variant", ["dense", "paged"])
def test_handle_wire_roundtrip_bit_exact(variant):
    """serialize → frame → deserialize → merge must be bit-exact with
    the in-process handoff for every handle flavor: the split engines'
    tokens match the single disagg engine's, greedy and sampled."""
    reference = _run_reference(variant)

    peng = build_engine_from_spec(_spec(variant))           # prefill side
    deng = build_engine_from_spec(_spec(variant), remote_prefill=True)
    for r in _requests():
        peng.submit(r)
    got = {}
    counters = TransportCounters()
    while peng.pending or deng.has_work:
        h = peng.run_prefill_round()
        if h is not None:
            # leaf-level bit-exactness across the wire, then merge the
            # DESERIALIZED handle (never the original: donation)
            frame = serialize_handle(h, counters=counters,
                                     extra_header={"batch_id": "t:0"})
            header, _ = unpack_frame(frame)
            assert header["batch_id"] == "t:0"
            assert header["p_pad"] == h.p_pad
            before = {p: np.asarray(jax.device_get(v))
                      for p, v in _flatten_state(h.state)}
            h2 = deserialize_handle(frame, counters=counters)
            after = dict(_flatten_state(h2.state))
            assert sorted(before) == sorted(after)
            for path, exp in before.items():
                arr = np.asarray(jax.device_get(after[path]))
                assert arr.dtype == exp.dtype, path
                np.testing.assert_array_equal(arr, exp, err_msg=path)
            assert [r.uid for r in h2.requests] == [r.uid for r in h.requests]
            assert deng.admit_handle(h2)
        for c in deng.step():
            if c.ok:
                got[c.uid] = [int(t) for t in c.tokens]
    assert got == reference
    assert deng.stage_seconds["prefill_s"] == 0.0  # never ran prefill
    assert counters.ser_s > 0 and counters.de_s > 0


def test_truncated_frame_raises_desync():
    peng = build_engine_from_spec(_spec())
    for r in _requests(2):
        peng.submit(r)
    frame = serialize_handle(peng.run_prefill_round())
    with pytest.raises(FrameDesync):
        unpack_frame(frame[:20])            # inside the prefix
    with pytest.raises(FrameDesync):
        unpack_frame(frame[:-5])            # payload cut short
    with pytest.raises(FrameDesync):
        unpack_frame(b"XXXX" + frame[4:])   # bad magic
    with pytest.raises(FrameDesync):        # header bit flip
        buf = bytearray(frame)
        buf[30] ^= 0xFF
        unpack_frame(bytes(buf))


def test_payload_crc_mismatch_sheds_typed_with_header():
    """A payload flip must raise FrameCorrupt CARRYING the header — the
    stream is still framed, so the router sheds/replays exactly the
    requests named in it instead of crashing."""
    peng = build_engine_from_spec(_spec())
    for r in _requests(2):
        peng.submit(r)
    frame = serialize_handle(peng.run_prefill_round(),
                             extra_header={"batch_id": "p:7"})
    buf = bytearray(frame)
    buf[-1] ^= 0xFF
    with pytest.raises(FrameCorrupt) as ei:
        deserialize_handle(bytes(buf))
    assert ei.value.header["batch_id"] == "p:7"
    assert [d["uid"] for d in ei.value.header["reqs"]] == [0, 1]


def test_request_wire_roundtrip_carries_deadline_budget():
    r = Request(uid="a", tokens=[1, 2], max_new_tokens=3, top_k=5,
                temperature=0.5, seed=9, ttl=10.0, submit_time=100.0)
    wire = request_to_wire(r, now=104.0)
    assert wire["deadline_remaining"] == pytest.approx(6.0)
    back = request_from_wire(wire, now=200.0)
    assert (back.uid, list(back.tokens), back.max_new_tokens) == \
        ("a", [1, 2], 3)
    assert (back.top_k, back.temperature, back.seed) == (5, 0.5, 9)
    assert back.deadline == pytest.approx(206.0)
    none = request_to_wire(Request(uid="b", tokens=[1]), now=0.0)
    assert "deadline_remaining" not in none


def test_frame_counters_merge():
    a, b = TransportCounters(), TransportCounters()
    a.sent(100), b.received(40)
    b.crc_failures += 1
    a.merge(b)
    a.merge({"frames_out": 2, "bytes_out": 10, "ser_s": 0.5})
    d = a.as_dict()
    assert d["frames_out"] == 3 and d["bytes_out"] == 110
    assert d["frames_in"] == 1 and d["bytes_in"] == 40
    assert d["crc_failures"] == 1 and d["ser_s"] == 0.5


# ------------------------------------------------------------- router policy


def test_router_least_loaded_placement():
    rt = Router(2, 2)
    reqs = {i: Request(uid=i, tokens=[1], max_new_tokens=10 * (i + 1))
            for i in range(4)}
    assert rt.pick_prefill() == 0
    rt.assign_prefill(0, reqs[0], 0, now=0.0)
    assert rt.pick_prefill() == 1          # least queued
    rt.assign_prefill(1, reqs[1], 1, now=0.0)
    rt.assign_prefill(2, reqs[2], rt.pick_prefill(), now=0.0)
    assert rt.prefill_load == {0: 2, 1: 1}

    rt.note_handle("0:0", [0, 2], src=0)
    assert rt.prefill_load[0] == 0
    assert rt.pick_replica() == 0
    rt.forward("0:0", 0)
    assert rt.outstanding[0] == 10 + 30    # sum of max_new_tokens
    assert rt.pick_replica() == 1          # least outstanding TOKENS
    rt.note_handle("1:0", [1], src=1)
    rt.forward("1:0", rt.pick_replica())
    assert rt.outstanding[1] == 20
    assert rt.ack("0:0") == 0 and rt.ack("nope") is None

    assert rt.complete(0) is True
    assert rt.complete(0) is False         # duplicate dropped
    assert rt.outstanding[0] == 30
    assert rt.stats()["completed"] == 1


def test_router_fail_worker_maps_dead_stage_to_exact_uids():
    rt = Router(2, 2)
    reqs = {i: Request(uid=i, tokens=[1], max_new_tokens=4)
            for i in range(5)}
    for i in range(4):
        rt.assign_prefill(i, reqs[i], i % 2, now=0.0)
    rt.note_handle("0:0", [0], src=0)
    rt.forward("0:0", 1)
    rt.complete(0)
    # prefill 0 now holds only uid 2; uid 0 completed, 1/3 are on worker 1
    assert rt.fail_worker("prefill", 0) == [2]
    assert rt.pick_prefill() == 1
    # replica 1 held nothing live; kill replica stage entirely
    rt.assign_prefill(4, reqs[4], 1, now=0.0)
    rt.note_handle("1:0", [4], src=1)
    rt.forward("1:0", 0)
    assert rt.fail_worker("decode", 0) == [4]
    assert rt.outstanding[0] == 0
    rt.fail_worker("decode", 1)
    assert rt.pick_replica() is None       # whole stage down
    rt.revive_worker("decode", 0)
    assert rt.pick_replica() == 0


def test_router_batch_credit_and_pruning():
    """A batch yields exactly ONE credit ever, and its entry is pruned
    once acked + every member uid resolved — long-running clusters must
    not grow router bookkeeping per batch."""
    rt = Router(1, 1)
    reqs = {i: Request(uid=i, tokens=[1], max_new_tokens=4)
            for i in range(2)}
    for i in range(2):
        rt.assign_prefill(i, reqs[i], 0, now=0.0)
    rt.note_handle("0.0:0", [0, 1], src=0)
    rt.forward("0.0:0", 0)
    assert rt.unacked_batches(0) == ["0.0:0"]
    assert rt.ack("0.0:0") == 0
    assert rt.ack("0.0:0") is None          # second ack: no double grant
    assert rt.unacked_batches(0) == []
    assert "0.0:0" in rt.batches            # member uids still open
    rt.complete(0)
    rt.complete(1)
    assert rt.batches == {}                 # acked + resolved -> pruned
    assert rt.stats()["open_batches"] == 0

    # requeue resolves membership too (bad frame / dead stage), and the
    # credit can come back through the drop path instead of an ack
    r = Request(uid="x", tokens=[1], max_new_tokens=4)
    rt.assign_prefill("x", r, 0, now=1.0)
    rt.note_handle("0.0:1", ["x"], src=0)
    rt.forward("0.0:1", 0)
    assert rt.requeue(["x"]) == ["x"]
    assert "0.0:1" in rt.batches            # credit not yet returned
    assert rt.ack("0.0:1") == 0
    assert rt.batches == {}


# ---------------------------------------- cluster handler logic (fake peers)


class _FakePeer:
    """Transport stand-in: records every frame the cluster sends."""

    def __init__(self, role, index):
        self.role, self.index = role, index
        self.alive, self.ready = True, True
        self.last_seen = 1e18    # never stale
        self.sent = []

    def send_json(self, obj):
        self.sent.append(obj)

    def send_bytes(self, frame):
        self.sent.append(("bytes", frame))

    def close(self):
        self.alive = False

    def reqs(self):
        return [m for m in self.sent
                if isinstance(m, dict) and m.get("type") == "req"]

    def acks(self):
        return [m for m in self.sent
                if isinstance(m, dict) and m.get("type") == "ack"]


def _bare_cluster(prefill=1, replicas=1, max_restarts=0):
    """A ServeCluster with fake peers and no subprocesses: drives the
    event handlers directly for deterministic credit/lifecycle asserts
    (the real-fleet paths are covered by the subprocess tests below)."""
    import queue as _q

    from progen_tpu.serve.cluster import ServeCluster

    c = ServeCluster.__new__(ServeCluster)
    c.prefill_procs, c.replicas = prefill, replicas
    c.supervisor = StageSupervisor(max_restarts=max_restarts)
    c.stale_after = 1e9
    c.counters = TransportCounters()
    c.router = Router(prefill, replicas)
    c.completions, c._new = {}, []
    c._events = _q.Queue()
    c._peers, c._procs, c._incarnations = {}, {}, {}
    c._handled_dead, c._respawning = set(), set()
    c._parked_uids, c._worker_stats, c._hb = [], {}, {}
    c._stats_age, c._clock_offsets = {}, {}
    c._ttft, c._cache_counts = {}, {}
    c.generation = 0
    c._worker_gen = {("prefill", i): 0 for i in range(prefill)}
    c._worker_gen.update({("decode", i): 0 for i in range(replicas)})
    c._worker_spec = {}
    c._retiring, c._pending_routable = set(), set()
    c._next_idx = {"prefill": prefill, "decode": replicas}
    c._spec_paths = {}
    c._statusz_providers = {}
    from progen_tpu.observe import metrics as _metrics
    from progen_tpu.observe import trace as _trace
    c._tracer = _trace.get_tracer()
    c._lat = _metrics.get_registry().histogram("cluster.latency_s")
    c._ok_ctr = _metrics.get_registry().counter("cluster.completions_ok")
    c._shed_ctr = _metrics.get_registry().counter("cluster.completions_shed")
    c._statusz = None
    c._statusz_ports = {}
    c._slo, c._slo_last = None, 0.0
    c._shutting_down = False
    c._spawn = lambda role, idx: None    # supervision grants don't fork
    for i in range(prefill):
        c._peers[("prefill", i)] = _FakePeer("prefill", i)
    for i in range(replicas):
        c._peers[("decode", i)] = _FakePeer("decode", i)
    return c


def _handle_header(uid=0, batch_id="0.0:0"):
    return {"type": "handle", "batch_id": batch_id, "src": 0,
            "reqs": [{"uid": uid}]}


def test_bad_frame_returns_credit_and_replays():
    """A payload-CRC shed must refund the producer's ack credit AND
    replay the named requests — otherwise handoff_depth such events pin
    the prefill worker's window shut forever."""
    c = _bare_cluster()
    pw, dw = c._peers[("prefill", 0)], c._peers[("decode", 0)]
    c.submit(Request(uid=0, tokens=[1, 2], max_new_tokens=4))
    assert len(pw.reqs()) == 1
    c._handle_event(("frame", pw, _handle_header(), b"<frame>"))
    assert dw.sent[-1] == ("bytes", b"<frame>")     # relayed verbatim
    c._handle_event(("frame", dw, {"type": "bad_frame",
                                   "batch_id": "0.0:0", "uids": [0]}, b""))
    assert pw.acks() == [{"type": "ack", "batch_id": "0.0:0"}]
    assert len(pw.reqs()) == 2                       # replayed
    assert pw.reqs()[1]["req"]["uid"] == 0
    assert c.router.batches == {}                    # entry pruned


def test_replica_death_returns_unacked_credits():
    """A decode replica dying while holding forwarded-but-unacked
    batches must refund every pinned credit and replay the uids."""
    c = _bare_cluster(max_restarts=1)
    pw, dw = c._peers[("prefill", 0)], c._peers[("decode", 0)]
    for uid in (0, 1):
        c.submit(Request(uid=uid, tokens=[1 + uid], max_new_tokens=4))
    c._handle_event(("frame", pw, _handle_header(uid=0, batch_id="0.0:0"),
                     b"f0"))
    c._handle_event(("frame", pw, _handle_header(uid=1, batch_id="0.0:1"),
                     b"f1"))
    assert c.router.unacked_batches(0) == ["0.0:0", "0.0:1"]
    c._handle_event(("dead", dw, "killed"))
    assert sorted(a["batch_id"] for a in pw.acks()) == ["0.0:0", "0.0:1"]
    assert c.router.unacked_batches(0) == []
    assert len(pw.reqs()) == 4                       # both uids replayed
    assert c.router.batches == {}


def test_no_replica_sheds_typed_and_returns_credit():
    """Handle arrives with the replica stage gone for good (zero restart
    budget): the uids shed as typed failed_fault completions and the
    batch credit still goes home to the producer."""
    c = _bare_cluster(max_restarts=0)
    pw, dw = c._peers[("prefill", 0)], c._peers[("decode", 0)]
    c._handle_event(("dead", dw, "killed"))          # restart denied
    c.submit(Request(uid=0, tokens=[1], max_new_tokens=4))
    c._handle_event(("frame", pw, _handle_header(), b"f"))
    assert pw.acks() == [{"type": "ack", "batch_id": "0.0:0"}]
    assert c.completions[0].status == FAILED_FAULT
    assert c.router.batches == {}
    assert c.supervisor.stats()["denied"] == 1


def test_stale_check_exempts_peers_until_ready():
    """A worker inside its engine build (hello sent, ready not yet) must
    not be declared stale-dead — a cold jit compile can exceed
    stale_after with no heartbeats, and killing it burns restart budget
    on a healthy process."""
    c = _bare_cluster()
    c.stale_after = 0.0                              # everything is late
    pw = c._peers[("prefill", 0)]
    pw.ready, pw.last_seen = False, 0.0              # mid-build
    c._check_stale()
    assert c._events.empty() and pw.alive
    c._handle_event(("frame", pw, {"type": "ready", "build_s": 1.0}, b""))
    assert pw.ready
    c._check_stale()                                 # now staleness applies
    assert c._events.get_nowait()[0] == "dead"


def test_spawn_passes_incarnation_nonce(monkeypatch, tmp_path):
    """Each respawn of a stage instance gets a fresh incarnation number
    on its argv, so a restarted worker's batch ids ('idx.inc:seq') can
    never collide with a dead incarnation's entries in the router."""
    import progen_tpu.serve.cluster as cluster_mod

    class _FakeProc:
        pid, returncode = 0, None

        def poll(self):
            return None

    cmds = []
    monkeypatch.setattr(cluster_mod.subprocess, "Popen",
                        lambda cmd, **kw: cmds.append(cmd) or _FakeProc())
    c = _bare_cluster()
    c.log_dir, c.port = tmp_path, 1
    c._spec_path = tmp_path / "spec.json"
    from progen_tpu.serve.cluster import ServeCluster
    ServeCluster._spawn(c, "prefill", 0)
    ServeCluster._spawn(c, "prefill", 0)             # the respawn
    ServeCluster._spawn(c, "decode", 0)              # independent counter
    # argv tail is (incarnation, generation); the respawn bumps the
    # nonce but stays pinned to the generation it was created under
    assert [cmd[-2] for cmd in cmds] == ["0", "1", "0"]
    assert [cmd[-1] for cmd in cmds] == ["0", "0", "0"]
    assert c._incarnations == {("prefill", 0): 2, ("decode", 0): 1}


def test_connect_clears_timeout():
    """The connect timeout must not persist on the socket: the reader
    thread blocks in recv() across idle lulls, and an inherited timeout
    would kill the peer after the first quiet minute."""
    import socket as _socket

    from progen_tpu.serve.transport import connect

    lst = _socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    try:
        sock = connect(lst.getsockname()[1], timeout=10.0)
        srv, _ = lst.accept()
        try:
            assert sock.gettimeout() is None
        finally:
            sock.close()
            srv.close()
    finally:
        lst.close()


def test_supervisor_budget_and_crash_loop_guard():
    sup = StageSupervisor(max_restarts=1)
    assert sup.request_restart("prefill", 0, "eof") is True
    assert sup.request_restart("prefill", 0, "eof") is False  # budget spent
    assert sup.request_restart("decode", 0) is True   # per-instance budget
    st = sup.stats()
    assert st["restarts"] == {"prefill:0": 1, "decode:0": 1}
    assert st["denied"] == 1
    loop = StageSupervisor(max_restarts=5, min_interval_s=3600.0)
    assert loop.request_restart("prefill", 1) is True
    assert loop.request_restart("prefill", 1) is False  # crash-looping


# -------------------------------------------------- real 2-process clusters


def _drain_cluster(variant="dense", n=4, **cluster_kw):
    from progen_tpu.serve.cluster import ServeCluster

    cluster = ServeCluster(_spec(variant), **cluster_kw)
    try:
        for r in _requests(n):
            cluster.submit(r)
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    return done, stats


@pytest.mark.parametrize("variant", ["dense", "paged"])
def test_cluster_token_identity(variant):
    """Real subprocess fleet (1 prefill + 1 decode replica): tokens
    identical to the single-process engine, greedy AND sampled, and the
    decode replica never pays prefill wall time."""
    reference = _run_reference(variant)
    done, stats = _drain_cluster(variant)
    assert {c.uid: [int(t) for t in c.tokens]
            for c in done if c.ok} == reference
    assert all(c.ok for c in done)
    dstats = stats["workers"]["decode:0"]
    assert dstats["stage_seconds"]["prefill_s"] == 0.0
    assert dstats["stage_seconds"]["merge_s"] > 0
    assert dstats["stage_seconds"]["decode_chunk_s"] > 0
    assert stats["workers"]["prefill:0"]["stage_seconds"]["prefill_s"] > 0
    tt = stats["transport_total"]
    assert tt["frames_out"] > 0 and tt["bytes_out"] > 0
    assert tt["ser_s"] > 0 and tt["de_s"] > 0
    assert tt["crc_failures"] == 0 and tt["desyncs"] == 0


@pytest.mark.slow  # respawn pays a second worker startup on one core;
                   # the zero-budget shed drill below stays in tier-1
def test_cluster_kill_prefill_worker_replays(tmp_path):
    """Chaos: SIGKILL the only prefill worker mid-run.  With restart
    budget the supervisor respawns it and every request completes OK,
    token-identical (per-request seed determinism makes the replay
    invisible)."""
    from progen_tpu.serve.cluster import ServeCluster

    reference = _run_reference(n=6)
    cluster = ServeCluster(_spec(), supervisor=StageSupervisor(max_restarts=2),
                           log_dir=str(tmp_path))
    try:
        for r in _requests(6):
            cluster.submit(r)
        # kill once the first handle is FORWARDED but before the ack
        # round-trip lets the later batches ship: the worker then still
        # holds queued requests, so the death must be processed (and the
        # respawn must replay them) before the drain can finish — a
        # first-completion trigger can land after all work already left
        # the worker, making the chaos a no-op and the restart assert
        # a race
        while not any(cluster.router.outstanding.values()):
            cluster.poll(0.05)
        assert any(cluster.router.prefill_load.values())
        cluster.kill_worker("prefill", 0)
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    assert len(done) == 6 and all(c.ok for c in done)
    assert {c.uid: [int(t) for t in c.tokens] for c in done} == reference
    assert stats["supervision"]["restarts"].get("prefill:0", 0) >= 1


@pytest.mark.slow  # respawn pays a second decode engine build on one core
def test_cluster_kill_decode_replica_replays(tmp_path):
    """Chaos: SIGKILL the only decode replica once it holds forwarded
    work.  The supervisor respawns it, the router refunds the dead
    replica's unacked batch credits (so the live prefill worker keeps
    producing), and every request completes OK, token-identical."""
    from progen_tpu.serve.cluster import ServeCluster

    reference = _run_reference(n=6)
    cluster = ServeCluster(_spec(), supervisor=StageSupervisor(max_restarts=2),
                           log_dir=str(tmp_path))
    try:
        for r in _requests(6):
            cluster.submit(r)
        # kill only once the replica owns in-flight decode work, so the
        # death always leaves requests to replay (not after they all
        # complete, which would make the chaos a no-op)
        while not any(cluster.router.outstanding.values()):
            cluster.poll(0.05)
        cluster.kill_worker("decode", 0)
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    assert len(done) == 6 and all(c.ok for c in done)
    assert {c.uid: [int(t) for t in c.tokens] for c in done} == reference
    assert stats["supervision"]["restarts"].get("decode:0", 0) >= 1


def test_cluster_decode_stage_down_sheds_typed(tmp_path):
    """Chaos: kill the only decode replica at zero restart budget, then
    submit MORE batches than the prefill credit window (3 batches of
    prefill_batch=2 vs handoff_depth=2).  Every request must come back
    as a typed failed_fault completion — each undeliverable batch's
    credit is refunded, so the prefill worker keeps producing instead
    of pinning its window shut and timing the drain out."""
    from progen_tpu.serve.cluster import ServeCluster

    cluster = ServeCluster(_spec(), supervisor=StageSupervisor(max_restarts=0),
                           log_dir=str(tmp_path))
    try:
        cluster.kill_worker("decode", 0)
        for r in _requests(6):
            cluster.submit(r)
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    assert sorted(c.uid for c in done) == list(range(6))
    assert all(c.status == "failed_fault" for c in done)
    assert stats["supervision"]["denied"] >= 1


def test_cluster_kill_prefill_worker_sheds_typed(tmp_path):
    """Same chaos with a zero restart budget: affected requests come
    back as typed failed_fault COMPLETIONS (exactly once, no raise);
    survivors stay token-identical to the reference."""
    from progen_tpu.serve.cluster import ServeCluster

    reference = _run_reference(n=6)
    cluster = ServeCluster(_spec(), supervisor=StageSupervisor(max_restarts=0),
                           log_dir=str(tmp_path))
    try:
        for r in _requests(6):
            cluster.submit(r)
        while not any(c.ok for c in cluster.completions.values()):
            cluster.poll(0.1)
        cluster.kill_worker("prefill", 0)
        # second wave submitted AFTER the kill: these uids can only
        # resolve once the cluster has processed the death (restart
        # requested -> denied at zero budget -> typed shed), so drain
        # observes the denial path even when the first wave had fully
        # handed off before the SIGKILL landed
        for r in _requests(6, start=6):
            cluster.submit(r)
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    assert len(done) == 12                     # every uid answered once
    assert sorted(c.uid for c in done) == list(range(12))
    ok = [c for c in done if c.ok]
    assert ok, "at least the pre-kill completion must survive"
    for c in ok:
        assert c.uid < 6                       # no prefill stage left
        assert [int(t) for t in c.tokens] == reference[c.uid]
    for c in done:
        if not c.ok:
            assert c.status == "failed_fault"
    assert stats["supervision"]["denied"] >= 1


# ------------------------------------------------------ platform placement


def test_worker_env_carries_no_platform_default(monkeypatch):
    """A worker runs where its parent runs: the cluster hands down
    ``JAX_PLATFORMS`` only when the caller's environment has it, and
    never defaults it to the CPU (on the chip machine that default put
    the workers on the CPU under the parent's "tpu" stamp)."""
    c = _bare_cluster()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    env = c._worker_env()
    assert "JAX_PLATFORMS" not in env
    # single-device runtime + repo on the path are still arranged
    assert "--xla_force_host_platform_device_count=1" in env["XLA_FLAGS"]
    assert env["PYTHONPATH"]
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert c._worker_env()["JAX_PLATFORMS"] == "cpu"


def test_cluster_refuses_worker_processes_on_tpu(monkeypatch):
    """On a TPU the parent that asked JAX for its devices holds every
    chip, so no worker process can start: the cluster refuses up front
    with the counts, instead of hanging in the spawn wait."""
    import types

    import jax

    from progen_tpu.serve import cluster as cl

    chips = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
             for _ in range(4)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    with pytest.raises(RuntimeError) as exc:
        cl.ServeCluster({}, prefill_procs=2, replicas=3)
    msg = str(exc.value)
    assert "5 worker process(es)" in msg and "4 chip(s)" in msg
    assert "0 workers can start" in msg
    # on the CPU (what JAX gives this test process) nothing is refused
    monkeypatch.undo()
    cl._refuse_unplaceable_workers(5)
