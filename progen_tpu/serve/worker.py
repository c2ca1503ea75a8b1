"""Serving worker process: ``python -m progen_tpu.serve.worker``.

One process per stage instance, spawned by :class:`ServeCluster` with
``JAX_PLATFORMS``/``XLA_FLAGS`` pinned so each worker owns its own JAX
runtime (pattern of ``tests/_multihost_worker.py``).  The worker
connects back to the router, says hello, builds its engine from the
JSON spec file, and enters its stage loop:

- ``prefill``: requests in → :meth:`ServingEngine.run_prefill_round` →
  serialized handle frames out, throttled by an ack credit window (the
  replica acks on admission; unacked handles ≤ the engine's
  ``handoff_depth``) so prefilled state never piles up un-merged;
- ``decode``: handle frames in → :func:`deserialize_handle` →
  :meth:`ServingEngine.admit_handle` (``remote_prefill=True``: the
  engine NEVER runs its own prefill — prefill wall leaves this process
  entirely) → completion messages out.

Every process builds bit-identical params from the same spec (same
init seed, same jit recipe — or the same checkpoint), so handles made
by any worker merge into any replica and trajectories depend only on
(params, prime, seed, knobs): placement is invisible in the tokens.

A payload-CRC-corrupt handle frame is reported home as a typed
``bad_frame`` message (the router replays the named requests); a
desynced stream ends the process, and stage supervision restarts it.

A decode replica may also be a multi-process TENSOR-PARALLEL GROUP
(``PROGEN_TPU_TP_GROUP_*`` env vars, docs/SERVING.md §13): member 0 is
the leader (role ``decode``), members 1..G-1 are followers (role
``dshard<k>``, same replica index).  The group forms a private
``jax.distributed`` job whose engine runs under a process-spanning
``tensor=G`` mesh; every collective-bearing step is driven in lockstep
by a leader-broadcast plan so the members' jax programs always agree.
"""

from __future__ import annotations

import json
import os
import queue as _queue
import sys
import time
from collections import deque


def make_spec(config, *, mixed_precision: bool = True, init_seed: int = 0,
              checkpoint_path: str | None = None,
              engine: dict | None = None,
              heartbeat_s: float = 1.0, trace: dict | None = None,
              statusz: bool = False, lora: dict | None = None,
              aot_warmup: bool = False,
              warmup_max_prime: int | None = None) -> dict:
    """Build the JSON-able worker spec.  ``engine`` holds
    :class:`ServingEngine` kwargs (slots/chunk/paged/...,
    including ``quantize`` — every worker built from the spec quantizes
    the same full-precision init/checkpoint tree, so int8 replicas stay
    bit-identical to each other); ``disagg`` is implied.  Params come from ``checkpoint_path`` when
    set, else from ``jit(model.init)(key(init_seed))`` — identical in
    every process either way.  ``trace`` (``{"dir": ..., "capacity"?}``)
    enables span tracing in every worker; each dumps its ring to
    ``trace_<role>_<index>.json`` in that directory at exit
    (docs/OBSERVABILITY.md).  ``statusz=True`` starts a loopback
    introspection server in every process (driver included) on an
    ephemeral port; workers report their port in the hello frame and the
    driver surfaces the map on its own /statusz.

    ``lora`` (``{"tenants": T, "rank": R, "seed"?, "scale"?}``) gives the
    worker a deterministic adapter bank built with
    :func:`~progen_tpu.workloads.lora.random_lora_bank` — bit-identical
    in every process, so multi-tenant handles merge into any replica of
    the same spec.  ``aot_warmup=True`` makes the worker compile its
    whole program grid BEFORE sending its ready frame (warm-before-
    routable: the control plane only routes to workers that answered
    ready, so a scaled-up worker never eats cold compiles on live
    traffic); ``warmup_max_prime`` caps the bucket sweep."""
    spec = {
        "config": config.to_dict(),
        "mixed_precision": bool(mixed_precision),
        "init_seed": int(init_seed),
        "checkpoint_path": checkpoint_path,
        "engine": dict(engine or {}),
        "heartbeat_s": float(heartbeat_s),
    }
    if trace:
        spec["trace"] = dict(trace)
    if statusz:
        spec["statusz"] = True
    if lora:
        spec["lora"] = dict(lora)
    if aot_warmup:
        spec["aot_warmup"] = True
        if warmup_max_prime is not None:
            spec["warmup_max_prime"] = int(warmup_max_prime)
    return spec


def build_engine_from_spec(spec: dict, *, remote_prefill: bool = False,
                           group_size: int = 1):
    """Construct the ServingEngine a worker spec describes — also used
    by tests/benches to build the in-process REFERENCE engine with the
    exact same param recipe, making token-identity a hard assert.

    ``group_size > 1`` builds the TP-GROUP flavor: the engine runs under
    a process-spanning ``tensor=group_size`` mesh (one device per member
    process) with the ``tp`` rule set, and the bit-identical per-process
    param tree is placed as global arrays before construction.  Every
    member calls this with the same spec, so the group's params — like a
    single-process replica's — depend only on (init seed | checkpoint).
    """
    import jax
    import jax.numpy as jnp

    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import ServingEngine
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.parallel import unbox

    cfg = ProGenConfig.from_dict(spec["config"])
    policy = make_policy(bool(spec.get("mixed_precision", True)))
    model = ProGen(config=cfg, policy=policy)
    toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
    if spec.get("checkpoint_path"):
        from progen_tpu.checkpoint import CheckpointStore, abstract_params_like

        store = CheckpointStore(spec["checkpoint_path"])
        params = {"params": store.restore_params(
            abstract_params_like(model, toks))}
        store.close()
    else:
        params = unbox(jax.jit(model.init)(
            jax.random.key(int(spec.get("init_seed", 0))), toks))
    kw = dict(spec.get("engine", {}))
    kw["disagg"] = True
    if spec.get("lora"):
        # spec-driven bank: random_lora_bank is deterministic per seed,
        # so every process rebuilds the SAME adapters (like init params)
        from progen_tpu.workloads.lora import random_lora_bank

        lcfg = spec["lora"]
        kw["lora_bank"] = random_lora_bank(
            cfg, int(lcfg["tenants"]), int(lcfg["rank"]),
            seed=int(lcfg.get("seed", 0)),
            scale=float(lcfg.get("scale", 1e-2)))
    if group_size > 1:
        import numpy as np

        from progen_tpu.core.mesh import MeshConfig, make_mesh
        from progen_tpu.parallel.sharding import (
            param_shardings,
            validate_tp_divisibility,
        )

        strategies = ("tp",)
        validate_tp_divisibility(cfg, group_size, strategies=strategies)
        mesh = make_mesh(MeshConfig(data=1, fsdp=1, tensor=group_size,
                                    seq=1))
        shardings = param_shardings(model, toks, mesh, strategies)

        def _place(leaf, sharding):
            # every member holds the full leaf; hand each device its
            # slice so placement needs no cross-process resharding
            host = np.asarray(leaf)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])

        params = jax.tree_util.tree_map(_place, params, shardings)
        kw["mesh"], kw["strategies"] = mesh, strategies
    return ServingEngine(cfg, params, policy=policy,
                         remote_prefill=remote_prefill, **kw)


def _completion_to_wire(c) -> dict:
    msg = {
        "type": "completion",
        "uid": c.uid,
        "prime": [int(t) for t in c.prime],
        "tokens": [int(t) for t in c.tokens],
        "finish_reason": c.finish_reason,
        "status": c.status,
        "worker_latency": float(c.latency),
    }
    if c.embedding is not None:
        msg["embedding"] = [float(x) for x in c.embedding]
    return msg


def _drain_inbox(inbox, *, timeout: float):
    """Pull every queued event (blocking up to ``timeout`` for the
    first); returns (messages, router_dead)."""
    out = []
    t = timeout
    while True:
        try:
            item = inbox.get(timeout=t)
        except _queue.Empty:
            return out, False
        t = 0.0
        if item[0] == "dead":
            return out, True
        out.append((item[2], item[3]))  # (header, frame)


def _stats_frame(eng, counters, **extra) -> dict:
    """One stats/metrics frame, sent both as the final flush and in reply
    to a mid-run ``stats_req`` (the drain-time freshness fix): the worker
    echoes its clock so the driver can stamp the snapshot's capture age."""
    from progen_tpu.observe.metrics import get_registry

    msg = {"type": "stats",
           "clock": time.perf_counter(),
           "stage_seconds": eng.stage_seconds,
           "transport": counters.as_dict(),
           "chunks_run": eng.chunks_run,
           # every role reports its robustness + QoS tallies: prefill
           # workers own the cluster's scheduling queues, so their
           # per-class/per-tenant counters are the fleet QoS view
           "robust": eng.robustness_counters(),
           "metrics": get_registry().snapshot()}
    dig = eng.prefix_digest()
    if dig is not None:
        # cache advertisement rides the stats frame too, so a drain-time
        # flush leaves the router's digest table current
        msg["digest"] = dig
    msg.update(extra)
    return msg


def _prefill_loop(eng, peer, inbox, counters, *, heartbeat_s: float,
                  window: int, incarnation: int = 0,
                  generation: int = 0) -> None:
    from progen_tpu.decode.handoff import (
        request_from_wire,
        serialize_handle,
    )
    from progen_tpu.observe.metrics import get_registry
    from progen_tpu.observe.trace import get_tracer

    tracer = get_tracer()
    unacked: set = set()
    batch_seq = 0
    running = True
    stall_t0 = None  # opened when prefill is blocked on ack credits
    last_hb = time.perf_counter()
    while running or eng.pending:
        idle = not (eng.pending and len(unacked) < window)
        msgs, dead = _drain_inbox(inbox, timeout=0.1 if idle else 0.0)
        if dead:
            return
        for header, _ in msgs:
            t = header.get("type")
            if t == "req":
                eng.submit(request_from_wire(
                    header["req"], vocab=eng.config.num_tokens))
            elif t == "embed_req":
                eng.submit_embed(request_from_wire(
                    header["req"], vocab=eng.config.num_tokens))
            elif t == "ack":
                unacked.discard(header.get("batch_id"))
            elif t == "shutdown":
                running = False
            elif t == "stats_req":
                peer.send_json(_stats_frame(eng, counters))
        # embed traffic shares this worker's prefill-shaped programs but
        # needs no ack credits — completions ship straight home
        while eng.embed_pending:
            eng.run_embed_round()
            for c in eng.drain_sheds():
                peer.send_json(_completion_to_wire(c))
        if eng.pending and len(unacked) >= window:
            if stall_t0 is None:
                stall_t0 = time.perf_counter()
        elif stall_t0 is not None:
            now = time.perf_counter()
            tracer.add("worker.credit_stall", stall_t0, now - stall_t0,
                       queue=eng.pending)
            stall_t0 = None
        for c in eng.drain_sheds():
            peer.send_json(_completion_to_wire(c))
        while eng.pending and len(unacked) < window:
            before = eng.pending
            h = eng.run_prefill_round()
            for c in eng.drain_sheds():
                peer.send_json(_completion_to_wire(c))
            if h is not None:
                # the incarnation nonce keeps a respawned worker's ids
                # (batch_seq restarts at 0) distinct from any the dead
                # incarnation left in the router's bookkeeping
                batch_id = f"{peer.index}.{incarnation}:{batch_seq}"
                batch_seq += 1
                frame = serialize_handle(
                    h, counters=counters,
                    extra_header={"batch_id": batch_id,
                                  "src": peer.index,
                                  "generation": generation,
                                  "trace_ctx": {
                                      "clock": time.perf_counter(),
                                      "src_proc": f"prefill:{peer.index}"}})
                unacked.add(batch_id)
                peer.send_bytes(frame)
                # handed-off requests are harvested by a decode replica,
                # never here — drop their first-token stamps
                eng.forget_ttft(r.uid for r in h.requests)
            elif eng.pending >= before:
                break  # no progress (should not happen; avoid spinning)
        now = time.perf_counter()
        if now - last_hb >= heartbeat_s:
            last_hb = now
            peer.send_json({
                "type": "hb", "queue": eng.pending,
                "unacked": len(unacked),
                "clock": now,
                "stage_seconds": eng.stage_seconds,
                "metrics": get_registry().snapshot()})
    peer.send_json(_stats_frame(eng, counters))


def _decode_loop(eng, peer, inbox, counters, *, heartbeat_s: float) -> None:
    from progen_tpu.decode.handoff import FrameCorrupt, deserialize_handle
    from progen_tpu.observe.metrics import get_registry
    from progen_tpu.observe.trace import get_tracer

    tracer = get_tracer()
    backlog: deque = deque()  # [header, frame, handle|None, recv_clock]
    running = True
    max_backlog = 0
    last_hb = time.perf_counter()
    while running or eng.has_work or backlog:
        idle = not (eng.has_work or backlog)
        msgs, dead = _drain_inbox(inbox, timeout=0.1 if idle else 0.0)
        if dead:
            return
        for header, frame in msgs:
            t = header.get("type")
            if t == "handle":
                backlog.append([header, frame, None, time.perf_counter()])
                max_backlog = max(max_backlog, len(backlog))
            elif t == "shutdown":
                running = False
            elif t == "stats_req":
                peer.send_json(_stats_frame(
                    eng, counters, max_handoff_backlog=max_backlog))
        while backlog:
            entry = backlog[0]
            if entry[2] is None:
                try:
                    entry[2] = deserialize_handle(entry[1],
                                                  counters=counters)
                except FrameCorrupt:
                    counters.crc_failures += 1
                    backlog.popleft()
                    peer.send_json({
                        "type": "bad_frame",
                        "batch_id": entry[0].get("batch_id"),
                        "uids": [d["uid"]
                                 for d in entry[0].get("reqs", [])]})
                    continue
            if not eng.admit_handle(entry[2]):
                break  # handoff at depth: step() below frees it
            backlog.popleft()
            # queue-wait: frame receipt -> successful admission, tagged
            # with the uids the handle header names
            now = time.perf_counter()
            tracer.add("worker.queue_wait", entry[3], now - entry[3],
                       uids=[d["uid"] for d in entry[0].get("reqs", [])],
                       batch_id=entry[0].get("batch_id"))
            peer.send_json({"type": "ack",
                            "batch_id": entry[0].get("batch_id")})
        if eng.has_work:
            for c in eng.step():
                peer.send_json(_completion_to_wire(c))
        now = time.perf_counter()
        if now - last_hb >= heartbeat_s:
            last_hb = now
            hb_msg = {
                "type": "hb", "inflight": eng.num_active,
                "handoff_backlog": len(backlog),
                "clock": now,
                "stage_seconds": eng.stage_seconds,
                "metrics": get_registry().snapshot()}
            dig = eng.prefix_digest()
            if dig is not None:
                hb_msg["digest"] = dig
            peer.send_json(hb_msg)
    peer.send_json(_stats_frame(eng, counters,
                                max_handoff_backlog=max_backlog))


# --- tp-group lockstep ------------------------------------------------
#
# A tp-group engine's jitted programs are collectives: every member must
# issue the SAME sequence of admit/step calls or the group deadlocks.
# The engine itself is deterministic — identical inputs in identical
# order produce identical host state on every member — so only the
# leader's nondeterministic inputs (which handle frames arrived, and
# whether shutdown was requested) need broadcasting.  Each loop
# iteration the leader publishes a tiny JSON plan; everything after it
# is deterministic replay.

_PLAN_BYTES = 16384  # fixed-size plan buffer (collectives need one shape)


def _group_plan_exchange(plan: dict | None) -> dict:
    """Leader→members broadcast of one lockstep plan dict.  Followers
    pass ``None``; every member returns the leader's plan."""
    import numpy as np
    from jax.experimental import multihost_utils

    buf = np.zeros(_PLAN_BYTES, np.uint8)
    if plan is not None:
        raw = json.dumps(plan).encode()
        if len(raw) >= _PLAN_BYTES:
            raise ValueError(
                f"tp-group plan overflows {_PLAN_BYTES}B: {len(raw)}B")
        buf[:len(raw)] = np.frombuffer(raw, np.uint8)
    # the broadcast's internal psum promotes uint8; narrow back before
    # reinterpreting the element buffer as the JSON byte string
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf),
                     dtype=np.uint8)
    return json.loads(bytes(out).rstrip(b"\x00").decode())


def _group_all_ok(flag: bool) -> bool:
    """Group consensus: True iff EVERY member voted True."""
    import numpy as np
    from jax.experimental import multihost_utils

    votes = multihost_utils.process_allgather(
        np.asarray([1 if flag else 0], np.int32))
    return bool(np.asarray(votes).min() > 0)


def _claim_slab(slabs: dict, batch_id: str, inbox, eng, peer, counters,
                *, deadline_s: float = 120.0):
    """Take ``batch_id``'s slab frame, waiting for late delivery.

    The leader only announces batch ids it has already received, but a
    follower's slab rides a separate TCP stream and may trail the plan
    broadcast.  Returns ``[header, frame, recv_clock]`` or None when the
    router died; a slab that never arrives is a wiring bug, not a
    transient — raise rather than desync the group."""
    deadline = time.perf_counter() + deadline_s
    while batch_id not in slabs:
        msgs, dead = _drain_inbox(inbox, timeout=0.2)
        if dead:
            return None
        for header, frame in msgs:
            t = header.get("type")
            if t == "handle":
                slabs[header.get("batch_id")] = [
                    header, frame, time.perf_counter()]
            elif t == "stats_req":
                peer.send_json(_stats_frame(eng, counters))
            # shutdown is leader-planned; a follower's copy is ignored
        if time.perf_counter() > deadline:
            raise RuntimeError(
                f"tp-group slab for batch {batch_id!r} never arrived")
    return slabs.pop(batch_id)


def _group_decode_loop(eng, peer, inbox, counters, *, heartbeat_s: float,
                       group_rank: int, group_size: int) -> None:
    """Decode loop for one member of a tp-group replica.

    Mirrors :func:`_decode_loop` exactly — same admit-then-step order,
    same at-depth backpressure — but frame arrival and shutdown flow
    through the leader's plan, deserialization verdicts take a group
    vote (a frame only enters the engine when EVERY member could parse
    its slab), and only the leader speaks results (ack / bad_frame /
    completion) to the router.  Heartbeats and stats stay per-member:
    the driver supervises each process independently."""
    from progen_tpu.decode.handoff import (
        FrameCorrupt,
        deserialize_handle_sharded,
    )
    from progen_tpu.observe.metrics import get_registry
    from progen_tpu.observe.trace import get_tracer

    leader = group_rank == 0
    tracer = get_tracer()
    backlog: deque = deque()  # [header, frame, handle|None, recv_clock]
    slabs: dict = {}          # batch_id -> [header, frame, recv_clock]
    announce: list = []       # leader: arrived, not yet planned
    running = True
    max_backlog = 0
    last_hb = time.perf_counter()
    while running or eng.has_work or backlog:
        idle = not (eng.has_work or backlog)
        msgs, dead = _drain_inbox(inbox, timeout=0.1 if idle else 0.0)
        if dead:
            return
        for header, frame in msgs:
            t = header.get("type")
            if t == "handle":
                bid = header.get("batch_id")
                slabs[bid] = [header, frame, time.perf_counter()]
                if leader:
                    announce.append(bid)
            elif t == "shutdown":
                if leader:
                    running = False
            elif t == "stats_req":
                peer.send_json(_stats_frame(
                    eng, counters, max_handoff_backlog=max_backlog,
                    group_rank=group_rank, group_size=group_size))
        plan = _group_plan_exchange(
            {"admit": announce, "running": running} if leader else None)
        running = bool(plan["running"])
        announce = []
        for bid in plan["admit"]:
            entry = _claim_slab(slabs, bid, inbox, eng, peer, counters)
            if entry is None:
                return
            backlog.append([entry[0], entry[1], None, entry[2]])
            max_backlog = max(max_backlog, len(backlog))
        while backlog:
            entry = backlog[0]
            if entry[2] is None:
                try:
                    handle = deserialize_handle_sharded(
                        entry[1], eng.mesh, counters=counters)
                    ok = True
                except FrameCorrupt:
                    handle, ok = None, False
                if not _group_all_ok(ok):
                    # some member's slab was corrupt: the whole group
                    # drops the batch so engine states stay identical
                    if not ok:
                        counters.crc_failures += 1
                    backlog.popleft()
                    if leader:
                        peer.send_json({
                            "type": "bad_frame",
                            "batch_id": entry[0].get("batch_id"),
                            "uids": [d["uid"]
                                     for d in entry[0].get("reqs", [])]})
                    continue
                entry[2] = handle
            if not eng.admit_handle(entry[2]):
                break  # handoff at depth: step() below frees it
            backlog.popleft()
            now = time.perf_counter()
            tracer.add("worker.queue_wait", entry[3], now - entry[3],
                       uids=[d["uid"] for d in entry[0].get("reqs", [])],
                       batch_id=entry[0].get("batch_id"))
            if leader:
                peer.send_json({"type": "ack",
                                "batch_id": entry[0].get("batch_id")})
        if eng.has_work:
            for c in eng.step():
                if leader:
                    peer.send_json(_completion_to_wire(c))
        now = time.perf_counter()
        if now - last_hb >= heartbeat_s:
            last_hb = now
            hb_msg = {
                "type": "hb", "inflight": eng.num_active,
                "handoff_backlog": len(backlog),
                "clock": now,
                "stage_seconds": eng.stage_seconds,
                "metrics": get_registry().snapshot()}
            if leader:
                dig = eng.prefix_digest()
                if dig is not None:
                    hb_msg["digest"] = dig
            peer.send_json(hb_msg)
    peer.send_json(_stats_frame(eng, counters,
                                max_handoff_backlog=max_backlog,
                                group_rank=group_rank,
                                group_size=group_size))


def main(argv) -> int:
    role, index, port, spec_path = (
        argv[0], int(argv[1]), int(argv[2]), argv[3])
    incarnation = int(argv[4]) if len(argv) > 4 else 0
    generation = int(argv[5]) if len(argv) > 5 else 0
    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()
    # tp-group membership (docs/SERVING.md §13): the G member processes
    # of one decode replica form a private jax.distributed job.  Must
    # initialize BEFORE anything touches the backend.
    group_size = int(os.environ.get("PROGEN_TPU_TP_GROUP_SIZE", "1"))
    group_rank = int(os.environ.get("PROGEN_TPU_TP_GROUP_RANK", "0"))
    if group_size > 1:
        import jax

        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address="localhost:{}".format(
                int(os.environ["PROGEN_TPU_TP_GROUP_PORT"])),
            num_processes=group_size,
            process_id=group_rank)
    with open(spec_path) as fh:
        spec = json.load(fh)

    from progen_tpu.observe.trace import (
        configure_tracing,
        get_tracer,
        trace_dump_path,
    )
    from progen_tpu.observe.transport import TransportCounters
    from progen_tpu.serve.transport import Peer, connect

    tcfg = spec.get("trace")
    if tcfg:
        configure_tracing(enabled=True,
                          capacity=tcfg.get("capacity"),
                          process=f"{role}:{index}")

    counters = TransportCounters()

    # the introspection server comes up BEFORE the engine build so
    # /healthz answers (phase "building") during a minutes-long cold jit;
    # its port rides the hello frame for the driver's endpoint map
    statusz_srv = None
    holder: dict = {"phase": "connecting"}
    if spec.get("statusz"):
        from progen_tpu.observe.statusz import StatuszServer

        def _health():
            out = {"phase": holder["phase"],
                   "transport": counters.as_dict()}
            eng_ = holder.get("eng")
            if eng_ is not None:
                out["pending"] = eng_.pending
                out["active"] = eng_.num_active
            return out

        def _status():
            eng_ = holder.get("eng")
            return (eng_.status() if eng_ is not None
                    else {"phase": holder["phase"]})

        statusz_srv = StatuszServer(
            role=role, index=index,
            providers={"health": _health, "status": _status})
        statusz_srv.start()

    sock = connect(port)
    peer = Peer(sock, counters)
    peer.role, peer.index = role, index
    # the clock echo lets the driver estimate this process's perf_counter
    # offset, so merged trace timelines are causally ordered
    hello = {"type": "hello", "role": role, "index": index,
             "generation": generation,
             "clock": time.perf_counter()}
    if statusz_srv is not None:
        hello["statusz_port"] = statusz_srv.port
    peer.send_json(hello)

    print(f"worker {role}:{index} building engine", flush=True)
    holder["phase"] = "building"
    t0 = time.perf_counter()
    eng = build_engine_from_spec(
        spec,
        remote_prefill=(role == "decode" or role.startswith("dshard")),
        group_size=group_size)
    eng.generation = generation
    warm = {}
    if spec.get("aot_warmup"):
        # warm-before-routable: the ready frame is what makes a
        # scaled-up worker placeable, so every compile lands before it
        holder["phase"] = "warming"
        warm = eng.aot_warmup(max_prime=spec.get("warmup_max_prime"))
    if group_size > 1:
        # group barrier before ANY member reports ready: the leader's
        # ready frame means the whole replica can run collectives
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("progen_tpu_tp_group_ready")
    print(f"worker {role}:{index} engine ready in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    holder["eng"] = eng
    holder["phase"] = "serving"
    ready = {"type": "ready", "build_s": time.perf_counter() - t0,
             "generation": generation}
    if warm:
        ready["warmup"] = warm
    peer.send_json(ready)

    inbox: _queue.Queue = _queue.Queue()
    peer.start_reader(inbox)
    hb = float(spec.get("heartbeat_s", 1.0))
    if role == "prefill":
        window = max(1, int(spec.get("engine", {}).get("handoff_depth", 2)))
        _prefill_loop(eng, peer, inbox, counters,
                      heartbeat_s=hb, window=window,
                      incarnation=incarnation, generation=generation)
    elif group_size > 1:
        _group_decode_loop(eng, peer, inbox, counters, heartbeat_s=hb,
                           group_rank=group_rank, group_size=group_size)
    else:
        _decode_loop(eng, peer, inbox, counters, heartbeat_s=hb)
    if tcfg and tcfg.get("dir"):
        try:
            get_tracer().dump(
                trace_dump_path(tcfg["dir"], f"{role}:{index}"))
        except OSError as e:
            print(f"worker {role}:{index} trace dump failed: {e}",
                  file=sys.stderr, flush=True)
    print(f"worker {role}:{index} exiting", flush=True)
    if statusz_srv is not None:
        statusz_srv.stop()
    peer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
