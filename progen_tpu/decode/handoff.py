"""Prefill→decode handoff: cache handles over a bounded queue.

Disaggregated serving (docs/SERVING.md §6) splits the engine's step into
a PREFILL stage and a DECODE stage on the same mesh.  The prefill worker
runs the bucketed parallel prefill as its own jit program and produces a
:class:`Handle`: a self-contained slab of per-row decode state (caches,
sequence row with the first sampled token, position/stop/key/sampling
knobs) for up to ``prefill_batch`` requests, shaped ``(num_slots, ...)``
so the decode pool's merge program can DONATE it — the handed-off cache
buffers move into the slot state instead of being copied.

The queue between the stages is BOUNDED (``handoff_depth`` handles): a
full queue skips the prefill round (backpressure — prefilled state is
the expensive thing to hold), while :meth:`HandoffQueue.requeue` puts a
handle back at the FRONT after a transiently failed merge without
counting against the bound (the handle was already admitted once; a
crash-replay loop must not deadlock against its own backpressure).

The :class:`HandoffQueue` is pure host-side bookkeeping between
dispatches — handles carry device arrays, but nothing in the queue may
force a sync (enforced by a graftcheck host-sync zone, like
``decode/paging.py``).  The module-level ``serialize_handle`` /
``deserialize_handle`` functions below are the opposite: they ARE the
cross-process transport (docs/SERVING.md §7) and sync by design
(``device_get`` on send, ``device_put`` on receive) — they run on
transport threads, never on the admission path, and are deliberately
OUTSIDE the host-sync zone.

Wire format (one handle = one frame)::

    <4sHHIQII> prefix (28 bytes, little-endian):
        magic  b"PGHF" | version u16 | reserved u16
        header_len u32 | payload_len u64
        header_crc u32 | payload_crc u32 (zlib.crc32)
    header: UTF-8 JSON — request rows, p_pad, and a manifest of
        (path, dtype, shape, offset, nbytes) per state leaf
    payload: the raw array bytes, concatenated at manifest offsets

A payload CRC mismatch raises :class:`FrameCorrupt` — the prefix and
header survived, so the stream is still framed and the router can shed
or replay exactly the requests named in the header.  A bad magic /
version / truncated read raises :class:`FrameDesync` — the stream can
no longer be trusted and the connection is poisoned (the supervisor
restarts the stage).  Both are typed: a corrupt frame sheds, never
crashes.
"""

from __future__ import annotations

import dataclasses
import json
import struct
import time
import zlib
from collections import deque
from typing import Any, Sequence

from progen_tpu.observe import trace as _trace


@dataclasses.dataclass
class Handle:
    """One prefill worker product awaiting decode admission.

    ``requests``: the admitted requests in row order (row ``i`` of the
    state slabs belongs to ``requests[i]``; later rows are dummy).
    ``state``: device arrays, ``(num_slots, ...)``-shaped — seq, caches
    (dense gate rows even in paged mode; the merge scatters them into
    the pool), pos/start/stop/done/keys/top_k/temp.  ``p_pad``: the
    prefill bucket that produced it (observability; the merge program is bucket-agnostic).
    """

    requests: list
    state: dict[str, Any]
    p_pad: int


class HandoffQueue:
    """Bounded FIFO of :class:`Handle`\\ s between the serving stages."""

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"handoff depth must be >= 1, got {depth}")
        self.depth = depth
        self._q: deque[Handle] = deque()
        self.puts = 0
        self.gets = 0
        self.rejects = 0

    def full(self) -> bool:
        return len(self._q) >= self.depth

    def put(self, handle: Handle) -> bool:
        """Append; False (and a ``rejects`` tick) when at depth — the
        caller should have checked :meth:`full` before paying for the
        prefill, so a reject indicates lost work."""
        if self.full():
            self.rejects += 1
            return False
        self._q.append(handle)
        self.puts += 1
        return True

    def requeue(self, handle: Handle) -> None:
        """Return a handle to the FRONT (failed merge retry path); not
        depth-bounded, see module docstring."""
        self._q.appendleft(handle)

    def get(self) -> Handle:
        self.gets += 1
        return self._q.popleft()

    def peek(self) -> Handle:
        return self._q[0]

    def num_requests(self) -> int:
        """Requests captured in queued handles (snapshot accounting)."""
        return sum(len(h.requests) for h in self._q)

    def __len__(self) -> int:
        return len(self._q)

    def __iter__(self):
        return iter(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)

    def stats(self) -> dict:
        return {"depth": self.depth, "queued": len(self._q),
                "puts": self.puts, "gets": self.gets,
                "rejects": self.rejects}


# --------------------------------------------------------------- wire format
#
# Transport layer: everything below may sync (device_get / device_put);
# it runs on transport threads only — see module docstring.

FRAME_MAGIC = b"PGHF"
FRAME_VERSION = 1
_PREFIX = struct.Struct("<4sHHIQII")
FRAME_PREFIX_LEN = _PREFIX.size  # 28


class FrameError(Exception):
    """A frame failed to decode.  Never escapes the serving runtime as a
    crash: subclasses pick the recovery (shed vs restart)."""


class FrameCorrupt(FrameError):
    """Payload CRC mismatch with an intact prefix+header: the stream is
    still framed — shed/replay the requests named in the header and keep
    the connection."""

    def __init__(self, msg: str, header: dict | None = None):
        super().__init__(msg)
        self.header = header


class FrameDesync(FrameError):
    """Bad magic/version, header corruption, or a truncated read: the
    byte stream can no longer be trusted — poison the connection and let
    stage supervision restart the peer."""


def pack_frame(header: dict, payload_parts: Sequence = ()) -> bytes:
    """Assemble one wire frame from a JSON-able header and raw payload
    parts (bytes-likes, concatenated in order)."""
    hdr = json.dumps(header, separators=(",", ":")).encode()
    parts = [memoryview(p).cast("B") for p in payload_parts]
    payload_len = sum(p.nbytes for p in parts)
    payload_crc = 0
    for p in parts:
        payload_crc = zlib.crc32(p, payload_crc)
    out = bytearray(_PREFIX.size + len(hdr) + payload_len)
    _PREFIX.pack_into(out, 0, FRAME_MAGIC, FRAME_VERSION, 0, len(hdr),
                      payload_len, zlib.crc32(hdr), payload_crc)
    out[_PREFIX.size:_PREFIX.size + len(hdr)] = hdr
    off = _PREFIX.size + len(hdr)
    for p in parts:
        out[off:off + p.nbytes] = p
        off += p.nbytes
    return bytes(out)


def parse_prefix(prefix: bytes) -> tuple[int, int, int, int]:
    """Validate a 28-byte frame prefix; returns ``(header_len,
    payload_len, header_crc, payload_crc)``.  :class:`FrameDesync` on a
    short read, bad magic, or unknown version."""
    if len(prefix) < _PREFIX.size:
        raise FrameDesync(
            f"truncated frame prefix: {len(prefix)} < {_PREFIX.size} bytes")
    magic, version, _, hlen, plen, hcrc, pcrc = _PREFIX.unpack_from(prefix)
    if magic != FRAME_MAGIC:
        raise FrameDesync(f"bad frame magic {magic!r}")
    if version != FRAME_VERSION:
        raise FrameDesync(f"unsupported frame version {version}")
    return hlen, plen, hcrc, pcrc


def unpack_frame(buf) -> tuple[dict, memoryview]:
    """Split one complete frame back into ``(header, payload_view)``.

    ``payload_view`` is a zero-copy view into ``buf``.  Raises
    :class:`FrameDesync` (untrustworthy stream) or :class:`FrameCorrupt`
    (payload CRC with a good header — ``.header`` names the casualties).
    """
    view = memoryview(buf).cast("B")
    hlen, plen, hcrc, pcrc = parse_prefix(bytes(view[:_PREFIX.size]))
    end = _PREFIX.size + hlen + plen
    if view.nbytes < end:
        raise FrameDesync(
            f"truncated frame: have {view.nbytes} bytes, need {end}")
    hdr_bytes = view[_PREFIX.size:_PREFIX.size + hlen]
    if zlib.crc32(hdr_bytes) != hcrc:
        raise FrameDesync("frame header CRC mismatch")
    try:
        header = json.loads(bytes(hdr_bytes))
    except ValueError as e:
        raise FrameDesync(f"frame header is not JSON: {e}") from e
    payload = view[_PREFIX.size + hlen:end]
    if zlib.crc32(payload) != pcrc:
        raise FrameCorrupt("frame payload CRC mismatch", header=header)
    return header, payload


def _flatten_state(state, prefix: str = "") -> list:
    """Deterministic (sorted-key, '/'-joined path) flatten of a handle
    state tree into ``[(path, leaf), ...]``.  List/tuple nodes (e.g.
    per-layer cache stacks) use ``#i``/``@i`` index segments so the
    receiver rebuilds the exact container types."""
    out = []
    if isinstance(state, dict):
        items = [(str(k), state[k]) for k in sorted(state)]
    elif isinstance(state, (list, tuple)):
        marker = "#" if isinstance(state, list) else "@"
        items = [(f"{marker}{i}", v) for i, v in enumerate(state)]
    else:
        raise TypeError(f"unsupported state node {type(state).__name__}")
    for k, v in items:
        path = f"{prefix}{k}"
        if isinstance(v, (dict, list, tuple)):
            out.extend(_flatten_state(v, prefix=path + "/"))
        else:
            out.append((path, v))
    return out


def _unflatten_state(pairs) -> dict:
    tree: dict = {}
    for path, leaf in pairs:
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return _rebuild_containers(tree)


def _rebuild_containers(node):
    """Turn ``#i``/``@i``-keyed dicts from :func:`_unflatten_state` back
    into lists/tuples, depth-first."""
    if not isinstance(node, dict):
        return node
    rebuilt = {k: _rebuild_containers(v) for k, v in node.items()}
    if rebuilt and all(k[:1] in "#@" and k[1:].isdigit() for k in rebuilt):
        marker = next(iter(rebuilt))[0]
        seq = [rebuilt[f"{marker}{i}"] for i in range(len(rebuilt))]
        return tuple(seq) if marker == "@" else seq
    return rebuilt


def request_to_wire(r, *, now: float | None = None) -> dict:
    """Host-side request row for a frame header.  ``perf_counter``
    instants don't cross processes, so an absolute deadline travels as
    its REMAINING budget (mirrors ``ServingEngine._snap_request``)."""
    if now is None:
        now = time.perf_counter()
    entry = {
        "uid": r.uid,
        "tokens": [int(t) for t in r.tokens],
        "max_new_tokens": int(r.max_new_tokens),
        "top_k": None if r.top_k is None else int(r.top_k),
        "temperature": float(r.temperature),
        "seed": int(r.seed),
        # trace context: the per-request trace id (its uid) plus the
        # sender's clock instant, so the receiving process can attribute
        # queue-wait to this request on an offset-corrected timeline
        # (docs/OBSERVABILITY.md)
        "trace": {"id": r.uid, "clock": now},
    }
    if getattr(r, "logit_mask", None) is not None:
        from progen_tpu.workloads.infill import mask_to_wire
        entry["logit_mask"] = mask_to_wire(r.logit_mask)
    tenant = int(getattr(r, "tenant", 0))
    if tenant != 0:
        entry["tenant"] = tenant
    priority = int(getattr(r, "priority", 0))
    if priority != 0:
        entry["priority"] = priority
    deadline = r.deadline
    if deadline is None and r.ttl is not None:
        deadline = r.submit_time + r.ttl
    if deadline is not None:
        entry["deadline_remaining"] = max(0.0, deadline - now)
    return entry


def request_from_wire(d: dict, *, now: float | None = None,
                      on_complete=None, vocab: int | None = None):
    """Rebuild a :class:`~progen_tpu.decode.engine.Request` in the
    receiving process; the deadline resumes from its remaining budget.
    ``vocab`` sizes a decoded infill mask (required when one rides)."""
    from progen_tpu.decode.engine import Request

    if now is None:
        now = time.perf_counter()
    lmask = None
    if d.get("logit_mask") is not None:
        if vocab is None:
            raise ValueError("request carries a logit_mask but the "
                             "receiver passed no vocab size")
        from progen_tpu.workloads.infill import mask_from_wire
        lmask = mask_from_wire(d["logit_mask"], vocab)
    r = Request(
        uid=d["uid"], tokens=list(d["tokens"]),
        max_new_tokens=int(d["max_new_tokens"]),
        top_k=d.get("top_k"), temperature=float(d.get("temperature", 1.0)),
        seed=int(d.get("seed", 0)), on_complete=on_complete,
        submit_time=now, logit_mask=lmask, tenant=int(d.get("tenant", 0)),
        priority=int(d.get("priority", 0)))
    if "deadline_remaining" in d:
        r.deadline = now + float(d["deadline_remaining"])
    tc = d.get("trace")
    if tc:
        # land the sender's clock instant on this process's timeline so
        # the offset-corrected merge can attribute cross-process queue
        # wait to the request (docs/OBSERVABILITY.md)
        _trace.get_tracer().event("request.arrive", trace=tc.get("id"),
                                  sender_clock=tc.get("clock"),
                                  recv_clock=now)
    return r


def serialize_handle(handle: Handle, *, extra_header: dict | None = None,
                     counters=None) -> bytes:
    """One prefill product → one wire frame.

    A single batched ``device_get`` pulls the whole state tree to host
    (one sync, not one per leaf), each leaf is appended at its manifest
    offset, and the header records ``(path, dtype, shape, offset,
    nbytes)`` so the receiver can rebuild the tree with zero-copy
    ``np.frombuffer`` views.  ``extra_header`` keys (batch ids, routing
    tags) are merged into the header verbatim.
    """
    import jax
    import numpy as np

    t0 = time.perf_counter()
    pairs = _flatten_state(handle.state)
    host = jax.device_get([leaf for _, leaf in pairs])
    manifest = []
    parts = []
    off = 0
    for (path, _), arr in zip(pairs, host):
        arr = np.ascontiguousarray(arr)
        manifest.append([path, str(arr.dtype), list(arr.shape), off,
                         arr.nbytes])
        # uint8 reinterpret: extension dtypes (bfloat16) reject the
        # buffer protocol directly
        parts.append(memoryview(arr.reshape(-1).view(np.uint8)))
        off += arr.nbytes
    header = {
        "type": "handle",
        "p_pad": int(handle.p_pad),
        "reqs": [request_to_wire(r) for r in handle.requests],
        "manifest": manifest,
    }
    if extra_header:
        header.update(extra_header)
    frame = pack_frame(header, parts)
    dt = time.perf_counter() - t0
    if counters is not None:
        counters.ser_s += dt
    _trace.get_tracer().add("handoff.serialize", t0, dt,
                            uids=[r.uid for r in handle.requests],
                            nbytes=len(frame))
    return frame


def slab_axis(path: str, shape, group_size: int) -> int | None:
    """The slab rule for tensor-parallel decode groups: which axis of a
    handle-state leaf is split across the group's shards.

    One pure function IS the wire contract — the cluster applies it when
    fanning a prefill frame out into per-shard slabs, and every group
    shard applies the inverse when reassembling its slab into a global
    array, so sender and receivers can never disagree.  Rule: a leaf of
    rank >= 2 whose LAST axis divides by ``group_size`` splits on that
    axis (cache hidden dims — the tp-sharded activations); everything
    else (token rows, per-slot scalars) replicates.  ``path`` is part of
    the signature so a future format revision can special-case leaves
    without changing call sites.
    """
    del path  # today's rule is shape-only; see docstring
    if group_size <= 1 or len(shape) < 2:
        return None
    last = len(shape) - 1
    if shape[last] >= group_size and shape[last] % group_size == 0:
        return last
    return None


def split_handle_frame(header: dict, payload, group_size: int) -> list[bytes]:
    """Fan one full handle frame out into ``group_size`` per-shard slab
    frames for a multi-process tensor-parallel decode replica.

    Pure numpy on the already-received payload bytes — no JAX, no device
    work; this runs on the driver's relay path at forward time.  Every
    slab frame carries the SAME group-consistent header (requests, batch
    id, routing tags) plus a ``slab`` section naming this shard's rank,
    the group size, and the per-leaf split axes; its manifest describes
    the shard-local slab shapes so :func:`deserialize_handle_sharded`
    (and plain :func:`unpack_frame`) parse it like any other frame.
    """
    import numpy as np

    if group_size <= 1:
        raise ValueError(f"group_size must be > 1, got {group_size}")
    view = memoryview(payload).cast("B")
    leaves = []
    split: dict[str, int] = {}
    for path, dtype, shape, off, nbytes in header["manifest"]:
        arr = np.frombuffer(view[off:off + nbytes],
                            dtype=_np_dtype(dtype)).reshape(shape)
        axis = slab_axis(path, shape, group_size)
        if axis is not None:
            split[path] = axis
        leaves.append((path, dtype, arr, axis))
    base = {k: v for k, v in header.items() if k != "manifest"}
    frames = []
    for shard in range(group_size):
        manifest = []
        parts = []
        off = 0
        for path, dtype, arr, axis in leaves:
            if axis is None:
                part = arr
            else:
                w = arr.shape[axis] // group_size
                sl = [slice(None)] * arr.ndim
                sl[axis] = slice(shard * w, (shard + 1) * w)
                part = np.ascontiguousarray(arr[tuple(sl)])
            manifest.append([path, dtype, list(part.shape), off,
                             part.nbytes])
            parts.append(memoryview(
                np.ascontiguousarray(part).reshape(-1).view(np.uint8)))
            off += part.nbytes
        hdr = dict(base)
        hdr["manifest"] = manifest
        hdr["slab"] = {"shard": shard, "group_size": group_size,
                       "split": split}
        frames.append(pack_frame(hdr, parts))
    return frames


def deserialize_handle_sharded(buf, mesh, *, header: dict | None = None,
                               payload=None, counters=None) -> Handle:
    """One per-shard slab frame → a :class:`Handle` of GLOBAL arrays on
    the group's process-spanning ``mesh``.

    The inverse of :func:`split_handle_frame`, run by every shard of a
    tensor-parallel decode group on its own slab: split leaves become
    arrays sharded over the mesh's ``tensor`` axis on their split axis
    (shard ``k``'s slab lands at tensor coordinate ``k`` — the mesh is
    process-ordered), replicated leaves are rebuilt whole from each
    process's identical copy.  The group-consistent header means every
    shard reconstructs the SAME requests and admission decision.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    t0 = time.perf_counter()
    if header is None:
        header, payload = unpack_frame(buf)
    view = memoryview(payload).cast("B")
    slab = header.get("slab") or {}
    group_size = int(slab.get("group_size", 1))
    split = slab.get("split") or {}
    pairs = []
    try:
        for path, dtype, shape, off, nbytes in header["manifest"]:
            local = np.ascontiguousarray(
                np.frombuffer(view[off:off + nbytes],
                              dtype=_np_dtype(dtype)).reshape(shape))
            axis = split.get(path)
            if axis is None:
                sharding = NamedSharding(mesh, PartitionSpec())
                gshape = tuple(shape)
            else:
                axis = int(axis)
                spec = [None] * len(shape)
                spec[axis] = "tensor"
                sharding = NamedSharding(mesh, PartitionSpec(*spec))
                gshape = tuple(d * group_size if i == axis else d
                               for i, d in enumerate(shape))
            pairs.append((path, jax.make_array_from_process_local_data(
                sharding, local, gshape)))
        reqs = [request_from_wire(d) for d in header["reqs"]]
        p_pad = int(header["p_pad"])
    except (KeyError, TypeError, ValueError) as e:
        raise FrameCorrupt(f"malformed slab frame header: {e}",
                           header=header) from e
    state = _unflatten_state(pairs)
    h = Handle(requests=reqs, state=state, p_pad=p_pad)
    dt = time.perf_counter() - t0
    if counters is not None:
        counters.de_s += dt
    _trace.get_tracer().add("handoff.deserialize_sharded", t0, dt,
                            uids=[r.uid for r in reqs])
    return h


def _np_dtype(name: str):
    import numpy as np

    try:
        return np.dtype(name)  # bfloat16 resolves via jax's ml_dtypes
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def deserialize_handle(buf, *, header: dict | None = None,
                       payload=None, counters=None) -> Handle:
    """One wire frame → a :class:`Handle` of fresh device arrays.

    Pass either the full frame ``buf`` or a pre-unpacked ``(header,
    payload)`` pair (the router parses headers without touching
    payloads).  Each manifest entry becomes an ``np.frombuffer`` view
    into the single received buffer — no host-side copy — and one
    batched ``device_put`` commits the tree to device, producing fresh
    buffers the decode merge can safely DONATE.
    """
    import jax
    import numpy as np

    t0 = time.perf_counter()
    if header is None:
        header, payload = unpack_frame(buf)
    view = memoryview(payload).cast("B")
    pairs = []
    try:
        for path, dtype, shape, off, nbytes in header["manifest"]:
            arr = np.frombuffer(view[off:off + nbytes],
                                dtype=_np_dtype(dtype)).reshape(shape)
            pairs.append((path, arr))
        reqs = [request_from_wire(d) for d in header["reqs"]]
        p_pad = int(header["p_pad"])
    except (KeyError, TypeError, ValueError) as e:
        raise FrameCorrupt(f"malformed handle header: {e}",
                           header=header) from e
    state = _unflatten_state(
        zip([p for p, _ in pairs],
            jax.device_put([a for _, a in pairs])))
    h = Handle(requests=reqs, state=state, p_pad=p_pad)
    dt = time.perf_counter() - t0
    if counters is not None:
        counters.de_s += dt
    _trace.get_tracer().add("handoff.deserialize", t0, dt,
                            uids=[r.uid for r in reqs])
    return h
