"""What an op that owns two lowerings of one contract can observe, and a
note of which one it took.

``ops/row_write.py``, ``ops/mla_prefill.py``, ``ops/mla_decode.py``,
``ops/gqa.py`` and ``models/experts.py:held_experts`` (its kernels are
``ops/moe_decode.py``'s; the note ``"moe_experts"``: ``"pallas"`` for a
decode step's handful of tokens, ``"pallas_grouped"`` for a block step's
few hundred, ``"pallas_sorted"`` for an admission's thousands, ``"xla"``
off the chip; under ``"pallas_sorted"`` the note ``"moe_combine"`` says how
the terms reached their tokens: ``"pallas_rows"``, the kernel's own row
DMAs) and ``ops/gdn.py:gdn_scan`` (the note ``"gdn_prefill"``: the kernel
``gdn_prefill_fwd`` at widths on the lane tile and chunks of whole blocks
of 16 rows, which stops at a row's true length; its one-token sibling
``gdn_step`` is plain XLA and says ``"xla"`` under ``"gdn_step"``; the rule
with a decay a channel, ``kda_scan``, chooses in the same way under
``"kda_prefill"`` — the kernel ``kda_prefill_fwd`` at widths on the lane
tile, chunks of whole blocks of 16 rows and products in blocks of 16 —, and
its one-token sibling ``kda_step`` says ``"xla"`` under ``"kda_step"``) each
keep a Pallas
lowering and an XLA form behind one function and choose between them from
the backend, the mesh in scope and the shapes, never from a knob.
``ops/kth.py:kth_largest_by_counting`` chooses in the same way between two
XLA forms of one loop, under the name its caller gives (``"sample_kth"``
from the engine's draw, ``decode/sampler.py``; ``"dsa_kth"`` from an
admission's learned selection, ``ops/dsa.py:selected``): ``"xla"`` where
the block's keys fit on the chip and the compiler keeps them there,
``"xla_tiled"`` where only a group of rows does and the loop runs a group
at a time.
(``ops/gqa.py`` owns three such ops: the prefill core, ``"gqa_prefill"``;
the one-query decode core, ``"gqa_decode"`` — the kernel at head widths on
the lane tile, the XLA form at Granite 4.0-H's 64 —; and
``block_decode_attention``, a block of queries a slot, which follows the
one-query core's rule and notes its choice as ``"gqa_block_decode"``.)
The choice is made while a program is traced, so a caller that traces one
(``ServingEngine`` around its chunk and admission programs) can collect it:
:func:`record_lowerings` yields ``{op name: {lowering, ...}}`` for the ops
traced inside the block, at no cost per step.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax

_recorder: contextvars.ContextVar = contextvars.ContextVar(
    "op_lowerings", default=None)


@contextlib.contextmanager
def record_lowerings():
    """Collect ``{op: {lowering, ...}}`` as :func:`note` reports them for
    the ops traced inside the block."""
    chosen: dict[str, set[str]] = {}
    token = _recorder.set(chosen)
    try:
        yield chosen
    finally:
        _recorder.reset(token)


def note(op: str, lowering: str) -> None:
    """An op's report of the lowering the call being traced takes."""
    chosen = _recorder.get()
    if chosen is not None:
        chosen.setdefault(op, set()).add(lowering)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def mesh_in_scope() -> bool:
    from jax._src import mesh as mesh_lib

    return not (mesh_lib.thread_resources.env.physical_mesh.empty
                and jax.sharding.get_abstract_mesh().empty)
