"""Mixed-precision policy for TPU.

The reference opts into a jmp policy ``params=float32, compute=float16,
output=float32`` set class-wide on its Haiku model
(``/root/reference/progen_transformer/progen.py:235-241``).  On TPU the MXU
natively computes in bfloat16, so the TPU-first policy is
``params=float32, compute=bfloat16, output=float32`` — the reference README's
own TODO list records "bfloat16 on xla" as the intended TPU path
(``/root/reference/README.md:111``).

Instead of monkeypatching module classes (the jmp/Haiku approach), the policy
is a plain dataclass threaded explicitly through the model: params live in
``param_dtype``, blocks compute in ``compute_dtype`` via flax's ``dtype=``
promotion inside Embed/LayerNorm/Dense, and the final logits are cast to
``output_dtype``.  The policy is visible to XLA as ordinary
``convert_element_type`` ops it can fuse.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    output_dtype: jnp.dtype = jnp.float32

    def cast_to_output(self, x):
        return jnp.asarray(x, self.output_dtype)


def make_policy(mixed_precision: bool = True, *,
                param_dtype=jnp.float32) -> Policy:
    """``mixed_precision=False`` computes in f32 end to end (parity/test mode).

    Mirrors the reference's ``ProGen(mixed_precision=...)`` kwarg
    (``progen.py:235``) but defaults to bf16 compute, the TPU-native choice.
    ``param_dtype=jnp.bfloat16`` stores the parameters as a checkpoint
    published in bfloat16 holds them (``models/longcat.py``); the logits
    stay float32.
    """
    compute = jnp.bfloat16 if mixed_precision else jnp.float32
    return Policy(param_dtype, compute, jnp.float32)
