"""Matrix products at a stated precision, the same on every backend.

The references compute every product with float32 operands at
``Precision.HIGHEST`` (exact float32 on a TPU, where the default is one
bfloat16 pass). The CPU runs every product in float32 whatever it is
asked, so a control that computes the reference at a lower precision
emulates it by rounding the operands explicitly before an exact product:

- ``"highest"``: float32;
- ``"bf16"``: one bfloat16 pass, the TPU's ``Precision.DEFAULT``.

Backward products run at the same precision as the forward ones, as they
do on the chip: the cotangent and the other operand are rounded alike.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "bf16")


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bf16_pass(spec, a, b):
    return _exact(spec, _bf16(a), _bf16(b))


def _bf16_pass_fwd(spec, a, b):
    return _bf16_pass(spec, a, b), (a, b)


def _bf16_pass_bwd(spec, res, g):
    a, b = res
    da = jax.vjp(lambda x: _exact(spec, x, _bf16(b)), a)[1](_bf16(g))[0]
    db = jax.vjp(lambda y: _exact(spec, _bf16(a), y), b)[1](_bf16(g))[0]
    return da, db


_bf16_pass.defvjp(_bf16_pass_fwd, _bf16_pass_bwd)


def einsum(spec: str, a, b, mode: str = "highest"):
    """``jnp.einsum(spec, a, b)`` on float32 operands at ``mode``."""
    if mode not in MODES:
        raise ValueError(f"unknown precision {mode!r}; expected one of {MODES}")
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "highest":
        return _exact(spec, a, b)
    return _bf16_pass(spec, a, b)
