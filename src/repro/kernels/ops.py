"""Jit'd public wrappers for the Pallas kernels.

Every kernel entry point takes ``interpret`` with no default: a direct
caller states it, and everything else goes through ``repro.kernels.dispatch``,
which picks the compiled kernel on TPU and interpret mode or the XLA
reference elsewhere. The kernels are validated against ``ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import (
    FUSED_OPS,
    attention,
    fused_agg,
    fused_agg_pytree,
    resolve_attention_backend,
    resolve_backend,
    resolve_use_kernel,
    use_kernel_default,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.masked_agg import (
    OP_ALL,
    OP_KNOWN_P,
    OP_MEAN,
    fused_masked_agg,
    masked_agg,
)
from repro.kernels.ref import (
    flash_attention_ref,
    fused_masked_agg_ref,
    masked_agg_ref,
    rwkv6_chunk_ref,
)
from repro.kernels.rwkv6_chunk import rwkv6_chunk


def masked_agg_pytree(clients, mask, prev=None, *, interpret: bool):
    """FedPBC aggregation over an [m, ...] client-stacked pytree using the
    masked_agg kernel per (flattened) leaf. ``prev`` (a pytree matching the
    server params) folds the empty-active-set guard into the kernel: a
    zero-active round returns ``prev`` unchanged instead of a zeroed model."""
    def leaf(x, pv=None):
        m = x.shape[0]
        flat = x.reshape(m, -1)
        pflat = None if pv is None else pv.reshape(-1)
        out = masked_agg(flat, mask, pflat, interpret=interpret)
        return out.reshape(x.shape[1:]).astype(x.dtype)
    if prev is None:
        return jax.tree.map(leaf, clients)
    return jax.tree.map(leaf, clients, prev)


def gqa_flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                        interpret: bool):
    """q: [B, T, H, D]; k, v: [B, T, KV, D] (GQA) -> [B, T, H, D]."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    qt = q.transpose(0, 2, 1, 3)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
    o = flash_attention(qt, kt, vt, causal=causal, window=window,
                        logit_softcap=logit_softcap, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


__all__ = [
    "masked_agg",
    "masked_agg_pytree",
    "masked_agg_ref",
    "fused_masked_agg",
    "fused_masked_agg_ref",
    "fused_agg",
    "fused_agg_pytree",
    "FUSED_OPS",
    "OP_MEAN",
    "OP_ALL",
    "OP_KNOWN_P",
    "resolve_backend",
    "resolve_use_kernel",
    "use_kernel_default",
    "attention",
    "resolve_attention_backend",
    "flash_attention",
    "flash_attention_ref",
    "gqa_flash_attention",
    "rwkv6_chunk",
    "rwkv6_chunk_ref",
]
