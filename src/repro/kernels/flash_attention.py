"""Pallas TPU kernel: flash attention (causal / sliding-window / softcap).

Online-softmax with explicit VMEM tiling: grid (B*H, Tq/bq, Tk/bk), the KV
axis innermost so the running (m, l, acc) triple lives in VMEM scratch across
KV steps and the output tile is written once on the last step. Block shapes
are MXU-aligned (multiples of 128 on the matmul dims).

Gradients: :func:`flash_attention` is a ``jax.custom_vjp``. The forward pass
is the Pallas kernel; the backward pass is the VJP of the float32 reference
(``repro.kernels.ref.flash_attention_ref``), recomputed from the saved
``q, k, v``. It materializes the ``[T, T]`` scores per head, which is cheap
at the federated clients' training lengths; a Pallas backward kernel is
left to a later change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import flash_attention_ref

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, softcap, bq, bk, n_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # [bq, d]
    k = k_ref[0].astype(jnp.float32)                # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allow = jnp.ones((bq, bk), bool)
    if causal:
        allow &= q_pos >= k_pos
    if window:
        allow &= q_pos - k_pos < window
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal, window, logit_softcap, bq, bk,
                   interpret):
    b, h, t, d = q.shape
    bq = min(bq, t)
    bk = min(bk, t)
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    n_k = t // bk
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, window=window,
        softcap=logit_softcap, bq=bq, bk=bk, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, t, d)


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=tuple(range(3, 9)))


def _flash_fwd(q, k, v, *static):
    return _flash_forward(q, k, v, *static), (q, k, v)


def _flash_bwd(causal, window, logit_softcap, bq, bk, interpret, res, g):
    def ref(q, k, v):
        f32 = jnp.float32
        return flash_attention_ref(q.astype(f32), k.astype(f32),
                                   v.astype(f32), causal=causal,
                                   window=window, logit_softcap=logit_softcap)

    _, vjp = jax.vjp(ref, *res)
    return vjp(g.astype(jnp.float32))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "logit_softcap", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    bq=128, bk=128, interpret: bool):
    """q, k, v: [B, H, T, D] (same head count; GQA handled by the wrapper).

    Differentiable: see the module docstring for the backward pass."""
    return _flash(q, k, v, causal, window, logit_softcap, bq, bk, interpret)
