"""Pallas TPU kernel: RWKV6 chunked WKV recurrence.

One grid cell per (batch*head); the kernel walks the sequence in chunks of
``chunk`` with the [D, D] state held in VMEM scratch across the fori_loop.
Intra-chunk contributions use the decay-weighted lower-triangular matmul (the
chunked-WKV form), so each chunk is two MXU matmuls + elementwise decay math
instead of ``chunk`` sequential rank-1 updates.

Block layout: r/k/v/w arrive as [T, D] VMEM blocks per (b, h); D = head_dim
(64/128) and chunk=64 keep every operand MXU-aligned and the working set
(4 x T x D fp32 + D^2 state) within VMEM for T <= 8k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_ref, state, *,
            chunk, n_chunks):
    state[...] = s0_ref[0].astype(jnp.float32)     # [D, D]
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)
    u = u_ref[0].astype(jnp.float32)               # [1, D] -> broadcast

    def body(c, _):
        sl = pl.dslice(c * chunk, chunk)
        rb = r_ref[0, sl, :].astype(jnp.float32)   # [C, D]
        kb = k_ref[0, sl, :].astype(jnp.float32)
        vb = v_ref[0, sl, :].astype(jnp.float32)
        wb = w_ref[0, sl, :].astype(jnp.float32)
        logw = jnp.log(jnp.maximum(wb, 1e-12))
        q_inc = jnp.cumsum(logw, axis=0)
        q_exc = q_inc - logw
        r_dec = rb * jnp.exp(q_exc)
        k_dec = kb * jnp.exp(-q_inc)
        o = jax.lax.dot(r_dec, state[...])                       # inter-chunk
        scores = jax.lax.dot_general(
            r_dec, k_dec, (((1,), (1,)), ((), ()))) * tri        # intra
        o = o + jax.lax.dot(scores, vb)
        cur = jnp.sum(rb * u * kb, axis=-1, keepdims=True)       # bonus
        o = o + cur * vb
        total = q_inc[-1:, :]                                    # [1, D]
        k_tail = kb * jnp.exp(total - q_inc)
        state[...] = (jnp.exp(total)[0][:, None] * state[...]
                      + jax.lax.dot_general(k_tail, vb, (((0,), (0,)), ((), ()))))
        o_ref[0, sl, :] = o.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, n_chunks, lambda c, _: body(c, _), ())
    s_ref[0] = state[...].astype(s_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunk(r, k, v, w, u, s0, *, chunk: int = 64, interpret: bool):
    """r,k,v,w: [B, H, T, D]; u: [H, D]; s0: [B, H, D, D] fp32.

    Returns (o [B,H,T,D] fp32, s_T [B,H,D,D] fp32).
    """
    b, h, t, d = r.shape
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    rf = r.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    wf = w.reshape(b * h, t, d)
    uf = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)
    sf = s0.reshape(b * h, d, d).astype(jnp.float32)
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, t, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, t, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, t, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, d, d), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, d), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, d, d), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
        name="rwkv6_chunk",
    )(rf, kf, vf, wf, uf, sf)
    return o.reshape(b, h, t, d), s_out.reshape(b, h, d, d)
