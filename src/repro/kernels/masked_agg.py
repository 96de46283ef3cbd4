"""Pallas kernels for the server-side aggregation hot spot (Alg. 1 line 11).

Two entry points share one tiled, memory-bound reduction structure:

- :func:`masked_agg` — the historical single-trajectory active-client mean
  over ``[m, n]`` stacked client params (kept for callers/benchmarks);
- :func:`fused_masked_agg` — the sweep-layout kernel: ``[B, m, n]`` stacked
  client params with a per-trajectory ``[B, m]`` active mask, a traced
  ``[B]`` branch opcode, the previous server params ``[B, n]`` and the
  connection probabilities ``[B, m]``. The state-compatible family's
  weighting branches (fedpbc / fedavg / fedavg_all / fedavg_known_p) are
  folded into ONE select inside the kernel body, so the whole family's
  server update is a single pass over HBM instead of a ``lax.switch`` that
  evaluates every branch under vmap.

Branch opcodes (see ``repro.kernels.dispatch``):

- ``OP_MEAN`` (0): guarded active-client mean — ``sum(mask*x)/max(|A|,1)``,
  falling back to ``prev`` when no client is active (the engine's
  ``any_active`` guard, folded into the kernel: a zero-active round
  preserves the previous server params instead of zeroing the model);
- ``OP_ALL`` (1): all-client delta mean — ``prev + sum(mask*(x-prev))/m``;
- ``OP_KNOWN_P`` (2): known-p importance weighting —
  ``prev + sum(mask*(x-prev) / max(p, 1e-3)) / m``.

All arithmetic is fp32 regardless of input dtype (fp32 accumulation for
bf16 inputs); outputs are fp32 and callers cast back per leaf. The kernel
tiles the (flattened) parameter dimension into VMEM-resident blocks and
keeps the whole client axis per block, so each output element is produced
in one pass: grid ``(n/bn,)`` for 2-D input; the 3-D sweep layout is the
2-D kernel under ``vmap``, which Pallas lifts to a ``(B, n/bn)`` grid with
the batch block dimension squeezed (so every block's last two dims are
either the full array dims or multiples of (8, 128), as TPU tiling needs).
The block width ``bn`` shrinks as ``m`` grows to keep a block inside the
VMEM budget (:func:`block_width`).

``interpret`` has no default: ``interpret=True`` traces the body to plain
XLA ops (on CPU bitwise identical to the engine's XLA masked-mean path for
fp32 leaves), ``interpret=False`` compiles the kernel for the TPU
(documented tolerance: see README "Kernels"). ``repro.kernels.dispatch``
picks it from the platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Branch opcodes of the fused kernel (must match repro.kernels.dispatch).
OP_MEAN = 0      # fedpbc / fedavg: guarded active-client mean
OP_ALL = 1       # fedavg_all: all-client delta mean
OP_KNOWN_P = 2   # fedavg_known_p: 1/(m * p_i) delta weighting


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# VMEM budget of one grid step. v5e gives a Mosaic kernel 16 MiB of scoped
# VMEM by default; 12 MiB leaves room for the compiler's own scratch. One
# step holds the double-buffered [m, bn] input block, about four [m, bn]
# float32 temporaries of the body (x*mask, x-prev and the two weighted
# deltas), and the two [m, 1] mask/p columns, which VMEM pads to 128 lanes
# and double-buffers. The whole client axis sits in every block, so bn is
# what gives: [256, 4096] float32 blocks ran out of VMEM on v5e, m=192
# still fit. Below bn=128 nothing is left to shrink, which caps the client
# axis at about 4000 per call (the cohort engine aggregates C, not m).
VMEM_BUDGET_BYTES = 12 * 2**20
_F32_TEMPS = 4


def block_width(m: int, n: int, itemsize: int, block_n: int = 4096) -> int:
    """Parameter-axis block width for an ``[m, n]`` aggregation: the
    largest multiple of 128 (the TPU lane width) that keeps one grid step
    inside :data:`VMEM_BUDGET_BYTES`, at most ``block_n`` and no wider than
    ``n`` rounded up to 128."""
    per_col = m * (2 * itemsize + _F32_TEMPS * 4)
    fixed = 2 * 2 * m * 128 * 4
    fit = max(VMEM_BUDGET_BYTES - fixed, 0) // per_col // 128 * 128
    return max(128, min(block_n, fit, _round_up(n, 128)))


# ---------------------------------------------------------------------------
# Historical single-trajectory active-mean kernel
# ---------------------------------------------------------------------------


def _mean_kernel(mask_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # [m, bn]
    mask = mask_ref[...].astype(jnp.float32)        # [m, 1]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    o_ref[...] = (jnp.sum(x * mask, axis=0, keepdims=True) / denom)[0]


def _guarded_mean_kernel(mask_ref, prev_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # [m, bn]
    mask = mask_ref[...].astype(jnp.float32)        # [m, 1]
    prev = prev_ref[...].astype(jnp.float32)        # [1, bn]
    n_active = jnp.sum(mask)
    agg = jnp.sum(x * mask, axis=0, keepdims=True) / jnp.maximum(n_active, 1.0)
    o_ref[...] = jnp.where(n_active > 0, agg, prev)[0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def masked_agg(x, mask, prev=None, *, block_n: int = 4096, interpret: bool):
    """x: [m, n]; mask: [m]. Returns [n] fp32 (active-client mean).

    Zero-active semantics: with ``prev=None`` an empty active set yields the
    zero vector — exactly ``algorithms.masked_mean``'s fallback (callers
    guard with ``any_active``). Passing ``prev`` ([n]) folds that guard into
    the kernel: an empty active set returns ``prev`` (the previous server
    params) instead of silently zeroing the model, matching the engine-level
    ``jnp.where(any_active, masked_mean(...), server)`` semantics.
    """
    m, n = x.shape
    bn = block_width(m, n, x.dtype.itemsize, block_n)
    pad = (-n) % bn
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    np_ = x.shape[1]
    mask2 = mask.astype(jnp.float32).reshape(m, 1)
    if prev is None:
        out = pl.pallas_call(
            _mean_kernel,
            grid=(np_ // bn,),
            in_specs=[
                pl.BlockSpec((m, 1), lambda i: (0, 0)),
                pl.BlockSpec((m, bn), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((np_,), jnp.float32),
            interpret=interpret,
            name="masked_mean",
        )(mask2, x)
        return out[:n]
    prev2 = jnp.pad(prev.astype(jnp.float32), (0, pad)).reshape(1, np_)
    out = pl.pallas_call(
        _guarded_mean_kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((np_,), jnp.float32),
        interpret=interpret,
        name="guarded_masked_mean",
    )(mask2, prev2, x)
    return out[:n]


# ---------------------------------------------------------------------------
# Fused family-aggregation kernel (the sweep hot path)
# ---------------------------------------------------------------------------


def _fused_kernel(op_ref, mask_ref, p_ref, prev_ref, x_ref, o_ref):
    """One [m, bn] block of one trajectory: every weighting variant computed
    from the single streamed read of ``x`` and selected by ``op``."""
    x = x_ref[...].astype(jnp.float32)              # [m, bn]
    mask = mask_ref[...].astype(jnp.float32)        # [m, 1]
    p = p_ref[...].astype(jnp.float32)              # [m, 1]
    prev = prev_ref[...].astype(jnp.float32)        # [1, bn]
    op = op_ref[0, 0]
    m = x.shape[0]
    # OP_MEAN: guarded active mean (the any_active guard folded in)
    n_active = jnp.sum(mask)
    mean_agg = jnp.sum(x * mask, axis=0, keepdims=True) \
        / jnp.maximum(n_active, 1.0)
    mean_out = jnp.where(n_active > 0, mean_agg, prev)
    # OP_ALL / OP_KNOWN_P: server + weighted delta sum (weights written in
    # the exact division order of the engine branches, for bitwise parity)
    delta = x - prev
    all_out = prev + jnp.sum(delta * (mask / m), axis=0, keepdims=True)
    w_kp = mask / jnp.maximum(p, 1e-3) / m
    kp_out = prev + jnp.sum(delta * w_kp, axis=0, keepdims=True)
    o_ref[...] = jnp.where(op == OP_MEAN, mean_out,
                           jnp.where(op == OP_ALL, all_out, kp_out))


def _fused_call_2d(x, mask, op, prev, p, bn: int, interpret: bool):
    m, np_ = x.shape
    assert np_ % bn == 0, (np_, bn)   # caller pads n up to a bn multiple
    return pl.pallas_call(
        _fused_kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        interpret=interpret,
        name="fused_masked_agg",
    )(op, mask, p, prev, x)[0]


def fused_masked_agg(x, mask, op, prev, p, *, block_n: int = 4096,
                     interpret: bool):
    """Fused family aggregation over stacked client params.

    Shapes — single trajectory: ``x [m, n]``, ``mask [m]``, ``op`` scalar,
    ``prev [n]``, ``p [m]``; sweep layout: ``x [B, m, n]``, ``mask [B, m]``,
    ``op [B]``, ``prev [B, n]``, ``p [B, m]``. Returns fp32 ``[n]`` /
    ``[B, n]``: the new server params under the branch each trajectory's
    ``op`` selects (see module docstring for the opcode table).

    The sweep layout is the single-trajectory kernel under ``jax.vmap``
    (Pallas lifts the call to a batched grid) — the same program the round
    engine reaches by vmapping over trajectories.
    """
    if x.ndim == 3:
        return jax.vmap(functools.partial(
            fused_masked_agg, block_n=block_n, interpret=interpret))(
            x, mask, op, prev, p)
    m, n = x.shape
    bn = block_width(m, n, x.dtype.itemsize, block_n)
    pad = (-n) % bn
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    prevp = jnp.pad(prev.astype(jnp.float32), (0, pad)).reshape(1, -1)
    out = _fused_call_2d(
        xp, mask.astype(jnp.float32).reshape(m, 1),
        jnp.asarray(op, jnp.int32).reshape(1, 1),
        prevp, p.astype(jnp.float32).reshape(m, 1), bn, interpret)
    return out[:n]
