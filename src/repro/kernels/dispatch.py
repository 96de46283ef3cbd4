"""Backend-aware kernel dispatch for the fused aggregation hot path.

One resolution layer decides how the sweep engine's server aggregation
executes, so the same traced program runs everywhere:

- ``"compiled"`` — the Pallas kernel compiled for the TPU
  (``interpret=False``); the default on TPU. The kernels are Mosaic (TPU)
  kernels, so no other platform defaults to it.
- ``"interpret"`` — the Pallas kernel in interpret mode: the kernel body is
  traced to plain XLA ops, so it runs (and is differentiable/shardable)
  anywhere; the default on CPU. On CPU this is bitwise identical to the
  engine's XLA path for fp32 leaves.
- ``"xla"`` — the pure-jnp reference (``fused_masked_agg_ref``), always
  available as a fallback independent of Pallas.

Overrides (highest precedence first): an explicit ``backend=`` argument,
the ``REPRO_KERNEL_BACKEND`` environment variable (``compiled`` /
``interpret`` / ``xla``), then the per-platform default above.

Several devices: XLA cannot partition a Mosaic kernel, so under a context
mesh of more than one device (``jax.set_mesh``, entered by the sweep
runner when its batch is laid out on a mesh) a compiled kernel runs inside
a ``shard_map`` whose per-call view is replicated; the enclosing vmaps'
``spmd_axis_name`` maps their batched dims (trajectories, clients) onto
mesh axes, so each device runs the kernel on its own slice.

Whether the engine uses the kernel at all is a separate knob, threaded as
``use_kernel`` through ``AlgorithmSpec.aggregate`` -> ``make_round_fn`` ->
``make_batched_run_rounds`` -> ``SweepSpec``; ``None`` at any of those
levels defers to :func:`use_kernel_default` (the ``REPRO_USE_KERNEL``
environment variable, default off).

Tolerance contract vs the engine's XLA masked-mean path, per backend
(equality statements are between JITTED programs — the only way the hot
path runs either side; op-by-op eager dispatch may fuse multiply+reduce
differently at one-ulp level, see ``tests/test_kernels.py``):

==============  ============================================================
``interpret``   fp32 leaves: bitwise on CPU (a family sweep with
                ``use_kernel=True`` equals the XLA-path program per
                trajectory, pinned by ``tests/test_kernel_sweep.py``);
                bf16 leaves: the kernel accumulates in fp32 where the XLA
                path computes in bf16 — differences up to ~1e-2 * magnitude
                (bf16 epsilon).
``xla``         identical math to the kernel (fp32 accumulation): bitwise
                vs ``interpret`` on every platform.
``compiled``    allclose within 1e-6 (fp32) / 2e-2 (bf16): accelerator
                reduction order inside a block may differ from XLA's.
==============  ============================================================
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.masked_agg import (
    OP_ALL,
    OP_KNOWN_P,
    OP_MEAN,
    fused_masked_agg,
)
from repro.kernels.ref import fused_masked_agg_ref

Pytree = Any

BACKENDS = ("compiled", "interpret", "xla")

_ENV_BACKEND = "REPRO_KERNEL_BACKEND"
_ENV_USE_KERNEL = "REPRO_USE_KERNEL"

# Aggregation opcode per algorithm name — the branch table the fused kernel
# folds into one select. Only these (the empty-state family) are fusable;
# stateful rules (fedau/mifa/f3ast/fedpbc_m) keep the lax.switch path.
FUSED_OPS = {
    "fedpbc": OP_MEAN,
    "fedavg": OP_MEAN,
    "fedavg_all": OP_ALL,
    "fedavg_known_p": OP_KNOWN_P,
}


def resolve_backend(backend: Optional[str] = None) -> str:
    """The kernel execution backend: explicit arg > ``REPRO_KERNEL_BACKEND``
    env var > platform default (compiled on tpu, interpret elsewhere)."""
    if backend is None:
        backend = os.environ.get(_ENV_BACKEND) or None
    if backend is None:
        backend = ("compiled" if jax.default_backend() == "tpu"
                   else "interpret")
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"available: {BACKENDS}")
    return backend


def use_kernel_default() -> bool:
    """The ambient ``use_kernel`` default: ``REPRO_USE_KERNEL`` env var
    (1/true/yes/on), else False (the engine's historical XLA path)."""
    return os.environ.get(_ENV_USE_KERNEL, "").strip().lower() in (
        "1", "true", "yes", "on")


def resolve_use_kernel(flag: Optional[bool] = None) -> bool:
    """Normalize a ``use_kernel`` knob: None defers to the env default."""
    return use_kernel_default() if flag is None else bool(flag)


def resolve_attention_backend(backend: Optional[str] = None) -> str:
    """The attention execution backend: explicit arg >
    ``REPRO_KERNEL_BACKEND`` env var > platform default.

    Unlike :func:`resolve_backend`, the CPU default is ``"xla"`` — the
    chunked online-softmax reference (``repro.models.attention``) IS the
    fast CPU path, while running the flash kernel's Pallas body in
    interpret mode is strictly slower there. ``"interpret"`` remains
    selectable (env var or arg) for kernel-parity audits.
    """
    if backend is None:
        backend = os.environ.get(_ENV_BACKEND) or None
    if backend is None:
        backend = ("compiled" if jax.default_backend() == "tpu"
                   else "xla")
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"available: {BACKENDS}")
    return backend


def _on_context_mesh(kernel):
    """``kernel`` as is, or inside a replicated-view ``shard_map`` over the
    context mesh when that mesh spans several devices (see the module
    docstring)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel
    return jax.shard_map(kernel, in_specs=P(), out_specs=P(), check_vma=False)


def attention(q, k, v, *, kind="full", window=4096, logit_softcap=0.0,
              chunk=1024, q_offset=0, backend: Optional[str] = None):
    """Backend-dispatched causal attention in the model stack's
    ``[B, T, H, D]`` layout (``repro.models.attention.attention``'s
    signature; that entry routes here, closing the masked_agg-style audit
    for ``repro.kernels.flash_attention``).

    The Pallas kernel covers the training shapes: self-attention
    (``Tq == Tk``, ``q_offset == 0``), ``kind`` full or swa, and ``T``
    divisible by the kernel's block size. Everything else — block-local
    ("chunked") masks, decode/prefill offsets, ragged lengths — falls back
    to the pure-XLA reference, as does ``backend="xla"``. The kernel path
    repeats GQA kv-heads and transposes to the kernel's ``[B, H, T, D]``
    layout; tolerance vs the reference follows the module contract table
    (fp32: bitwise-adjacent allclose; the reference chunks over KV where
    the kernel blocks over both axes, so reduction order differs).
    """
    # lazy: models.attention routes its public entry through this function
    from repro.models import attention as ref

    backend = resolve_attention_backend(backend)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    bq = min(128, tq)
    kernel_ok = (backend != "xla" and kind in ("full", "swa")
                 and q_offset == 0 and tq == tk and tq % bq == 0)
    if not kernel_ok:
        return ref.attention_ref(q, k, v, kind=kind, window=window,
                                 logit_softcap=logit_softcap, chunk=chunk,
                                 q_offset=q_offset)
    from repro.kernels.flash_attention import flash_attention

    n_rep = h // k.shape[2]
    kr = ref._repeat_kv(k, n_rep).transpose(0, 2, 1, 3)
    vr = ref._repeat_kv(v, n_rep).transpose(0, 2, 1, 3)
    flash = partial(flash_attention, causal=True,
                    window=window if kind == "swa" else 0,
                    logit_softcap=logit_softcap,
                    interpret=(backend == "interpret"))
    if backend == "compiled":
        flash = _on_context_mesh(flash)
    out = flash(q.transpose(0, 2, 1, 3), kr, vr)
    return out.transpose(0, 2, 1, 3)


def fused_agg(x, mask, op, prev, p, *, block_n: int = 4096,
              backend: Optional[str] = None):
    """Backend-dispatched fused aggregation over one flattened leaf.

    Shapes as in ``fused_masked_agg``: ``[m, n]`` single-trajectory or
    ``[B, m, n]`` sweep layout (the 2-D form also lifts under ``vmap``).
    Returns fp32 new server params ``[n]`` / ``[B, n]``.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return fused_masked_agg_ref(x, mask, op, prev, p)
    if backend == "interpret":
        return fused_masked_agg(x, mask, op, prev, p, block_n=block_n,
                                interpret=True)
    kernel = partial(fused_masked_agg, block_n=block_n, interpret=False)
    return _on_context_mesh(kernel)(x, mask, op, prev, p)


def fused_agg_pytree(x_star: Pytree, mask, op, server: Pytree, p, *,
                     block_n: int = 4096,
                     backend: Optional[str] = None) -> Pytree:
    """Per-leaf fused aggregation over an ``[m, ...]`` client-stacked pytree.

    Every leaf of ``x_star`` is flattened to ``[m, n]``, aggregated by one
    kernel call against the matching ``server`` leaf (flattened ``[n]``),
    and cast back to the leaf's dtype/shape. ``mask``/``p`` are shared
    across leaves ([m]); ``op`` is the per-trajectory branch opcode.
    Composable with ``vmap`` for the batched sweep layout.
    """
    backend = resolve_backend(backend)

    def leaf(xs, s):
        m = xs.shape[0]
        out = fused_agg(xs.reshape(m, -1), mask, op,
                        s.reshape(-1), p, block_n=block_n, backend=backend)
        return out.reshape(s.shape).astype(s.dtype)

    return jax.tree.map(leaf, x_star, server)
