"""Kernel micro-benchmarks.

Historical arms time the pure-jnp references on CPU (the flash-attention /
rwkv kernels are TPU-target; interpret-mode timing is not meaningful there)
and report kernel/oracle allclose deltas.

The ``batched_agg`` arm times the sweep hot path both ways: the fused
family-aggregation kernel through ``repro.kernels.dispatch`` (the backend
the current platform resolves to) against the pure-XLA reference, at the
sweep layout ``[B, m, n]`` with mixed per-trajectory opcodes. Emits a
``BENCH {...}`` JSON line and writes ``benchmarks/out/kernels.json`` with
per-arm ``xla_us`` / ``kernel_us`` / ``speedup`` / ``max_abs_diff``. On CPU
the kernel runs in interpret mode (same XLA ops, so speedup ~1 is expected
and the interesting column is ``max_abs_diff == 0``); on TPU/GPU the
compiled kernel is the one being sold.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (
    OP_ALL,
    OP_KNOWN_P,
    OP_MEAN,
    flash_attention,
    flash_attention_ref,
    fused_agg,
    masked_agg,
    masked_agg_ref,
    resolve_backend,
    rwkv6_chunk,
    rwkv6_chunk_ref,
)


def _time(fn, *args, reps=5):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def batched_agg_arms(key, sizes=((8, 32), (8, 256), (64, 32), (64, 256)),
                     n=1024, reps=5):
    """Time fused kernel (resolved backend) vs the XLA reference per
    ``[B, m, n]`` size; returns the BENCH sub-dict list."""
    backend = resolve_backend()
    call = jax.jit(fused_agg, static_argnames=("backend", "block_n"))
    arms = []
    for B, m in sizes:
        k = jax.random.fold_in(key, B * m)
        x = jax.random.normal(k, (B, m, n))
        mask = jax.random.uniform(jax.random.fold_in(k, 1), (B, m)) < 0.5
        prev = jax.random.normal(jax.random.fold_in(k, 2), (B, n))
        p = jax.random.uniform(jax.random.fold_in(k, 3), (B, m),
                               minval=0.05, maxval=1.0)
        ops = jnp.asarray([(OP_MEAN, OP_ALL, OP_KNOWN_P)[b % 3]
                           for b in range(B)], jnp.int32)
        args = (x, mask, ops, prev, p)
        kernel_us = _time(lambda *a: call(*a, backend=backend), *args,
                          reps=reps)
        xla_us = _time(lambda *a: call(*a, backend="xla"), *args, reps=reps)
        diff = float(jnp.max(jnp.abs(call(*args, backend=backend)
                                     - call(*args, backend="xla"))))
        arms.append({
            "arm": f"batched_agg_B{B}_m{m}_n{n}",
            "B": B, "m": m, "n": n,
            "kernel_backend": backend,
            "kernel_us": round(kernel_us, 1),
            "xla_us": round(xla_us, 1),
            "speedup": round(xla_us / kernel_us, 3),
            "max_abs_diff": diff,
        })
    return arms


def run(csv=True, out_path=None):
    key = jax.random.PRNGKey(0)
    rows = []
    # the oracle-delta arms call kernels outside the dispatch layer, so
    # they take its platform decision explicitly
    interpret = resolve_backend() != "compiled"

    agg_arms = batched_agg_arms(jax.random.fold_in(key, 100))
    for a in agg_arms:
        rows.append((a["arm"], a["kernel_us"],
                     f"xla_us={a['xla_us']};speedup={a['speedup']};"
                     f"max_abs_diff={a['max_abs_diff']:.2e}"))

    x = jax.random.normal(key, (64, 1 << 16))
    mask = (jax.random.uniform(jax.random.fold_in(key, 1), (64,)) < 0.5)
    us = _time(jax.jit(masked_agg_ref), x, mask)
    err = float(jnp.max(jnp.abs(masked_agg(x, mask, interpret=interpret)
                                 - masked_agg_ref(x, mask))))
    rows.append(("masked_agg_64x65536", us, f"kernel_max_err={err:.2e}"))

    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 4, 512, 64))
               for i in range(3))
    us = _time(jax.jit(flash_attention_ref), q, k, v)
    err = float(jnp.max(jnp.abs(flash_attention(q, k, v, interpret=interpret)
                                - flash_attention_ref(q, k, v))))
    rows.append(("flash_attention_512", us, f"kernel_max_err={err:.2e}"))

    b, h, t, d = 1, 4, 256, 64
    r_, k_, v_ = (0.5 * jax.random.normal(jax.random.fold_in(key, 10 + i),
                                          (b, h, t, d)) for i in range(3))
    w = jnp.exp(-jnp.exp(-3.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 13), (b, h, t, d))))
    u = 0.2 * jax.random.normal(jax.random.fold_in(key, 14), (h, d))
    s0 = jnp.zeros((b, h, d, d))
    us = _time(jax.jit(rwkv6_chunk_ref), r_, k_, v_, w, u, s0)
    o1, _ = rwkv6_chunk(r_, k_, v_, w, u, s0, interpret=interpret)
    o2, _ = rwkv6_chunk_ref(r_, k_, v_, w, u, s0)
    err = float(jnp.max(jnp.abs(o1 - o2)))
    rows.append(("rwkv6_chunk_256", us, f"kernel_max_err={err:.2e}"))

    result = {
        "suite": "kernels",
        "backend": jax.default_backend(),
        "kernel_backend": resolve_backend(),
        "batched_agg": agg_arms,
    }
    print("BENCH " + json.dumps(result), flush=True)
    if out_path is None:
        out_path = os.path.join(os.path.dirname(__file__), "out",
                                "kernels.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    if csv:
        print("kernels,name,us_per_call,derived")
        for n, us, d_ in rows:
            print(f"kernels,{n},{us:.1f},{d_}")
    return rows


if __name__ == "__main__":
    run()
