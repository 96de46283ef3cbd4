"""Pallas kernels vs. pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps and hypothesis properties. Every direct kernel call
states ``interpret``: the entry points have no default."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # bare image: fall back to seeded-random example cases
    HAVE_HYPOTHESIS = False

from repro.kernels.ops import (
    FUSED_OPS,
    OP_ALL,
    OP_KNOWN_P,
    OP_MEAN,
    flash_attention,
    flash_attention_ref,
    fused_agg,
    fused_agg_pytree,
    fused_masked_agg,
    fused_masked_agg_ref,
    gqa_flash_attention,
    masked_agg,
    masked_agg_pytree,
    masked_agg_ref,
    resolve_backend,
    resolve_use_kernel,
    rwkv6_chunk,
    rwkv6_chunk_ref,
    use_kernel_default,
)


# ---------------------------------------------------------------------------
# masked_agg
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,dtype", [
    (4, 128, jnp.float32), (8, 1000, jnp.float32), (16, 4097, jnp.bfloat16),
    (3, 64, jnp.float32), (100, 257, jnp.bfloat16),
])
def test_masked_agg_sweep(m, n, dtype):
    key = jax.random.PRNGKey(m * n)
    x = jax.random.normal(key, (m, n), jnp.float32).astype(dtype)
    mask = (jax.random.uniform(jax.random.fold_in(key, 1), (m,)) < 0.5)
    out = masked_agg(x, mask, block_n=256, interpret=True)
    ref = masked_agg_ref(x, mask)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


def _check_masked_agg(m, n, bits):
    mask = jnp.asarray([(bits >> i) & 1 for i in range(m)], jnp.float32)
    x = jnp.arange(m * n, dtype=jnp.float32).reshape(m, n)
    out = masked_agg(x, mask, block_n=128, interpret=True)
    ref = masked_agg_ref(x, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


if HAVE_HYPOTHESIS:

    @given(st.integers(1, 12), st.integers(1, 300), st.integers(0, 2 ** 12 - 1))
    @settings(max_examples=25, deadline=None)
    def test_masked_agg_property(m, n, bits):
        _check_masked_agg(m, n, bits)

else:
    _rng = np.random.default_rng(0)
    _CASES = (
        # edge cases hypothesis would shrink to: single row, empty/full masks
        [(1, 1, 0), (1, 1, 1), (12, 300, 0), (12, 300, 2 ** 12 - 1)]
        + [(int(_rng.integers(1, 13)), int(_rng.integers(1, 301)),
            int(_rng.integers(0, 2 ** 12))) for _ in range(21)]
    )

    @pytest.mark.parametrize("m,n,bits", _CASES)
    def test_masked_agg_property(m, n, bits):
        _check_masked_agg(m, n, bits)


def test_masked_agg_pytree_matches_engine():
    from repro.core import masked_mean
    key = jax.random.PRNGKey(7)
    clients = {"a": jax.random.normal(key, (6, 10, 3)),
               "b": jax.random.normal(jax.random.fold_in(key, 1), (6, 5))}
    mask = jnp.asarray([1, 1, 0, 1, 0, 0], jnp.float32)
    got = masked_agg_pytree(clients, mask, interpret=True)
    want = masked_mean(clients, mask)
    for k in clients:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


def test_masked_agg_zero_active_semantics():
    """The zero-active-round contract: without ``prev`` an empty active set
    yields the zero vector — exactly ``algorithms.masked_mean``'s fallback —
    and with ``prev`` the kernel preserves the previous server params (the
    engine's ``any_active`` guard, folded in) instead of zeroing the model."""
    from repro.core import masked_mean
    key = jax.random.PRNGKey(5)
    m, n = 6, 300
    x = jax.random.normal(key, (m, n))
    prev = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    empty = jnp.zeros((m,), bool)
    # legacy / masked_mean semantics: empty -> zeros
    np.testing.assert_array_equal(np.asarray(masked_agg(x, empty, interpret=True)),
                                  np.zeros(n, np.float32))
    np.testing.assert_array_equal(np.asarray(masked_mean(x, empty)),
                                  np.zeros((n,), np.float32))
    # guarded semantics: empty -> prev, bit for bit
    np.testing.assert_array_equal(np.asarray(masked_agg(x, empty, prev, interpret=True)),
                                  np.asarray(prev, np.float32))
    np.testing.assert_array_equal(
        np.asarray(masked_agg_ref(x, empty, prev)),
        np.asarray(prev, np.float32))
    # with any client active, prev is inert: both forms agree exactly
    some = jnp.arange(m) < 2
    np.testing.assert_array_equal(np.asarray(masked_agg(x, some, prev, interpret=True)),
                                  np.asarray(masked_agg(x, some, interpret=True)))
    # pytree form
    tree_x = {"w": x.reshape(m, 30, 10), "b": x[:, :4]}
    tree_prev = {"w": prev.reshape(30, 10), "b": prev[:4]}
    got = masked_agg_pytree(tree_x, empty, tree_prev, interpret=True)
    for k in tree_x:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(tree_prev[k]))


# ---------------------------------------------------------------------------
# fused batched family aggregation
# ---------------------------------------------------------------------------


# The exactness contract is between JITTED programs — that's how the hot
# path runs both sides (the whole sweep is one jit). Op-by-op eager dispatch
# of the pure-jnp reference can fuse multiply+reduce differently at ulp
# level, so every bitwise assertion below compares jitted callables.
_fused_jit = jax.jit(
    lambda x, mask, op, prev, p, block_n: fused_masked_agg(
        x, mask, op, prev, p, block_n=block_n, interpret=True),
    static_argnames="block_n")
_fused_ref_jit = jax.jit(fused_masked_agg_ref)


def _fused_case(key, B, m, n, dtype=jnp.float32, mask_kind="random"):
    x = jax.random.normal(key, (B, m, n), jnp.float32).astype(dtype)
    if mask_kind == "zeros":
        mask = jnp.zeros((B, m), bool)
    elif mask_kind == "ones":
        mask = jnp.ones((B, m), bool)
    else:
        mask = jax.random.uniform(jax.random.fold_in(key, 1), (B, m)) < 0.5
    prev = jax.random.normal(jax.random.fold_in(key, 2), (B, n),
                             jnp.float32).astype(dtype)
    p = jax.random.uniform(jax.random.fold_in(key, 3), (B, m),
                           minval=0.05, maxval=1.0)
    ops = jnp.asarray([(OP_MEAN, OP_ALL, OP_KNOWN_P)[b % 3]
                       for b in range(B)], jnp.int32)
    return x, mask, ops, prev, p


@pytest.mark.parametrize("B,m,n,mask_kind", [
    (4, 8, 512, "random"),
    (3, 13, 257, "random"),      # m not a multiple of 8, n not of block
    (2, 3, 100, "zeros"),        # no client active on any trajectory
    (2, 5, 130, "ones"),         # every client active
    (5, 100, 1000, "random"),
])
def test_fused_masked_agg_vs_ref(B, m, n, mask_kind):
    key = jax.random.PRNGKey(B * m + n)
    x, mask, ops, prev, p = _fused_case(key, B, m, n, mask_kind=mask_kind)
    got = _fused_jit(x, mask, ops, prev, p, block_n=128)
    ref = _fused_ref_jit(x, mask, ops, prev, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the [B, m, n] entry and an explicit vmap over the 2-D kernel agree
    via_vmap = jax.jit(jax.vmap(lambda *a: fused_masked_agg(
        *a, block_n=128, interpret=True)))(
        x, mask, ops, prev, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(via_vmap))


def test_fused_masked_agg_zero_active_preserves_prev():
    """An all-inactive trajectory returns the previous server params under
    EVERY opcode (mean is guarded; the delta branches weight by the mask)."""
    key = jax.random.PRNGKey(9)
    B, m, n = 3, 7, 200
    x, _, _, prev, p = _fused_case(key, B, m, n)
    mask = jnp.zeros((B, m), bool)
    ops = jnp.asarray([OP_MEAN, OP_ALL, OP_KNOWN_P], jnp.int32)
    out = fused_masked_agg(x, mask, ops, prev, p, block_n=128,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prev))


def test_fused_masked_agg_bf16_fp32_accumulation():
    """bf16 inputs accumulate in fp32: the kernel output matches the fp32
    oracle run on the SAME bf16-quantized inputs exactly (no bf16-precision
    reduction error on top of the input quantization)."""
    key = jax.random.PRNGKey(21)
    B, m, n = 4, 16, 513
    x, mask, ops, prev, p = _fused_case(key, B, m, n, dtype=jnp.bfloat16)
    got = _fused_jit(x, mask, ops, prev, p, block_n=256)
    assert got.dtype == jnp.float32
    ref = _fused_ref_jit(x, mask, ops, prev, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # and stays close to the full-fp32 computation (quantization error only)
    full = _fused_ref_jit(x.astype(jnp.float32), mask, ops,
                          prev.astype(jnp.float32), p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


def _check_fused(m, n, bits, op):
    mask = jnp.asarray([(bits >> i) & 1 for i in range(m)], jnp.float32)
    x = jnp.arange(m * n, dtype=jnp.float32).reshape(m, n) / (m * n)
    prev = jnp.linspace(-1.0, 1.0, n)
    p = jnp.linspace(0.1, 0.9, m)
    got = _fused_jit(x, mask, jnp.int32(op), prev, p, block_n=128)
    ref = _fused_ref_jit(x, mask, jnp.int32(op), prev, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


if HAVE_HYPOTHESIS:

    @given(st.integers(1, 12), st.integers(1, 300), st.integers(0, 2 ** 12 - 1),
           st.integers(0, 2))
    @settings(max_examples=25, deadline=None)
    def test_fused_masked_agg_property(m, n, bits, op):
        _check_fused(m, n, bits, op)

else:
    _rng_f = np.random.default_rng(1)
    _FCASES = (
        [(1, 1, 0, 0), (1, 1, 1, 2), (12, 300, 0, 1),
         (12, 300, 2 ** 12 - 1, 2)]
        + [(int(_rng_f.integers(1, 13)), int(_rng_f.integers(1, 301)),
            int(_rng_f.integers(0, 2 ** 12)), int(_rng_f.integers(0, 3)))
           for _ in range(21)]
    )

    @pytest.mark.parametrize("m,n,bits,op", _FCASES)
    def test_fused_masked_agg_property(m, n, bits, op):
        _check_fused(m, n, bits, op)


def test_fused_agg_pytree_matches_engine_branches():
    """Per-leaf fused aggregation == the engine's branch math over a ragged
    params pytree, for every opcode.

    Tolerance note: kernel and engine are separate jitted programs here, and
    XLA may schedule the kernel's fused three-branch body's reduces
    differently from the engine's standalone reduce — up to one ulp apart on
    CPU. The sweep-level tests (test_kernel_sweep.py) pin exact program-to-
    program equality at the engine's real shapes; this cross-program check
    asserts the documented <=1-ulp contract."""
    from repro.core.algorithms import masked_mean, weighted_sum
    key = jax.random.PRNGKey(13)
    m = 6
    x_star = {"w1": jax.random.normal(key, (m, 10, 3)),
              "b1": jax.random.normal(jax.random.fold_in(key, 1), (m, 3)),
              "s": jax.random.normal(jax.random.fold_in(key, 2), (m,))}
    server = {"w1": jax.random.normal(jax.random.fold_in(key, 3), (10, 3)),
              "b1": jax.random.normal(jax.random.fold_in(key, 4), (3,)),
              "s": jax.random.normal(jax.random.fold_in(key, 5), ())}
    active = jnp.asarray([1, 0, 1, 1, 0, 0], bool)
    p = jax.random.uniform(jax.random.fold_in(key, 6), (m,),
                           minval=0.1, maxval=1.0)

    kern = jax.jit(fused_agg_pytree, static_argnames="op")

    def engine(op):
        if op == OP_MEAN:
            return masked_mean(x_star, active)  # any_active is True here
        w = active.astype(jnp.float32) / m
        if op == OP_KNOWN_P:
            w = active.astype(jnp.float32) / jnp.maximum(p, 1e-3) / m
        delta = jax.tree.map(lambda xs, s: xs - s[None], x_star, server)
        return jax.tree.map(lambda s, u: s + u, server,
                            weighted_sum(delta, w))

    for op in (OP_MEAN, OP_ALL, OP_KNOWN_P):
        got = kern(x_star, active, op, server, p)
        want = jax.jit(lambda op=op: engine(op))()
        for k in x_star:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=3e-7, atol=3e-7)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------


def test_resolve_backend_defaults_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    # this suite runs on CPU (conftest pins JAX_PLATFORMS=cpu)
    assert resolve_backend() == "interpret"
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("compiled") == "compiled"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert resolve_backend() == "xla"
    assert resolve_backend("interpret") == "interpret"   # arg wins over env
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_backend("triton")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "cuda")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_backend()


def test_resolve_use_kernel_env(monkeypatch):
    monkeypatch.delenv("REPRO_USE_KERNEL", raising=False)
    assert use_kernel_default() is False
    assert resolve_use_kernel(None) is False
    assert resolve_use_kernel(True) is True
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")
    assert use_kernel_default() is True
    assert resolve_use_kernel(None) is True
    assert resolve_use_kernel(False) is False            # arg wins over env
    monkeypatch.setenv("REPRO_USE_KERNEL", "off")
    assert use_kernel_default() is False


def test_fused_agg_xla_backend_bitwise_vs_interpret():
    """The always-available XLA fallback path and the interpret-mode kernel
    implement the same fp32 math: bitwise-equal outputs."""
    key = jax.random.PRNGKey(17)
    x, mask, ops, prev, p = _fused_case(key, 4, 9, 300)
    call = jax.jit(fused_agg, static_argnames=("backend", "block_n"))
    a = call(x, mask, ops, prev, p, backend="interpret", block_n=128)
    b = call(x, mask, ops, prev, p, backend="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_ops_table_covers_exactly_the_empty_state_family():
    from repro.core.algorithms import AlgorithmSpec, algo_family
    assert set(FUSED_OPS) == set(algo_family("fedavg"))
    assert AlgorithmSpec(algo_family("fedavg")).fusable
    assert AlgorithmSpec(("fedpbc",)).fusable
    assert not AlgorithmSpec(("fedau",)).fusable
    assert not AlgorithmSpec(("mifa",)).fusable


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,t,d,win,cap,dtype", [
    (2, 2, 256, 64, 0, 0.0, jnp.float32),
    (1, 3, 256, 128, 0, 0.0, jnp.float32),
    (1, 2, 256, 64, 128, 0.0, jnp.float32),     # sliding window
    (1, 2, 128, 64, 0, 50.0, jnp.float32),      # gemma softcap
    (1, 2, 256, 64, 0, 0.0, jnp.bfloat16),
])
def test_flash_attention_sweep(b, h, t, d, win, cap, dtype):
    key = jax.random.PRNGKey(t + d)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, t, d),
                                 jnp.float32).astype(dtype) for i in range(3))
    out = flash_attention(q, k, v, window=win, logit_softcap=cap,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, window=win, logit_softcap=cap)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("win,cap", [(0, 0.0), (64, 0.0), (0, 30.0)])
def test_flash_attention_grad_matches_reference(win, cap):
    """The custom_vjp backward (the float32 reference's VJP, recomputed
    from the saved q, k, v) against jax.grad of the reference itself, in
    float32. The two gradients share the backward math; they differ only
    through the cotangent, which flows back from the kernel's forward
    output (online softmax, a different reduction order from the
    reference's one-shot softmax). That difference is the forward
    tolerance of test_flash_attention_sweep (2e-3), so the same bound
    holds here."""
    key = jax.random.PRNGKey(31)
    b, h, t, d = 1, 2, 256, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, t, d))
               for i in range(3))
    w = jax.random.normal(jax.random.fold_in(key, 3), (b, h, t, d))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, window=win, logit_softcap=cap) * w)

    kern = jax.jit(jax.grad(loss(lambda *a, **kw: flash_attention(
        *a, **kw, interpret=True)), argnums=(0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(loss(flash_attention_ref),
                           argnums=(0, 1, 2)))(q, k, v)
    for g_kern, g_ref in zip(kern, ref):
        np.testing.assert_allclose(np.asarray(g_kern), np.asarray(g_ref),
                                   rtol=2e-3, atol=2e-3)


def test_gqa_wrapper():
    key = jax.random.PRNGKey(3)
    b, t, h, kv, d = 1, 128, 4, 2, 64
    q = jax.random.normal(key, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, d))
    out = gqa_flash_attention(q, k, v, interpret=True)
    from repro.models.attention import attention
    ref = attention(q, k, v, kind="full", chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# attention dispatch (the masked_agg-style backend audit for the LM path)
# ---------------------------------------------------------------------------


def _qkv_gqa(key, b=2, t=64, h=4, kv=2, d=16):
    q = jax.random.normal(key, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, d))
    return q, k, v


def test_resolve_attention_backend_defaults_and_env(monkeypatch):
    """CPU default is "xla" (the chunked reference IS the fast CPU path);
    REPRO_KERNEL_BACKEND and the explicit arg override it, unknown names
    raise."""
    from repro.kernels.dispatch import resolve_attention_backend
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    expect = "compiled" if jax.default_backend() == "tpu" else "xla"
    assert resolve_attention_backend() == expect
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert resolve_attention_backend() == "interpret"
    assert resolve_attention_backend("xla") == "xla"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_attention_backend("metal")


def test_attention_cpu_routing_is_bitwise_reference(monkeypatch):
    """On CPU the dispatched model entry resolves to the pure-XLA reference
    — routing through the dispatch layer must not change a single bit of
    the model forward."""
    from repro.models.attention import attention, attention_ref
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    if jax.default_backend() != "cpu":
        pytest.skip("CPU routing contract")
    q, k, v = _qkv_gqa(jax.random.PRNGKey(0))
    for kw in (dict(kind="full"), dict(kind="swa", window=32),
               dict(kind="full", logit_softcap=30.0),
               dict(kind="chunked", window=16)):
        out = attention(q, k, v, **kw)
        ref = attention_ref(q, k, v, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("kw", [
    dict(kind="full"),
    dict(kind="swa", window=32),
    dict(kind="full", logit_softcap=30.0),
])
def test_attention_interpret_kernel_parity(kw):
    """The Pallas path (interpret on CPU) vs the pure-XLA reference, GQA
    shapes in the model's [B, T, H, D] layout — the flash_attention row of
    the dispatch tolerance table."""
    from repro.kernels.dispatch import attention as dispatch_attention
    from repro.models.attention import attention_ref
    q, k, v = _qkv_gqa(jax.random.PRNGKey(7))
    out = dispatch_attention(q, k, v, backend="interpret", **kw)
    ref = attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_attention_dispatch_gates_unsupported_to_reference():
    """Shapes/masks the kernel doesn't cover fall back to the reference
    bitwise even when a kernel backend is forced: block-local masks,
    cross-length prefill (q_offset), and T not divisible by the block."""
    from repro.kernels.dispatch import attention as dispatch_attention
    from repro.models.attention import attention_ref
    q, k, v = _qkv_gqa(jax.random.PRNGKey(9))
    out = dispatch_attention(q, k, v, kind="chunked", window=16,
                             backend="interpret")
    ref = attention_ref(q, k, v, kind="chunked", window=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # ragged T: 192 % min(128, 192) != 0 -> reference
    q2, k2, v2 = _qkv_gqa(jax.random.PRNGKey(10), t=192)
    out2 = dispatch_attention(q2, k2, v2, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out2),
                                  np.asarray(attention_ref(q2, k2, v2)))


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,t,d,chunk", [
    (1, 1, 64, 64, 64), (2, 2, 128, 64, 64), (1, 2, 256, 128, 64),
    (1, 1, 192, 64, 64),
])
def test_rwkv6_chunk_sweep(b, h, t, d, chunk):
    key = jax.random.PRNGKey(b * t + d)
    r, k, v = (0.5 * jax.random.normal(jax.random.fold_in(key, i),
                                       (b, h, t, d), jnp.float32)
               for i in range(3))
    w = jnp.exp(-jnp.exp(-3.0 + 0.5 * jax.random.normal(
        jax.random.fold_in(key, 3), (b, h, t, d))))
    u = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (h, d))
    s0 = 0.1 * jax.random.normal(jax.random.fold_in(key, 5), (b, h, d, d))
    o, sT = rwkv6_chunk(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    oref, sref = rwkv6_chunk_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sref), rtol=3e-3, atol=3e-3)


def test_rwkv6_kernel_matches_model_path():
    """Kernel == the model's _wkv_chunk_scan (two independent implementations)."""
    from repro.models.rwkv import _wkv_chunk_scan
    key = jax.random.PRNGKey(11)
    b, h, t, d = 1, 2, 128, 64
    r, k, v = (0.5 * jax.random.normal(jax.random.fold_in(key, i),
                                       (b, t, h, d), jnp.float32)
               for i in range(3))
    w = jnp.exp(-jnp.exp(-3.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 3), (b, t, h, d))))
    u = 0.2 * jax.random.normal(jax.random.fold_in(key, 4), (h, d))
    s0 = jnp.zeros((b, h, d, d))
    o_model, s_model = _wkv_chunk_scan(r, k, v, w, u, s0)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    o_kern, s_kern = rwkv6_chunk(tr(r), tr(k), tr(v), tr(w), u, s0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(tr(o_kern)), np.asarray(o_model),
                               rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(s_kern), np.asarray(s_model),
                               rtol=3e-3, atol=3e-3)
