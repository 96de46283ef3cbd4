"""Compile rehearsals for one TPU v5e chip, made without the chip.

The TPU compiler is installed even where no TPU is attached, and it
compiles for a described topology. These tests compile the kernels of the
main path at real widths for ``v5e:2x2`` and check that the compiler
accepts them (block tiling, VMEM use) and that the compiled program holds
the Mosaic kernel (``tpu_custom_call``). Nothing runs, so nothing here says
anything about results or time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file. Where it cannot be
described, the fixture skips. ``jax.default_backend()`` is the CPU here, so
the kernels are called with ``interpret=False`` (or ``backend="compiled"``)
explicitly.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.sources import example_sampler
from repro.kernels.dispatch import fused_agg_pytree
from repro.kernels.flash_attention import flash_attention
from repro.kernels.masked_agg import fused_masked_agg


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler plug-in in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, kernel: str):
    """Compile ``fn`` for the described chip and check that the program
    runs the Mosaic kernel under its stable ``name=``: the kernel's
    instruction carries it (``vmap_`` prefixed under vmap), and a device
    trace names the kernel's op by that instruction."""
    lowered = jax.jit(fn).lower(*args)
    assert kernel in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%[\w.]*{kernel}[\w.]* = ", text), kernel
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _agg_args(sharding, lead, m, n):
    s = functools.partial(_spec, sharding)
    return (s(lead + (m, n)), s(lead + (m,)), s(lead, jnp.int32),
            s(lead + (n,)), s(lead + (m,)))


def test_fused_agg_vmap_on_mlp_leaves(one_chip):
    """The sweep hot path: the dispatch layer's per-leaf aggregation of the
    paper-protocol MLP (32x64x10), vmapped over B=8 trajectories at m=100."""
    B, m = 8, 100
    s = functools.partial(_spec, one_chip)
    leaves = {"w1": (32, 64), "b1": (64,), "w2": (64, 10), "b2": (10,)}
    x_star = {k: s((B, m) + v) for k, v in leaves.items()}
    server = {k: s((B,) + v) for k, v in leaves.items()}
    agg = jax.vmap(functools.partial(fused_agg_pytree, backend="compiled"))
    _compile(agg, x_star, s((B, m), jnp.bool_), s((B,), jnp.int32), server,
             s((B, m)), kernel="fused_masked_agg")


@pytest.mark.parametrize("m", [100, 256, 1000])
@pytest.mark.parametrize("form", ["2d", "vmap", "3d"])
def test_fused_masked_agg_lowers(one_chip, form, m):
    """Every form of the fused kernel at every client count fits VMEM:
    ``[m, 65536]`` single-trajectory, the 2-D kernel under ``vmap`` and the
    ``[B, m, n]`` entry, at B=4."""
    n = 65536
    kernel = functools.partial(fused_masked_agg, interpret=False)
    if form == "2d":
        compiled = _compile(kernel, *_agg_args(one_chip, (), m, n),
                            kernel="fused_masked_agg")
    else:
        fn = jax.vmap(kernel) if form == "vmap" else kernel
        compiled = _compile(fn, *_agg_args(one_chip, (4,), m, n),
                            kernel="fused_masked_agg")
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("t", [128, 256])
def test_flash_attention_forward_and_grad_lower(one_chip, t):
    """smollm-135m's attention shape (9 heads of 64, bf16) at the training
    lengths the federated clients use: the forward kernel and its
    custom_vjp gradient both compile."""
    q, k, v = (_spec(one_chip, (2, 9, t, 64), jnp.bfloat16) for _ in range(3))
    attn = functools.partial(flash_attention, interpret=False)
    _compile(attn, q, k, v, kernel="flash_attention")

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    # value_and_grad, as the round engine calls it: the loss keeps the
    # forward kernel live next to the backward pass
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, k, v,
             kernel="flash_attention")


def test_in_row_sampling_is_not_materialised(one_chip):
    """mlp-family's round of draws (B=16 trajectories x m=100 clients x 5
    steps x 32) resolved inside index rows 1,024 wide, 16 times
    mlp-family's: the chip's compiler fuses the compare-and-select into its
    reduction, so the program's scratch stays under half of the
    ``[B, m, s, b, per_client]`` int32 operand it would take materialised."""
    B, m, s, b, n, d, per_client = 16, 100, 5, 32, 5000, 32, 1024

    def sample(keys, idx, x, y):
        take = example_sampler(x, y, local_steps=s, batch_size=b)
        return jax.vmap(lambda k, ix: take(k, ix, None))(keys, idx)

    compiled = jax.jit(sample).lower(
        _spec(one_chip, (B, 2), jnp.uint32),
        _spec(one_chip, (B, m, per_client), jnp.int32),
        _spec(one_chip, (n, d)), _spec(one_chip, (n,), jnp.int32)).compile()
    operand = B * m * s * b * per_client * 4
    assert compiled.memory_analysis().temp_size_in_bytes < operand // 2
