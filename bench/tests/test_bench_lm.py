"""The language-model client path against its plain reference, on the CPU
at a small size with seeded random weights: SmolLM-135M's block cut to
``reduced()`` widths (``bench.refs.smollm_135m``), and the
``smollm-family`` driver's whole check path over such a model."""
import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.drivers import lm_sweep
from bench.refs import smollm_135m as ref
from repro.configs import get_config, reduced
from repro.models import model as lm

ROOT = Path(__file__).resolve().parents[2]
D_MODEL, LAYERS, SEQ = 64, 2, 32


def small(dtype="float32"):
    return dataclasses.replace(
        reduced(get_config("smollm-135m"), d_model=D_MODEL, layers=LAYERS),
        dtype=dtype)


def arch_of(cfg) -> ref.Arch:
    a = cfg.attention
    return ref.Arch(layers=cfg.num_layers, d_model=cfg.d_model,
                    d_ff=cfg.d_ff, heads=a.num_heads,
                    kv_heads=a.num_kv_heads, head_dim=cfg.head_dim,
                    vocab=cfg.vocab_size, rope_theta=a.rope_theta,
                    norm_eps=cfg.norm_eps)


def test_init_is_the_references():
    """The program's float32 masters and the reference's weights are the
    same draws, bit for bit: the change of the server is measured from
    one starting point."""
    cfg = small("bfloat16")
    key = jax.random.PRNGKey(7)
    program = ref.from_program(jax.jit(lm.init_params, static_argnums=1)(
        key, lm.master_config(cfg)))
    reference = ref.init_params(key, arch_of(cfg))
    assert set(program) == set(reference)
    for k, v in reference.items():
        np.testing.assert_array_equal(program[k], np.asarray(v), err_msg=k)


# (compute dtype, remat, loss tolerance, gradient tolerance). Float32: the
# two differ only in the order of float32 sums and where the attention
# scale is applied, a few ulps of the loss and about 1e-6 of the gradient.
# bfloat16: the program rounds activations to bfloat16 where the reference
# in that mode rounds them too, but not at the same points (the attention
# kernel's and the reference's float32 softmax, the cross-entropy's
# chunks); each rounding is 2^-9 relative, and over two layers the
# gradient's relative distance stays under 2e-2.
CASES = [("float32", False, 1e-6, 1e-5), ("float32", True, 1e-6, 1e-5),
         ("bfloat16", True, 5e-3, 2e-2)]


@pytest.mark.parametrize("dtype,remat,loss_tol,grad_tol", CASES)
def test_loss_and_gradient_match_reference(dtype, remat, loss_tol, grad_tol):
    """The program's loss and its gradient with respect to the float32
    masters against the reference's at the same compute dtype."""
    cfg = small(dtype)
    key = jax.random.PRNGKey(3)
    params = lm.init_params(key, lm.master_config(cfg))
    seqs = jax.random.randint(jax.random.PRNGKey(4), (2, SEQ + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch, remat=remat, ce_chunk=16)))(
            params)
        r_loss, r_grad = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch["tokens"], batch["labels"],
                               arch_of(cfg), dtype, block=8, ce_block=16)))(
            ref.init_params(key, arch_of(cfg)))
    assert {str(x.dtype) for x in jax.tree.leaves(grad)} == {"float32"}
    assert abs(float(loss) - float(r_loss)) <= loss_tol * float(r_loss)
    grad = ref.from_program(grad)
    for k, g in r_grad.items():
        g = np.asarray(g, np.float64)
        dist = np.linalg.norm(grad[k] - g) / max(np.linalg.norm(g), 1e-30)
        assert dist <= grad_tol, (k, dist)


def _workload(seed=28):
    """The cell's driver over SmolLM-135M at ``reduced()`` widths: the
    configuration's model section and the sweep's model knobs steered to
    the small float32 model, everything else as the cell has it."""
    config = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/lm-family.json").read_text())
    cfg = small()
    a = arch_of(cfg)
    config["model"] = dict(
        config["model"], num_hidden_layers=a.layers, hidden_size=a.d_model,
        intermediate_size=a.d_ff, num_attention_heads=a.heads,
        num_key_value_heads=a.kv_heads, head_dim=a.head_dim,
        vocab_size=a.vocab)
    traffic = dict(traffic, seq=SEQ, batch=2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lm_sweep, "program_config", lambda config: cfg)
        wl = lm_sweep.Workload(config, traffic, seed)
    knobs = dict(lm_reduced=True, lm_d_model=D_MODEL, lm_layers=LAYERS)
    wl.spec = dataclasses.replace(wl.spec, **knobs)
    wl.early_spec = dataclasses.replace(wl.early_spec, **knobs)
    return wl


@pytest.fixture(scope="module")
def driven():
    """One window call and the check, as a run makes them."""
    wl = _workload()
    wl.prepare()
    wl.step()
    wl.release()
    return wl, wl.check()


def test_sweep_rounds_match_reference(driven):
    """A fedpbc + fedavg ``run_sweep`` call against the reference over its
    first ``loss_rounds`` rounds. Active counts: the same draws, exactly.
    Loss and each leaf's change: float32 on both sides, apart by the order
    of float32 sums over 4 rounds of 2 local steps, far under 1e-4."""
    wl, readings = driven
    assert wl.failed == 0
    assert readings["active_mismatch"] == 0
    assert readings["loss_gap"] < 1e-5
    assert readings["change_gap"] < 1e-4


@pytest.mark.parametrize("name", sorted(ref.CONTROLS))
def test_controls_read_far_above_the_sound_program(driven, name):
    """The parent's numerics (bfloat16 masters and mean) and float32
    masters with a bfloat16 mean, each put in the program's place, move
    the server's change by far more than the sound program's gap."""
    wl, readings = driven
    control = wl.control(name)
    assert control["active_mismatch"] == 0
    assert control["change_gap"] > 100 * max(readings["change_gap"], 1e-7)


def test_client_state_bytes_counts_the_masters(driven):
    """B x m x P x 4: the float32 client stacks of a call."""
    wl, _ = driven
    assert wl.client_state_bytes() == (
        wl.trajectories * wl.spec.num_clients * small().param_count() * 4)


def test_flop_counts_from_shapes():
    """The benchmark's parameter count is the program's at the published
    widths, and a traj-round is every client's steps of 3 forward passes
    and an update."""
    from bench import lm_flops

    config = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    arch = lm_sweep.arch_of(config)
    assert lm_flops.params(arch) == get_config("smollm-135m").param_count() \
        == config["model"]["parameters"]
    per_seq = lm_flops.forward_flops_per_sequence(arch, 512)
    assert lm_flops.train_flops_per_traj_round(
        arch, seq=512, clients=4, local_steps=2, batch=4) == \
        4 * 2 * (3 * per_seq * 4 + 2 * lm_flops.params(arch))
    seconds, bound = lm_flops.attention_roofline_seconds(arch, 512, 197e12,
                                                         819e9)
    assert bound == "bytes" and seconds == pytest.approx(
        4 * 9 * 512 * 64 * 2 / 819e9)
