"""What decides ``correct``, at a size a test run holds, on the CPU.

For the sweep cell: a run of the harness (everything but its look for a
chip) comes out correct against the plain reference; the server change
over the first ``loss_rounds`` rounds is read from a call of that length
that repeats the window call's first rounds; the control (the reference
computed at a lower precision, put in the program's place) fails at least
one of the cell's limits; and with each fault the cell can have planted
under the timed path (``bench.control.FAULTS``) the run comes out not
correct. The limits are the configuration's own (``bench/configs``).
"""
import contextlib
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, run
from bench.refs import precision

ROOT = Path(__file__).resolve().parents[2]


def _json(rel):
    return json.loads((ROOT / rel).read_text())


def _fails(readings, limits):
    return any(not v <= limits[k] for k, v in readings.items())


@pytest.fixture
def mlp_cell():
    traffic = dict(_json("bench/traffic/sweep-family.json"), clients=8,
                   rounds=4, eval_every=2)
    return run.Cell("tiny-mlp", 1, _json("bench/configs/paper-mlp.json"),
                    traffic, [], [])


def _measure(cell, seed, fault=None):
    with (control.FAULTS[fault]() if fault else contextlib.nullcontext()):
        import jax

        jax.clear_caches()
        return run.measure(cell, seed, 0.3, False, require_tpu=False)


# the last three read the highest whole-call change gaps on the chip
@pytest.mark.parametrize("seed", [2**31 + 11, 12, 2150000018, 2150000031,
                                  2150000033])
def test_sound_run_is_correct(mlp_cell, seed):
    out = run.measure(mlp_cell, seed, 0.3, False, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"


def test_early_call_repeats_the_window_and_its_server_is_compared(mlp_cell):
    """The call over the first ``loss_rounds`` rounds repeats the window
    call's rows; ``change_gap`` compares its server, and the window call's
    in its place fails the limit; a call that does not repeat the window's
    rows stops the check."""
    traffic = dict(mlp_cell.traffic, rounds=6, eval_every=3)
    wl = mlp_cell.driver.Workload(mlp_cell.config, traffic,
                                  run.program_seed(7))
    wl.prepare()
    wl.step()
    n, k = wl.early_spec.rounds, wl.spec.rounds
    rows, refs = wl.rows(wl.last), wl.reference(wl.last)
    early_cells, early = wl.call_servers(wl.early_spec)
    assert [c.loss.shape[1] for c in early_cells] == [n] * len(early_cells)
    assert mlp_cell.driver.same_rounds(early_cells, wl.last, n)
    for row, server in zip(rows, early):
        for leaf, value in server.items():
            np.testing.assert_array_equal(row["servers"][n][leaf], value)
    readings = wl.compare(rows, refs)
    assert readings["change_gap"] <= mlp_cell.config["limits"]["change_gap"]
    late = [dict(r, servers={n: r["servers"][k], k: r["servers"][k]})
            for r in rows]
    assert not (wl.compare(late, refs)["change_gap"]
                <= mlp_cell.config["limits"]["change_gap"])
    early_cells[0] = dataclasses.replace(early_cells[0],
                                         loss=early_cells[0].loss + 1.0)
    wl.call_servers = lambda spec: (early_cells, early)
    mlp_cell.driver.capture_runners()
    wl.step()           # the window call is the runners' last again
    with pytest.raises(RuntimeError, match="does not repeat"):
        wl.rows(wl.last)


def test_leaf_gaps_skip_still_leaves_and_flag_a_moved_server(mlp_cell):
    gaps = mlp_cell.driver.leaf_gaps
    init = {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)}
    ref = {"a": np.full(4, 1.0), "b": np.full(4, 2.0), "c": np.full(4, 1e-5)}
    prog = {"a": np.full(4, 1.1), "b": np.full(4, 2.0), "c": np.full(4, 1.0)}
    out = gaps(prog, ref, init)
    assert set(out) == {"a", "b"}          # c moves by round-off alone
    # norms 2.2 against 2, 4 against 4; the median leaf's norm is 2
    assert out["a"] == pytest.approx(0.1) and out["b"] == 0.0
    assert gaps(prog, init, init) == {"a": float("inf"), "b": float("inf"),
                                      "c": float("inf")}
    assert gaps(init, init, init) == {"a": 0.0, "b": 0.0, "c": 0.0}
    assert mlp_cell.driver.change_gap(prog, ref, init) == out["a"]


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_planted_fault_is_not_correct(mlp_cell, fault):
    out = _measure(mlp_cell, 3, fault)
    assert not out["correct"], out["checks"]


def test_einsum_precisions_order():
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    exact = jnp.einsum("ik,kj->ij", a, b, precision="highest")
    err = {m: float(jnp.max(jnp.abs(precision.einsum("ik,kj->ij", a, b, m)
                                    - exact)) / float(jnp.max(jnp.abs(exact))))
           for m in ("highest", "bf16")}
    assert err["highest"] < 1e-6 and 1e-4 < err["bf16"] < 1e-2


def test_einsum_gradient_runs_at_the_same_precision():
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 8))

    def f(a, b, mode):
        return jnp.sum(precision.einsum("ik,kj->ij", a, b, mode) ** 2)

    g_hi = jax.grad(f, argnums=(0, 1))(a, b, "highest")
    g_lo = jax.grad(f, argnums=(0, 1))(a, b, "bf16")
    for x, y in zip(g_hi, g_lo):
        rel = float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(x)))
        assert 1e-5 < rel < 5e-2


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_control_fails_a_limit(mlp_cell, seed):
    """The reference at a lower precision, in the program's place, fails at
    least one of the cell's limits. On the chip the control is the program
    at ``high``; the CPU runs every product in float32 whatever it is asked,
    so here the reference takes one bfloat16 pass a product."""
    wl = mlp_cell.driver.Workload(mlp_cell.config, mlp_cell.traffic,
                                  run.program_seed(seed))
    readings = wl.control("bf16")
    assert _fails(readings, mlp_cell.config["limits"]), readings
