import os

# Tests run on the CPU and see ONE device (the dry run sets its own
# 512-device flag in repro.launch.dryrun.main, which no test calls).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses

import jax
import pytest

from repro.configs import get_config, reduced


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def compiles_once():
    """The suite-wide compile-counter pin: every runner stage passed in
    must hold exactly ONE jit cache entry — the ROADMAP contract that all
    swept axes (hparams, seeds, algo_id, strategies) ride traced inputs.
    No-ops gracefully where jit cache introspection is unavailable, like
    the per-file ``hasattr(fn, "_cache_size")`` guards it replaces."""
    from repro.analysis.sanitize import assert_no_new_compiles

    def check(*fns, expect_total=1):
        assert_no_new_compiles(*fns, expect_total=expect_total)

    return check


def reduced_f32(arch: str, **kw):
    cfg = reduced(get_config(arch), **kw)
    return dataclasses.replace(cfg, dtype="float32")
