"""Device-resident data sources for the multi-round scan engine.

A ``DataSource`` is the functional counterpart of the host-side batch
generators in ``repro.data.synthetic``: the full dataset (or the generator's
parameters) lives on device, and one round's per-client batches are sampled
*inside* the jit program::

    sample(ds_state, round, key) -> (batches, ds_state)

``batches`` is the pytree ``round_fn`` expects — leading ``[m, s, ...]`` axes
(per client, per local step). Because sampling is pure and device-side, K
rounds can run under a single ``jax.lax.scan`` (``repro.core.federated
.run_rounds``) with no per-round host dispatch or H2D transfer.

Sources that need no evolving state return ``ds_state`` unchanged (an empty
tuple); randomness comes from the per-round ``key`` the engine derives via
``fold_in(data_key, round)``, so trajectories are reproducible and identical
between the scanned and sequential paths.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


def draw_indices(key, client_idx, clients, local_steps: int,
                 batch_size: int):
    """Dataset indices of one round's draws, ``[C, s, b]``:
    ``client_idx[clients[c], pick[c, s, b]]`` with ``pick`` drawn uniformly
    from ``[0, per_client)``; ``clients=None`` means every client in order
    (the dense round), else a ``[C]`` cohort.

    Each draw is resolved inside its client's row, not by an element gather
    over the ``[m, per_client]`` table: its position is compared with the
    row's iota and the one match summed. That is exact integer work which
    fuses into a reduction, so the ``[C, s, b, per_client]`` operand is
    never materialised and the index table is only row-gathered (for a
    cohort). It costs ``per_client`` integer operations a draw: at the
    row widths the tasks use (64 and below) far less than an element
    gather costs a draw on the TPU.
    """
    per_client = client_idx.shape[1]
    rows = client_idx if clients is None else client_idx[clients]
    pick = jax.random.randint(
        key, (rows.shape[0], local_steps, batch_size), 0, per_client)
    hit = pick[..., None] == jnp.arange(per_client, dtype=pick.dtype)
    return jnp.sum(jnp.where(hit, rows[:, None, None, :], 0), axis=-1,
                   dtype=client_idx.dtype)


def example_sampler(x, y, *, local_steps: int, batch_size: int):
    """``take(key, client_idx, clients) -> {"x", "y"}``: one round's
    batches, drawn by ``draw_indices`` and fetched by ONE row gather.

    ``x [n, ...]`` (32-bit) and ``y [n]`` are packed once into an
    ``[n, f + 1]`` int32 table: the features' bits, then the label. Both
    come back bit-exactly. The label is not stored as a float32 bit
    pattern: small integers are float32 denormals, which the TPU may flush
    to zero.
    """
    n, shape = x.shape[0], x.shape[1:]
    # built where the source factory is called, outside the round engine's
    # sampling scope: name it so that its device time still counts there
    with jax.named_scope("fed.sample"):
        table = jnp.concatenate(
            [jax.lax.bitcast_convert_type(x.reshape(n, -1), jnp.int32),
             y.astype(jnp.int32)[:, None]], axis=1)

    def take(key, client_idx, clients):
        rows = table[draw_indices(key, client_idx, clients, local_steps,
                                  batch_size)]
        feats = jax.lax.bitcast_convert_type(rows[..., :-1], x.dtype)
        return {"x": feats.reshape(rows.shape[:-1] + shape),
                "y": rows[..., -1].astype(y.dtype)}

    return take


@dataclass(frozen=True)
class DataSource:
    init: Callable[..., Any]      # (key) -> ds_state
    sample: Callable[..., Any]    # (ds_state, round, key) -> (batches, ds_state)
    name: str = ""
    # cohort mode (repro.scale): (ds_state, round, key, cohort [C] int32) ->
    # ([C, s, ...] batches, ds_state) — only the sampled clients' batches are
    # materialized, so per-round data memory is O(C) not O(m). With
    # cohort = arange(m) the draw is bit-for-bit the dense ``sample``.
    sample_cohort: Optional[Callable[..., Any]] = None


def classification_source(x, y, client_idx, *, local_steps: int,
                          batch_size: int) -> DataSource:
    """Device-resident sampler over a partitioned classification dataset.

    ``x [n, ...]``, ``y [n]`` and ``client_idx [m, per_client]`` are captured
    as jit constants; each round draws ``[m, s, b]`` examples with replacement
    from every client's shard (same distribution as the host-side
    ``federated_classification_batches``).
    """
    client_idx = jnp.asarray(client_idx)
    take = example_sampler(jnp.asarray(x), jnp.asarray(y),
                           local_steps=local_steps, batch_size=batch_size)

    def init(key):
        return ()

    def sample(ds_state, t, key):
        return take(key, client_idx, None), ds_state

    def sample_cohort(ds_state, t, key, cohort):
        return take(key, client_idx, cohort), ds_state

    return DataSource(init, sample, "classification", sample_cohort)


def traced_classification_source(shared, *, local_steps: int,
                                 batch_size: int) -> DataSource:
    """Traced counterpart of ``classification_source``: nothing about the
    dataset is a jit constant.

    The dataset arrays travel in ``shared`` (``{"x": [n, ...], "y": [n]}``,
    typically traced jit inputs — the factory is meant to be called *inside*
    a traced function, mirroring the sweep engine's ``link_factory``), and the
    per-client partition travels in ``ds_state`` (``{"idx": [m, per_client]}``),
    so a Dirichlet-alpha re-partition or a dataset swap of the same shapes
    reuses the compiled program instead of rebuilding it.

    ``init(key, data) -> ds_state`` takes the per-trajectory data pytree (the
    batched-runner protocol; the key is accepted for signature symmetry and
    unused). ``sample`` draws the same indices as ``classification_source`` —
    given equal arrays the two sources produce bit-for-bit equal batches.
    The packed example table (``example_sampler``) is built when the factory
    is called: the sweep engine calls it once per compiled call, outside the
    round scan.
    """

    take = example_sampler(shared["x"], shared["y"], local_steps=local_steps,
                           batch_size=batch_size)

    def init(key, data):
        return data

    def sample(ds_state, t, key):
        return take(key, ds_state["idx"], None), ds_state

    def sample_cohort(ds_state, t, key, cohort):
        return take(key, ds_state["idx"], cohort), ds_state

    return DataSource(init, sample, "classification_traced", sample_cohort)


def traced_lm_source(shared, *, local_steps: int,
                     batch_size: int) -> DataSource:
    """Traced next-token-prediction counterpart of
    ``traced_classification_source``.

    The corpus travels in ``shared`` (``{"toks": [n, T+1]}`` int32 sequences,
    each of length context+1 so tokens/labels come from one slice), the
    per-client Dirichlet partition in ``ds_state`` (``{"idx":
    [m, per_client]}`` sequence indices). Each round draws ``[m, s, b]``
    sequences with replacement from every client's shard — the index draw is
    the same ``randint`` protocol as the classification sources, so the LM
    task rides the sweep engine's compiled programs with nothing about the
    dataset baked in as a constant.
    """

    def init(key, data):
        return data

    def _slice(seqs):
        return {"tokens": seqs[..., :-1], "labels": seqs[..., 1:]}

    def sample(ds_state, t, key):
        sel = draw_indices(key, ds_state["idx"], None, local_steps,
                           batch_size)
        return _slice(shared["toks"][sel]), ds_state

    def sample_cohort(ds_state, t, key, cohort):
        sel = draw_indices(key, ds_state["idx"], cohort, local_steps,
                           batch_size)
        return _slice(shared["toks"][sel]), ds_state

    return DataSource(init, sample, "lm_traced", sample_cohort)


def lm_source(*, num_clients: int, local_steps: int, batch: int, seq: int,
              vocab: int, client_shift: bool = True,
              memory_shape: Optional[Tuple[int, ...]] = None) -> DataSource:
    """Synthetic non-IID token streams generated on device.

    Mirrors ``federated_lm_batches``: each client draws tokens from its own
    half-vocab slice (offset drawn once at ``init``). ``memory_shape`` appends
    a constant ``memory`` leaf of shape ``[m, s, *memory_shape]`` for
    vlm/audio model families.
    """
    m, s = num_clients, local_steps

    def init(key):
        lo = (jax.random.randint(key, (m,), 0, vocab // 2)
              if client_shift else jnp.zeros((m,), jnp.int32))
        return {"lo": lo.astype(jnp.int32)}

    def sample(ds_state, t, key):
        toks = ds_state["lo"][:, None, None, None] + jax.random.randint(
            key, (m, s, batch, seq), 0, vocab // 2)
        toks = toks.astype(jnp.int32)
        batches = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}
        if memory_shape is not None:
            batches["memory"] = 0.1 * jnp.ones((m, s) + tuple(memory_shape))
        return batches, ds_state

    def sample_cohort(ds_state, t, key, cohort):
        C = cohort.shape[0]
        toks = ds_state["lo"][cohort][:, None, None, None] + jax.random.randint(
            key, (C, s, batch, seq), 0, vocab // 2)
        toks = toks.astype(jnp.int32)
        batches = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}
        if memory_shape is not None:
            batches["memory"] = 0.1 * jnp.ones((C, s) + tuple(memory_shape))
        return batches, ds_state

    return DataSource(init, sample, "lm", sample_cohort)


def fixed_source(batches: Pytree) -> DataSource:
    """Every round sees the same ``[m, s, ...]`` batch pytree (the quadratic
    counterexample setups, where each client's objective is deterministic)."""
    batches = jax.tree.map(jnp.asarray, batches)

    def init(key):
        return ()

    def sample(ds_state, t, key):
        return batches, ds_state

    def sample_cohort(ds_state, t, key, cohort):
        return jax.tree.map(lambda b: b[cohort], batches), ds_state

    return DataSource(init, sample, "fixed", sample_cohort)
