"""The synthetic stand-in task used by the paper-table sweeps.

The image datasets of the paper (SVHN/CIFAR-10/CINIC-10) are unavailable
offline; every quantitative suite runs the same protocol (Dirichlet(alpha)
non-IID split, Eq.-9 heterogeneous p_i, s local steps, decaying LR) on the
10-class Gaussian task from ``repro.data.synthetic`` with a 2-layer MLP.

A ``ClassificationTask`` bundles everything the sweep engine vmaps over a
seed axis: the loss, a per-seed ``init_params(key)``, device-side train/test
accuracy evals (they return traced scalars, NOT floats, so they compose with
``vmap``), and the shared device-resident ``DataSource``. The dataset itself
is shared across seeds — per-seed randomness enters through PRNG keys and the
per-seed Eq.-9 ``p_base`` draw, matching the paper's seed protocol.

``ClassificationTask`` captures the dataset and its Dirichlet(alpha)
partition as jit constants, which is fine for a fixed alpha but forces a full
task + compile rebuild per swept alpha. ``TracedClassificationTask``
(``make_traced_classification_task``) is the traced-everything variant the
batched sweep core runs on: the dataset arrays enter the compiled program as
the ``shared`` traced input, the partition travels per hyperparameter point
in ``ds_state`` (``partition(alpha)`` is host-side numpy, identical to the
constant task's split for equal alpha), and the evals take ``(params,
shared)`` so they stay traced too.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import (
    classification_source,
    dirichlet_partition,
    make_classification_data,
    traced_classification_source,
)
from repro.data.sources import DataSource, traced_lm_source


def mlp_init(key, dim=32, classes=10, hidden=64):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (dim, hidden)) * dim ** -0.5,
        "b1": jnp.zeros(hidden),
        "w2": jax.random.normal(k2, (hidden, classes)) * hidden ** -0.5,
        "b2": jnp.zeros(classes),
    }


def mlp_loss(params, batch):
    h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    labels = jax.nn.one_hot(batch["y"], logits.shape[-1])
    return -jnp.mean(jnp.sum(labels * jax.nn.log_softmax(logits), -1))


def mlp_accuracy(params, x, y):
    """Traced accuracy (use ``float(...)`` at the call site for host scalars)."""
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return (jnp.argmax(logits, -1) == y).mean()


@dataclass(frozen=True)
class ClassificationTask:
    loss_fn: Callable[..., Any]
    init_params: Callable[..., Any]     # (key) -> params, vmap-able
    eval_test: Callable[..., Any]       # (params) -> traced scalar accuracy
    eval_train: Callable[..., Any]      # (params) -> traced scalar accuracy
    source: DataSource
    meta: Dict[str, Any] = field(default_factory=dict)


def make_classification_task(*, data_seed=0, num_clients=100, dim=32,
                             classes=10, hidden=64, n_per_class=600, sep=3.0,
                             n_train=5000, alpha=0.1, per_client=64,
                             local_steps=5, batch_size=32) -> ClassificationTask:
    """Build the shared dataset + partition + source + eval closures.

    ``alpha`` shapes the Dirichlet partition (and hence the jit-constant index
    table inside the source), so tasks — unlike Eq.-9 knobs — are rebuilt per
    distinct ``alpha``.
    """
    rng = np.random.default_rng(data_seed)
    x_all, y_all = make_classification_data(data_seed, dim=dim,
                                            num_classes=classes,
                                            n_per_class=n_per_class, sep=sep)
    x, y = x_all[:n_train], y_all[:n_train]
    xt, yt = x_all[n_train:], y_all[n_train:]
    idx, _ = dirichlet_partition(rng, y, num_clients, alpha=alpha,
                                 per_client=per_client)
    source = classification_source(x, y, idx, local_steps=local_steps,
                                   batch_size=batch_size)
    x_j, y_j = jnp.asarray(x), jnp.asarray(y)
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)

    def init_params(key):
        return mlp_init(key, dim=dim, classes=classes, hidden=hidden)

    return ClassificationTask(
        loss_fn=mlp_loss,
        init_params=init_params,
        eval_test=lambda params: mlp_accuracy(params, xt_j, yt_j),
        eval_train=lambda params: mlp_accuracy(params, x_j, y_j),
        source=source,
        meta={"dataset": "gaussian10", "data_seed": data_seed, "dim": dim,
              "classes": classes, "hidden": hidden, "n_train": n_train,
              "n_test": int(len(x_all) - n_train), "alpha": alpha,
              "num_clients": num_clients, "per_client": per_client,
              "local_steps": local_steps, "batch_size": batch_size},
    )


def with_label_noise(shared: Dict[str, Any], key, frac: float = 0.1,
                     classes: int = None) -> Dict[str, Any]:
    """Same-shape label-noise variant of a task's ``shared`` dataset: a
    Bernoulli(``frac``) subset of the train labels is shifted to the next
    class (cyclically). Because the dataset arrays are *traced* inputs of the
    batched sweep runner, the variant rides an existing compiled program —
    no new task, no new partition, no recompile (the ROADMAP "traced dataset
    swaps" path; pinned by ``tests/test_traced_axes.py``)."""
    y = shared["y"]
    c = classes if classes is not None else int(y.max()) + 1
    flip = jax.random.uniform(key, y.shape) < frac
    return dict(shared, y=jnp.where(flip, (y + 1) % c, y))


@dataclass(frozen=True)
class TracedClassificationTask:
    """Alpha-free task bundle for the batched sweep core.

    ``shared`` is the dataset pytree the runner threads through its compiled
    programs as an *unbatched traced input* (``{"x", "y", "xt", "yt"}``);
    ``partition(alpha)`` produces one hyperparameter point's per-client index
    table (host-side numpy, cache the result per alpha); ``source_factory``
    and the evals are meant to be called inside the trace on the traced
    ``shared``.
    """

    loss_fn: Callable[..., Any]
    init_params: Callable[..., Any]      # (key) -> params, vmap-able
    source_factory: Callable[..., DataSource]  # (shared) -> traced DataSource
    eval_test: Callable[..., Any]        # (params, shared) -> traced scalar
    eval_train: Callable[..., Any]       # (params, shared) -> traced scalar
    partition: Callable[[float], np.ndarray]   # (alpha) -> idx [m, per_client]
    shared: Dict[str, Any]
    meta: Dict[str, Any] = field(default_factory=dict)


def make_traced_classification_task(*, data_seed=0, num_clients=100, dim=32,
                                    classes=10, hidden=64, n_per_class=600,
                                    sep=3.0, n_train=5000, per_client=64,
                                    local_steps=5,
                                    batch_size=32) -> TracedClassificationTask:
    """Traced-everything variant of ``make_classification_task``.

    No ``alpha`` argument: the partition is a per-hyperparameter-point input
    (``partition(alpha)``), drawn from a fresh ``default_rng(data_seed)`` so
    it is bit-identical to the constant task's split at the same alpha.
    """
    x_all, y_all = make_classification_data(data_seed, dim=dim,
                                            num_classes=classes,
                                            n_per_class=n_per_class, sep=sep)
    x, y = x_all[:n_train], y_all[:n_train]
    xt, yt = x_all[n_train:], y_all[n_train:]
    shared = {"x": jnp.asarray(x), "y": jnp.asarray(y),
              "xt": jnp.asarray(xt), "yt": jnp.asarray(yt)}

    def partition(alpha: float) -> np.ndarray:
        rng = np.random.default_rng(data_seed)
        idx, _ = dirichlet_partition(rng, y, num_clients, alpha=alpha,
                                     per_client=per_client)
        return idx

    def init_params(key):
        return mlp_init(key, dim=dim, classes=classes, hidden=hidden)

    return TracedClassificationTask(
        loss_fn=mlp_loss,
        init_params=init_params,
        source_factory=lambda sh: traced_classification_source(
            sh, local_steps=local_steps, batch_size=batch_size),
        eval_test=lambda params, sh: mlp_accuracy(params, sh["xt"], sh["yt"]),
        eval_train=lambda params, sh: mlp_accuracy(params, sh["x"], sh["y"]),
        partition=partition,
        shared=shared,
        meta={"dataset": "gaussian10", "data_seed": data_seed, "dim": dim,
              "classes": classes, "hidden": hidden, "n_train": n_train,
              "n_test": int(len(x_all) - n_train),
              "num_clients": num_clients, "per_client": per_client,
              "local_steps": local_steps, "batch_size": batch_size},
    )


# Same field protocol as TracedClassificationTask — the sweep engine and
# grid.py treat both uniformly; the alias exists so call sites can say what
# workload they hold.
LMTask = TracedClassificationTask


def _styled_corpus(rng, *, n, seq_len, vocab, classes):
    """Synthetic byte-level-style corpus: ``n`` sequences of ``seq_len + 1``
    tokens (tokens/labels come from one slice), each tagged with one of
    ``classes`` styles. Style ``c`` draws uniformly from the half-vocab window
    ``[c*V//(2*classes), c*V//(2*classes) + V//2)`` — overlapping slices, so
    styles are statistically (not trivially) separable, mirroring the
    overlapping half-vocab protocol of ``lm_source``."""
    styles = rng.integers(0, classes, size=n).astype(np.int32)
    offsets = (styles * (vocab // 2)) // max(classes, 1)
    toks = offsets[:, None] + rng.integers(
        0, vocab // 2, size=(n, seq_len + 1))
    return toks.astype(np.int32), styles


# Bytes of float32 logits one eval chunk may hold, per trajectory: the
# next-token eval runs the corpus through the model this many sequences'
# worth at a time (at least one), so a published vocabulary never puts a
# whole corpus's [n, T, V] logits on the device at once.
EVAL_LOGITS_BYTES = 1 << 28


def eval_chunk(n: int, seq_len: int, vocab: int) -> int:
    """Sequences per next-token eval chunk: the largest divisor of ``n``
    whose float32 logits fit :data:`EVAL_LOGITS_BYTES`."""
    fit = max(1, EVAL_LOGITS_BYTES // (seq_len * vocab * 4))
    return max(c for c in range(1, min(fit, n) + 1) if n % c == 0)


def next_token_accuracy(forward, seqs, chunk: int):
    """Share of ``seqs[:, 1:]`` that ``forward(tokens) -> logits`` predicts
    by argmax, ``chunk`` sequences at a time (one pass where ``chunk``
    covers them all). Exact whatever the chunk: correct predictions are
    counted, then divided once."""
    n = seqs.shape[0]
    if chunk >= n:
        return (jnp.argmax(forward(seqs[:, :-1]), -1) == seqs[:, 1:]).mean()

    def hits(count, block):
        right = jnp.argmax(forward(block[:, :-1]), -1) == block[:, 1:]
        return count + right.sum(dtype=jnp.int32), None

    count, _ = jax.lax.scan(hits, jnp.int32(0),
                            seqs.reshape((n // chunk, chunk) + seqs.shape[1:]))
    return count.astype(jnp.float32) / (n * (seqs.shape[1] - 1))


def make_traced_lm_task(*, data_seed=0, num_clients=8, arch="smollm-135m",
                        d_model=64, layers=2, seq_len=32, classes=4,
                        n_seqs=256, n_test=64, per_client=16, local_steps=2,
                        batch_size=2, reduced_widths=True) -> LMTask:
    """Transformer LM as a first-class sweep workload.

    ``reduced_widths`` (the CPU-test form) builds ``reduced(get_config(arch),
    d_model, layers)`` forced to float32 (the sweep engine's bitwise
    contracts assume f32 accumulation); without it the model is ``arch`` at
    its published widths and depth, computing in its config's dtype.
    Either way the federated state holds float32 master weights
    (``repro.models.model.master_config``) and each client's loss casts
    them to the compute dtype (``compute_params``), so local SGD,
    aggregation and broadcast run in float32. At published widths each
    layer is recomputed in the backward pass: a chip holds B x m float32
    client masters, not their saved activations too.

    The corpus is a synthetic styled token set, Dirichlet-partitioned over
    per-sequence style labels exactly like the classification task is over
    class labels — so the non-IID severity knob ``alpha`` means the same
    thing. Everything is traced: the corpus rides ``shared`` ({"toks"
    [n, T+1], "toks_t" [n_test, T+1]}), the partition rides ``ds_state``,
    evals take ``(params, shared)`` and report next-token accuracy, run in
    chunks of sequences (:func:`eval_chunk`).
    """
    import dataclasses as _dc

    from repro.configs import get_config, reduced
    from repro.models import model as lm

    cfg = get_config(arch)
    if reduced_widths:
        cfg = _dc.replace(reduced(cfg, d_model=d_model, layers=layers),
                          dtype="float32")
    master = lm.master_config(cfg)
    rng = np.random.default_rng(data_seed)
    toks, styles = _styled_corpus(rng, n=n_seqs, seq_len=seq_len,
                                  vocab=cfg.vocab_size, classes=classes)
    toks_t, _ = _styled_corpus(rng, n=n_test, seq_len=seq_len,
                               vocab=cfg.vocab_size, classes=classes)
    shared = {"toks": jnp.asarray(toks), "toks_t": jnp.asarray(toks_t)}
    ce_chunk = min(512, seq_len)
    remat = not reduced_widths

    def partition(alpha: float) -> np.ndarray:
        prng = np.random.default_rng(data_seed)
        idx, _ = dirichlet_partition(prng, styles, num_clients, alpha=alpha,
                                     per_client=per_client)
        return idx

    def lm_loss(params, batch):
        return lm.loss_fn(params, cfg, batch, remat=remat, ce_chunk=ce_chunk)

    def accuracy(params, seqs):
        return next_token_accuracy(
            lambda tokens: lm.forward(params, cfg, tokens)[0], seqs,
            eval_chunk(seqs.shape[0], seq_len, cfg.vocab_size))

    return LMTask(
        loss_fn=lm_loss,
        init_params=lambda key: lm.init_params(key, master),
        source_factory=lambda sh: traced_lm_source(
            sh, local_steps=local_steps, batch_size=batch_size),
        eval_test=lambda params, sh: accuracy(params, sh["toks_t"]),
        eval_train=lambda params, sh: accuracy(params, sh["toks"]),
        partition=partition,
        shared=shared,
        meta={"dataset": "styled-lm", "data_seed": data_seed, "arch": arch,
              "d_model": cfg.d_model, "layers": cfg.num_layers,
              "seq_len": seq_len, "classes": classes,
              "vocab": cfg.vocab_size, "dtype": cfg.dtype,
              "n_train": n_seqs, "n_test": n_test,
              "num_clients": num_clients, "per_client": per_client,
              "local_steps": local_steps, "batch_size": batch_size,
              "param_count": cfg.param_count()},
    )
