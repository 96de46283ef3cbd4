"""Plain float32 reference of the paper-protocol sweep (``paper-mlp``).

One trajectory of the federated simulation, written out from the paper's
Algorithm 1 and the simulator's documented seed protocol, importing nothing
of the program:

- the 10-class Gaussian data set and its Dirichlet(alpha) client split,
  drawn with NumPy from the data seed;
- the Eq.-9 uplink probabilities ``p_i = max(<r, nu_i>, delta)``, with
  ``nu_i ~ Dirichlet(alpha)`` and ``r`` a normalized log-normal draw;
- per round: a Bernoulli uplink mask (time-varying ``p_i^t`` where the scheme
  says so), per-client mini-batches drawn with replacement from the client's
  shard, ``s`` local SGD steps with ``eta_t = eta_0 / sqrt(t/10 + 1)``, and
  the server rule of the algorithm (FedPBC's postponed broadcast, FedAvg,
  FedAvg over all m, FedAvg with known p).

The key protocol (which key feeds which draw) is the simulator's, so the
same seed gives the same draws; the arithmetic is plain float32, every
product at ``Precision.HIGHEST`` unless a control asks for less
(``bench.refs.precision``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.precision import einsum

F32 = jnp.float32


# --------------------------------------------------------------------------
# data, split and uplink probabilities
# --------------------------------------------------------------------------


def dataset(data_seed: int, *, dim: int, classes: int, n_per_class: int,
            n_train: int, sep: float, noise: float = 1.0) -> Dict[str, np.ndarray]:
    """Gaussian clusters ``x ~ N(sep * mu_c, noise^2 I)`` with unit-norm
    class means, shuffled, split into train and test."""
    rng = np.random.default_rng(data_seed)
    mus = rng.normal(size=(classes, dim))
    mus /= np.linalg.norm(mus, axis=1, keepdims=True)
    xs, ys = [], []
    for c in range(classes):
        xs.append(sep * mus[c] + noise * rng.normal(size=(n_per_class, dim)))
        ys.append(np.full(n_per_class, c))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    perm = rng.permutation(len(x))
    x, y = x[perm], y[perm]
    return {"x": x[:n_train], "y": y[:n_train],
            "xt": x[n_train:], "yt": y[n_train:]}


def dirichlet_split(data_seed: int, labels: np.ndarray, m: int, alpha: float,
                    per_client: int) -> np.ndarray:
    """Each client's class mixture ~ Dirichlet(alpha), ``per_client``
    examples each (Hsu et al. 2019): ``[m, per_client]`` train indices."""
    rng = np.random.default_rng(data_seed)
    classes = np.unique(labels)
    pools = {int(c): rng.permutation(np.where(labels == c)[0]).tolist()
             for c in classes}
    nu = rng.dirichlet(np.full(len(classes), alpha), size=m)
    out = np.zeros((m, per_client), dtype=np.int64)
    for i in range(m):
        counts = rng.multinomial(per_client, nu[i])
        got: List[int] = []
        for c, n in zip(classes, counts):
            pool = pools[int(c)]
            take = pool[:n]
            if len(take) < n:       # class exhausted: draw with replacement
                take = take + list(rng.choice(np.where(labels == c)[0],
                                              n - len(take)))
            pools[int(c)] = pool[n:]
            got.extend(take)
        while len(got) < per_client:
            got.append(int(rng.integers(len(labels))))
        out[i] = np.array(got[:per_client])
    return out


def uplink_probs(seed: int, m: int, classes: int, *, alpha: float,
                 sigma0: float, delta: float, mu0: float = 0.0):
    """Eq. 9: ``p_i = max(<r, nu_i>, delta)``, keyed by ``PRNGKey(seed)``."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    nu = jax.random.dirichlet(k1, jnp.full((classes,), alpha), (m,))
    r = jnp.exp(mu0 + sigma0 * jax.random.normal(k2, (classes,)))
    r = r / r.sum()
    return jnp.maximum(jnp.einsum("mc,c->m", nu, r, precision="highest"),
                       delta)


# --------------------------------------------------------------------------
# one trajectory
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Trajectory:
    """One row of the sweep: its algorithm, base LR and seed."""

    algo: str
    lr: float
    seed: int
    gamma: float = 0.5


@dataclass(frozen=True)
class Protocol:
    """The shapes and link scheme every trajectory of a cell shares."""

    m: int
    local_steps: int
    batch: int
    per_client: int
    dim: int
    hidden: int
    classes: int
    time_varying: bool
    period: float = 40.0


@partial(jax.jit, static_argnames=("dim", "hidden", "classes"))
def init_mlp(seed: int, dim: int, hidden: int, classes: int):
    """The client model: ``dim -> hidden (ReLU) -> classes``, weights
    ``N(0, 1/fan_in)`` from ``PRNGKey(seed + 1)``, zero biases. Compiled,
    so the scale is rounded as a compiled program rounds it."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    return {"w1": jax.random.normal(k1, (dim, hidden)) * dim ** -0.5,
            "b1": jnp.zeros(hidden, F32),
            "w2": jax.random.normal(k2, (hidden, classes)) * hidden ** -0.5,
            "b2": jnp.zeros(classes, F32)}


def _losses(params, x, y, classes, mode):
    """Per-client mean cross-entropy; leaves carry a leading client axis."""
    h = jax.nn.relu(einsum("nbd,ndh->nbh", x, params["w1"], mode)
                    + params["b1"][:, None])
    logits = einsum("nbh,nhc->nbc", h, params["w2"], mode) \
        + params["b2"][:, None]
    gold = jax.nn.one_hot(y, classes)
    return -jnp.mean(jnp.sum(gold * jax.nn.log_softmax(logits), -1), -1)


def _local_sgd(params, xs, ys, step0, lr, classes, mode):
    """``s`` SGD steps per client (xs ``[n, s, b, d]``); returns the trained
    params and each client's mean loss over its steps."""
    total = jnp.zeros(xs.shape[0], F32)
    for j in range(xs.shape[1]):
        def f(p):
            per = _losses(p, xs[:, j], ys[:, j], classes, mode)
            return per.sum(), per
        (_, per), g = jax.value_and_grad(f, has_aux=True)(params)
        eta = lr / jnp.sqrt(jnp.asarray(step0 + j, F32) / 10.0 + 1.0)
        params = jax.tree.map(lambda p, d: (p - eta * d).astype(p.dtype),
                              params, g)
        total = total + per
    return params, total / xs.shape[1]


def _tile(tree, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


def _select(mask, new, old):
    return jax.tree.map(
        lambda a, b: jnp.where(mask.reshape((-1,) + (1,) * (b.ndim - 1)),
                               a, b), new, old)


def _weighted(xs, w):
    return jax.tree.map(lambda x: jnp.sum(
        x * w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype), 0), xs)


def _server_rule(algo, server, x_star, active, p_t):
    """The round's new server params (synchronous engine)."""
    n = active.shape[0]
    act = active.astype(F32)
    if algo in ("fedpbc", "fedavg"):
        count = act.sum()
        mean = jax.tree.map(
            lambda a, s: (a / jnp.maximum(count, 1.0)).astype(s.dtype),
            _weighted(x_star, act), server)
        return jax.tree.map(lambda a, s: jnp.where(count > 0, a, s),
                            mean, server)
    w = act / n if algo == "fedavg_all" else act / jnp.maximum(p_t, 1e-3) / n
    delta = jax.tree.map(lambda x, s: x - s[None], x_star, server)
    return jax.tree.map(lambda s, u: (s + u).astype(s.dtype), server,
                        _weighted(delta, w))


@partial(jax.jit, static_argnames=("algo", "proto", "mode"))
def _dense_round(carry, round_idx, data, idx, p_base, lr, gamma, data_key,
                 *, algo, proto, mode):
    """One synchronous round over all m clients."""
    server, clients, key = carry
    m, s = proto.m, proto.local_steps
    k_data = jax.random.fold_in(data_key, round_idx)
    pick = jax.random.randint(k_data, (m, s, proto.batch), 0, proto.per_client)
    sel = idx[jnp.arange(m)[:, None, None], pick]
    key, k_link = jax.random.split(key)
    p_t = p_base
    if proto.time_varying:
        wave = jnp.sin(2.0 * jnp.pi * round_idx / proto.period)
        p_t = jnp.clip(p_base * ((1.0 - gamma) + gamma * wave), 0.0, 1.0)
    active = jax.random.uniform(k_link, (m,)) < p_t
    starts = clients if algo == "fedpbc" else _tile(server, m)
    x_star, losses = _local_sgd(starts, data["x"][sel], data["y"][sel],
                                round_idx * s, lr, proto.classes, mode)
    new_server = _server_rule(algo, server, x_star, active, p_t)
    if algo == "fedpbc":        # postponed broadcast: active clients only
        new_clients = _select(active, _tile(new_server, m), x_star)
    else:
        new_clients = _tile(new_server, m)
    return (new_server, new_clients, key), {
        "loss": losses.mean(), "num_active": active.sum()}


def follow(traj: Trajectory, proto: Protocol, data, idx, p_base,
           rounds: int, mode: str = "highest",
           keep: Sequence[int] = ()) -> Dict[str, np.ndarray]:
    """The trajectory's first ``rounds`` rounds: per-round mean client loss
    and active-client count, the server parameters it starts from
    (``init``) and those it holds after each round count in ``keep``
    (``servers``, by count)."""
    init = init_mlp(traj.seed, dim=proto.dim, hidden=proto.hidden,
                    classes=proto.classes)
    # the state key is the second half of PRNGKey(seed + 2)'s split (the
    # first seeds the link process, which Bernoulli links do not use)
    key = jax.random.split(jax.random.PRNGKey(traj.seed + 2))[1]
    data_key = jax.random.PRNGKey(traj.seed + 4)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    idx = jnp.asarray(idx, jnp.int32)
    lr = jnp.asarray(traj.lr, F32)
    gamma = jnp.asarray(traj.gamma, F32)
    step = partial(_dense_round, algo=traj.algo, proto=proto, mode=mode)
    carry = (init, _tile(init, proto.m), key)
    out: Dict[str, list] = {}
    servers = {}
    for r in range(rounds):
        carry, mets = step(carry, jnp.int32(r), data, idx, p_base, lr, gamma,
                           data_key)
        for k, v in mets.items():
            out.setdefault(k, []).append(np.asarray(v))
        if r + 1 in keep:
            servers[r + 1] = {k: np.asarray(v, np.float32)
                              for k, v in carry[0].items()}
    res = {k: np.stack(v) for k, v in out.items()}
    res["init"] = {k: np.asarray(v, np.float64) for k, v in init.items()}
    res["servers"] = servers
    return res
