"""Client data, made on the device from a seed in one jitted call.

The laws are those of the program's synthetic tasks (`data/synthetic.py`
and the λ partition of `data/partition.py`), moved onto the device so
that set-up does not spend tens of seconds in NumPy on the host:

* `image`: class templates of smoothed Gaussian noise scaled to a
  signal-to-noise ratio, plus unit noise per sample, with a share of
  flipped labels; the whole set is standardised once. Client i holds
  `per_client` samples, round(λ·per_client) of its dominant label
  (labels dealt evenly over clients, in a random order) and the rest
  uniform over the other labels, in a random order.
* `chars`: per-client Markov chains over a byte alphabet; each client
  mixes two global Dirichlet(0.3) transition matrices by its own weight
  (LEAF's one speaking role per client). Targets are the next character,
  so the labels array is unused and zero.
* `tokens`: token sequences over a vocabulary of real size, with tables
  of O(V): two seeded permutations π0 and π1 of the ids and a mixing
  weight m ~ U(0, 1) per client. Each next token is, with probability
  `noise`, drawn from a Zipf(`zipf`) law over a third seeded
  permutation of the ids; otherwise it is π0[prev] with probability m
  and π1[prev] else. The first token is drawn from the Zipf law. Labels
  are unused and zero, as for `chars`.

Samples are drawn fresh for every slot (the program's partitioner draws
with replacement from a pool); the label law per client is the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def make_client_data(key, kind: str, n_clients: int, per_client: int,
                     lam: float, n_test: int, data_cfg: dict):
    """(cx, cy, test_x, test_y) on the default device."""
    if kind == "image":
        return _image(key, n_clients, per_client, float(lam), n_test,
                      tuple(data_cfg["shape"]), int(data_cfg["n_classes"]),
                      float(data_cfg["snr"]),
                      float(data_cfg["label_noise"]))
    if kind == "chars":
        return _chars(key, n_clients, per_client, n_test,
                      int(data_cfg["vocab"]), int(data_cfg["seq_len"]),
                      float(data_cfg["dirichlet"]))
    if kind == "tokens":
        return _tokens(key, n_clients, per_client, n_test,
                       int(data_cfg["vocab"]), int(data_cfg["seq_len"]),
                       float(data_cfg["zipf"]), float(data_cfg["noise"]))
    raise ValueError(f"unknown data kind {kind!r}")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _image(key, S, n, lam, n_test, shape, C, snr, label_noise):
    kt, kd, ko, ks, kx, kf, kl, ky, ktx = jax.random.split(key, 9)
    t = jax.random.normal(kt, (C,) + shape, jnp.float32)
    for _ in range(2):  # low-frequency templates
        t = (t + jnp.roll(t, 1, 1) + jnp.roll(t, 1, 2)) / 3.0
    t = t * (snr / (jnp.std(t) + 1e-6))

    dominant = jax.random.permutation(kd, jnp.arange(S) % C)
    n_dom = int(round(lam * n))
    other = (dominant[:, None]
             + jax.random.randint(ko, (S, n), 1, max(C, 2))) % C
    labels = jnp.where(jnp.arange(n)[None, :] < n_dom, dominant[:, None],
                       other)
    order = jnp.argsort(jax.random.uniform(ks, (S, n)), axis=1)
    labels = jnp.take_along_axis(labels, order, axis=1)

    x = t[labels] + jax.random.normal(kx, (S, n) + shape, jnp.float32)
    flip = jax.random.uniform(kf, (S, n)) < label_noise
    y = jnp.where(flip, jax.random.randint(kl, (S, n), 0, C), labels)

    ty_true = jax.random.randint(ky, (n_test,), 0, C)
    k1, k2, k3 = jax.random.split(ktx, 3)
    tx = t[ty_true] + jax.random.normal(k1, (n_test,) + shape, jnp.float32)
    tflip = jax.random.uniform(k2, (n_test,)) < label_noise
    ty = jnp.where(tflip, jax.random.randint(k3, (n_test,), 0, C), ty_true)

    mu, sd = jnp.mean(x), jnp.std(x) + 1e-6
    return ((x - mu) / sd, y.astype(jnp.int32), (tx - mu) / sd,
            ty.astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _chars(key, S, n, n_test, V, T, conc):
    kb, km, k0, ku = jax.random.split(key, 4)
    n_test_roles = -(-n_test // n)
    R = S + n_test_roles
    base = jax.random.dirichlet(kb, jnp.full((V,), conc, jnp.float32),
                                shape=(2, V))
    mix = jax.random.uniform(km, (R,))[:, None, None]
    cdf = jnp.cumsum(mix * base[0] + (1 - mix) * base[1], axis=-1)
    s0 = jax.random.randint(k0, (R, n), 0, V)
    rows = jnp.arange(R)[:, None]

    def step(s, u):
        nxt = jnp.sum(cdf[rows, s] < u[..., None], axis=-1)
        return jnp.clip(nxt, 0, V - 1), s

    u = jax.random.uniform(ku, (T, R, n))
    _, seq = jax.lax.scan(step, s0, u)          # (T, R, n)
    seq = jnp.moveaxis(seq, 0, -1).astype(jnp.int32)  # (R, n, T)
    cx = seq[:S]
    tx = seq[S:].reshape(-1, T)[:n_test]
    return (cx, jnp.zeros((S, n), jnp.int32), tx,
            jnp.zeros((tx.shape[0],), jnp.int32))


def token_law(key, R: int, V: int, zipf: float):
    """(π0, π1, Zipf ids, Zipf CDF, m): the `tokens` law of R clients
    (the test roles included), from the data key."""
    kp0, kp1, kpz, km, _ = jax.random.split(key, 5)
    ranks = jnp.arange(1, V + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -zipf)
    return (jax.random.permutation(kp0, V), jax.random.permutation(kp1, V),
            jax.random.permutation(kpz, V), cdf / cdf[-1],
            jax.random.uniform(km, (R,)))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _tokens(key, S, n, n_test, V, T, zipf, noise):
    ks = jax.random.split(key, 5)[4]
    R = S + -(-n_test // n)
    pi0, pi1, zids, cdf, m = token_law(key, R, V, zipf)

    def draw_zipf(u):
        return zids[jnp.minimum(jnp.searchsorted(cdf, u), V - 1)]

    def step(prev, t):
        u = jax.random.uniform(jax.random.fold_in(ks, t), (3, R, n))
        nxt = jnp.where(u[1] < m[:, None], pi0[prev], pi1[prev])
        nxt = jnp.where(u[0] < noise, draw_zipf(u[2]), nxt)
        return nxt, nxt

    first = draw_zipf(jax.random.uniform(jax.random.fold_in(ks, 0), (R, n)))
    _, rest = jax.lax.scan(step, first, jnp.arange(1, T))  # (T-1, R, n)
    seq = jnp.concatenate([first[None], rest]).astype(jnp.int32)
    seq = jnp.moveaxis(seq, 0, -1)                           # (R, n, T)
    cx = seq[:S]
    tx = seq[S:].reshape(-1, T)[:n_test]
    return (cx, jnp.zeros((S, n), jnp.int32), tx,
            jnp.zeros((tx.shape[0],), jnp.int32))
