"""A configuration that does not declare `trainable` reads as it did
before the key existed: the reference's local SGD and FedAvg equal, bit
for bit, the code that ran before it, kept here as it was."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec
from bench.data import make_client_data
from bench.fleet import draw_fleet


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7, 8, 9))
def old_local_sgd(model, params, xk, yk, hk_keys, H_max, batch, lr, dtype,
                  matmul):
    Hk, keys = hk_keys
    n = xk.shape[1]

    def loss(p, x, y):
        with jax.default_matmul_precision(matmul):
            return jnp.mean(model.per_sample_loss(p, x, y, dtype))

    def one(x, y, H, key):
        p0 = jax.tree.map(lambda a: a.astype(dtype), params)

        def body(it, p):
            idx = jax.random.randint(jax.random.fold_in(key, it), (batch,),
                                     0, n)
            g = jax.grad(loss)(p, x[idx], y[idx])
            stepped = jax.tree.map(lambda a, b: (a - lr * b).astype(dtype),
                                   p, g)
            return jax.tree.map(lambda s, a: jnp.where(it < H, s, a),
                                stepped, p)

        return jax.lax.fori_loop(0, H_max, body, p0)

    return jax.vmap(one)(xk, yk, Hk, keys)


def old_train_and_aggregate(model, fl, fleet, cx, cy, params, selected,
                            participating, H, k_train, K, dtype, matmul):
    f32 = np.float32
    sel_idx = np.flatnonzero(selected)
    live = np.zeros(K, bool)
    live[:len(sel_idx)] = True
    sel_idx = np.concatenate([sel_idx, np.zeros(K - len(sel_idx), int)])
    part_k = participating[sel_idx] & live
    keys = jax.random.split(k_train, K)
    client = old_local_sgd(model, params, cx[sel_idx], cy[sel_idx],
                           (jnp.asarray(H[sel_idx]), keys),
                           int(fl["H_max"]), int(fl["batch_size"]),
                           float(fl["lr"]), dtype, matmul)
    w = fleet["data_size"][sel_idx].astype(f32) * part_k.astype(f32)
    if w.sum() > 0:
        wn = jnp.asarray(w / max(w.sum(), f32(1e-9)))
        return jax.tree.map(
            lambda c: jnp.tensordot(wn, c.astype(jnp.float32),
                                    axes=1).astype(dtype), client)
    return jax.tree.map(lambda a: a.astype(dtype), params)


def _inputs(cell):
    mc, tr = cell.config, cell.traffic
    S, K = int(tr["clients"]), int(tr["select"])
    ref = spec.reference_model(cell)
    fleet = draw_fleet(S, 11, **tr["fleet"])
    cx, cy, _, _ = make_client_data(
        jax.random.PRNGKey(12), mc["data"]["kind"], S, int(tr["per_client"]),
        float(tr["lam"]), int(mc["data"]["n_test"]), mc["data"])
    params = ref.init(jax.random.PRNGKey(13), mc)
    rng = np.random.RandomState(14)
    sel = np.zeros((2, S), bool)
    for r in range(2):
        sel[r, rng.choice(S, K, replace=False)] = True
    part = sel.copy()
    part[1, np.flatnonzero(sel[1])[0]] = False
    H = rng.randint(1, int(tr["fl"]["H_max"]) + 1, size=(2, S))
    return ref, fleet, cx, cy, params, sel, part, H.astype(np.int32)


def _equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("precision", ["reference", "control"])
@pytest.mark.parametrize("name", ["cnn_mnist.s3500_k20",
                                  "lstm_shakespeare.s1129_k20"])
def test_default_reference_reads_as_before(tiny_cells, name, precision):
    cell = tiny_cells[name]
    assert "trainable" not in cell.config
    mc, tr = cell.config, cell.traffic
    fl, K = tr["fl"], int(tr["select"])
    ref, fleet, cx, cy, params, sel, part, H = _inputs(cell)
    dtype, matmul = reference._dtype(precision), reference.MATMUL
    key = jnp.asarray(jax.random.PRNGKey(15))
    got = reference.follow_rounds(ref, mc, fl, fleet, cx, cy, params, key,
                                  sel, part, H, K, precision)
    want = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), params)
    for r in range(2):
        key, kr = jax.random.split(key)
        _, _, k_train = jax.random.split(kr, 3)
        want = old_train_and_aggregate(ref, fl, fleet, cx, cy, want, sel[r],
                                       part[r], H[r], k_train, K, dtype,
                                       matmul)
    _equal(got, want)
