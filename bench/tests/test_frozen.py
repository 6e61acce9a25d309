"""What a configuration may declare for a model whose base is frozen:
`trainable` through the reference, the tap's fingerprint and the
comparison; the `tokens` data kind at a real vocabulary; and the
traffic's measured `t_iter_s`."""
import dataclasses
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, data, harness, reference, spec
from bench.fleet import DEVICE_TYPES, draw_fleet

HEAD = ["head"]


def _setup(cell):
    mc = dict(cell.config, trainable=HEAD)
    tr = cell.traffic
    S = int(tr["clients"])
    fleet = draw_fleet(S, 21, **tr["fleet"])
    cx, cy, _, _ = data.make_client_data(
        jax.random.PRNGKey(22), mc["data"]["kind"], S,
        int(tr["per_client"]), float(tr["lam"]), 4, mc["data"])
    ref = spec.reference_model(cell)
    params = ref.init(jax.random.PRNGKey(23), mc)
    selected = np.zeros(S, bool)
    selected[[1, 5, 7, 12]] = True
    participating = selected.copy()
    participating[7] = False
    H = np.zeros(S, np.int32)
    H[[1, 5, 7, 12]] = [1, 3, 2, 2]
    return mc, ref, fleet, cx, cy, params, selected, participating, H


def test_frozen_leaves_come_back_bit_identical_and_the_head_is_trained(
        tiny_cells):
    cell = tiny_cells["lstm_shakespeare.s1129_k20"]
    mc, ref, fleet, cx, cy, params, sel, part, H = _setup(cell)
    fl, K = cell.traffic["fl"], int(cell.traffic["select"])
    k_train = jax.random.PRNGKey(24)
    out = reference.train_and_aggregate(ref, mc, fl, fleet, cx, cy, params,
                                        sel, part, H, k_train, K,
                                        jnp.float32, reference.MATMUL)
    for k in ("embed", "lstm"):
        for a, b in zip(jax.tree.leaves(out[k]), jax.tree.leaves(params[k])):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    # a plain loop over the slots that steps the head alone
    keys = jax.random.split(k_train, K)
    n, batch, lr = cx.shape[1], int(fl["batch_size"]), float(fl["lr"])

    def loss(head, x, y):
        return jnp.mean(ref.per_sample_loss({**params, "head": head}, x, y))

    heads, weights = [], []
    for slot, i in enumerate(np.flatnonzero(sel)):
        h = params["head"]
        for it in range(int(H[i])):
            idx = jax.random.randint(jax.random.fold_in(keys[slot], it),
                                     (batch,), 0, n)
            g = jax.grad(loss)(h, cx[i][idx], cy[i][idx])
            h = jax.tree.map(lambda a, b: a - lr * b, h, g)
        heads.append(h)
        weights.append(float(fleet["data_size"][i]) * bool(part[i]))
    w = np.asarray(weights) / sum(weights)
    for k in ("w", "b"):
        want = sum(wi * np.asarray(h[k], np.float64)
                   for wi, h in zip(w, heads))
        moved = np.abs(want - np.asarray(params["head"][k])).max()
        assert moved > 1e-3
        assert np.abs(np.asarray(out["head"][k]) - want).max() \
            <= 1e-4 * moved



def test_split_and_merge():
    p = {"a": 1, "b": {"c": 2}, "head": 3}
    frozen, train = reference.split(p, HEAD)
    assert frozen == {"a": 1, "b": {"c": 2}} and train == {"head": 3}
    assert reference.merge(frozen, train) == p
    assert reference.split(p, None) == ({}, p)
    with pytest.raises(KeyError):
        reference.split(p, ["tail"])


TOKENS = {"kind": "tokens", "vocab": 65536, "seq_len": 16, "zipf": 1.1,
          "noise": 0.1, "n_test": 3}


def _tokens(key, S, n, cfg):
    return data.make_client_data(jax.random.PRNGKey(key), "tokens", S, n,
                                 0.8, int(cfg["n_test"]), cfg)


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_tokens_build_at_a_real_vocabulary():
    cx, cy, tx, ty = _tokens(31, 4, 2, TOKENS)
    V = TOKENS["vocab"]
    assert cx.shape == (4, 2, 16) and cx.dtype == jnp.int32
    assert tx.shape == (3, 16) and tx.dtype == jnp.int32
    assert cy.shape == (4, 2) and ty.shape == (3,)
    assert not np.asarray(cy).any() and not np.asarray(ty).any()
    for a in (cx, tx):
        assert 0 <= int(a.min()) and int(a.max()) < V
    assert len(np.unique(np.asarray(cx))) > 32
    again = _tokens(31, 4, 2, TOKENS)
    for a, b in zip((cx, cy, tx, ty), again):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(cx),
                              np.asarray(_tokens(32, 4, 2, TOKENS)[0]))
    # no table of V x V, nor anything near it: the tables are O(V)
    jaxpr = jax.make_jaxpr(lambda k: data._tokens(k, 4, 2, 3, V, 16, 1.1,
                                                  0.1))(
        jax.random.PRNGKey(0))
    assert max(int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)
               if hasattr(a, "shape")) <= 4 * V


def test_tokens_follow_pi0_by_the_mixing_weight():
    cfg = dict(TOKENS, vocab=1024, seq_len=32, n_test=8)
    S, n = 64, 8
    cx = np.asarray(_tokens(33, S, n, cfg)[0])
    pi0, _, _, _, m = data.token_law(jax.random.PRNGKey(33), S + 1, 1024,
                                     cfg["zipf"])
    pi0, m = np.asarray(pi0), np.asarray(m)[:S]
    rate = (pi0[cx[:, :, :-1]] == cx[:, :, 1:]).mean(axis=(1, 2))
    lo, hi = np.argmin(m), np.argmax(m)
    assert m[lo] < 0.05 and m[hi] > 0.95
    assert rate[lo] < 0.1 and rate[hi] > 0.8
    assert np.corrcoef(rate, m)[0, 1] > 0.95


def test_t_iter_s_replaces_the_time_per_iteration_only():
    base = draw_fleet(50, 41)
    times = [10.0, 20.0, 30.0, 40.0, 50.0]
    measured = draw_fleet(50, 41, t_iter_s=times,
                          t_iter_source="a measurement of this model")
    assert measured["t_iter"].dtype == np.float32
    assert np.array_equal(measured["t_iter"],
                          np.float32(times)[base["type_id"]])
    assert all(np.array_equal(base[k], measured[k])
               for k in base if k != "t_iter")
    assert np.array_equal(base["t_iter"], np.float32(
        [t[1] for t in DEVICE_TYPES])[base["type_id"]])


@pytest.mark.parametrize("law", [{"t_iter_s": [1.0] * 5},
                                 {"t_iter_source": "a paper"},
                                 {"t_iter_s": [1.0] * 4,
                                  "t_iter_source": "a paper"}])
def test_t_iter_s_needs_its_source_and_a_time_per_type(law):
    with pytest.raises(ValueError):
        draw_fleet(10, 1, **law)


def _moved(frozen, how):
    f = jax.tree.map(np.array, frozen)
    r = f["lstm"]["r"]
    if how in ("ulp", "two"):
        r.flat[5] = np.nextafter(r.flat[5], np.float32(np.inf))
    if how == "swap":
        r.flat[[3, 4]] = r.flat[[4, 3]]
    if how == "two":
        t = f["embed"]["table"]
        t.flat[0] = np.nextafter(t.flat[0], np.float32(-np.inf))
    return f


@pytest.mark.parametrize("how,want", [("none", 0), ("ulp", 1),
                                      ("swap", 1), ("two", 2)])
def test_frozen_counts_the_moved_frozen_leaves(tiny_cells, how, want):
    cell = tiny_cells["lstm_shakespeare.s1129_k20"]
    ref = spec.reference_model(cell)
    frozen = reference.split(ref.init(jax.random.PRNGKey(3), cell.config),
                             HEAD)[0]
    fp0 = np.asarray(compare.fingerprint(frozen))
    assert fp0.shape == (4, 2) and fp0.dtype == np.uint32
    fp = np.asarray(compare.fingerprint(_moved(frozen, how)))
    # counted over the tapped chunks so far: a leaf that moved stays
    # counted
    assert compare.frozen_moved(fp0, [fp0, fp, fp0]) == [0, want, want]


def test_model_leaves_frozen_leaves_out():
    z = {"base": {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)},
         "head": {"w": np.zeros(4), "b": np.zeros(1)}}
    ref = {"base": z["base"], "head": {"w": np.full(4, 1.0),
                                       "b": np.full(1, 0.5)}}
    out = {"base": {k: np.full(4, 5.0) for k in "abc"},
           "head": {"w": np.full(4, 1.1), "b": np.full(1, 0.5)}}
    head = compare.norm_gap(z["head"], out["head"], ref["head"])
    assert head == pytest.approx(0.1)
    assert compare.norm_gap(z, out, ref, HEAD) == head
    # over every leaf, the moved base and its median of 0 would read
    assert compare.norm_gap(z, out, ref) > 1e6


def _head_only_program(real):
    """A stand-in for a program that trains and averages the head alone:
    no gradient reaches the other leaves, and FedAvg hands them back."""
    def make(cell):
        model = real(cell)

        def loss(params, batch):
            return model.loss({k: v if k in HEAD else jax.lax.stop_gradient(v)
                               for k, v in params.items()}, batch)
        return dataclasses.replace(model, loss=loss)
    return make


@pytest.mark.parametrize("program", ["today", "head_only"])
def test_a_run_with_a_frozen_base(tiny_cells, monkeypatch, program):
    """Today's program trains every leaf, so a configuration that freezes
    all but the head catches it by `frozen`; a program that keeps the
    base frozen passes the whole comparison."""
    from repro.core import round as round_mod
    cell = tiny_cells["lstm_shakespeare.s1129_k20"]
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 trainable=HEAD))
    if program == "head_only":
        fedavg = round_mod._fedavg

        def head_fedavg(g, c, w, backend=None):
            new = fedavg(g, c, w, backend)
            return {k: new[k] if k in HEAD else g[k] for k in g}

        monkeypatch.setattr(round_mod, "_fedavg", head_fedavg)
        monkeypatch.setattr(harness, "_program_model",
                            _head_only_program(harness._program_model))
    out = harness.run(cell, 2147483791, 0.2, False, time.time(),
                      require_tpu=False, log=io.StringIO())
    checks = out["checks"]
    assert checks["compiles"]["value"] == 0
    assert checks["frozen"]["limit"] == 0
    if program == "today":
        assert checks["frozen"]["value"] > 0
        assert out["correct"] is False and out["failed"] >= 1
    else:
        assert checks["frozen"]["value"] == 0
        assert out["correct"] is True, checks
        assert out["attempted"] == 2 and out["failed"] == 0
