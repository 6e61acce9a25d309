"""FL round-loop integration tests: Algorithm 1 invariants over real
rounds on a small fleet/dataset (the paper's system end-to-end).

Tier-1 runs the structurally distinct methods (rewafl = rea+rewa policy,
oort = ε-greedy+fixed); the remaining baselines ride the slow tier. The
jitted round fn per method is compiled once and shared module-wide."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FLConfig, METHODS, init_env_state,
                        init_fleet_state, make_round_fn)
from repro.core.policy import PolicyCfg
from repro.launch.fl_run import build_task
from repro.models.fl_models import make_fl_model
from repro.sim.devices import build_fleet

N, K = 10, 4

FAST_METHODS = ("rewafl", "oort")
SLOW_METHODS = tuple(m for m in sorted(METHODS) if m not in FAST_METHODS)


@pytest.fixture(scope="module")
def setup():
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(N, seed=0, init_energy_mean=0.3)
    cx, cy, test = build_task("cnn@mnist", N, 0.8, per_client=16, n_test=32)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=6))
    return model, fleet, cx, cy, cfg


@pytest.fixture(scope="module")
def round_fns(setup):
    """Lazily compiled round fn per method, shared by every test here."""
    model, fleet, cx, cy, cfg = setup
    cache = {}

    def get(method):
        if method not in cache:
            cache[method] = make_round_fn(model, fleet, cx, cy, cfg,
                                          METHODS[method])
        return cache[method]

    return get


def _check_invariants(setup, round_fns, method, rounds=2):
    model, fleet, cx, cy, cfg = setup
    rf = round_fns(method)
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet)
    key = jax.random.PRNGKey(1)
    for r in range(rounds):
        key, kr = jax.random.split(key)
        params, new_state, env, m = rf(params, state, env, kr,
                                       jnp.asarray(r, jnp.int32))
        # residual energy never increases; only participants pay
        dE = np.asarray(state.residual_energy - new_state.residual_energy)
        assert (dE >= -1e-4).all()
        part = int(m["n_participating"])
        assert part <= K
        assert (dE > 1e-6).sum() == part
        # never spend below the reserve
        assert (np.asarray(new_state.residual_energy)
                >= np.asarray(fleet.e0_reserve) - 1e-3).sum() == N
        # u resets exactly for participants, increments otherwise
        u_new = np.asarray(new_state.u)
        assert ((u_new == 0).sum() >= part)
        # H never shrinks
        assert (np.asarray(new_state.H) >= np.asarray(state.H)).all()
        assert np.isfinite(float(m["global_loss"]))
        state = new_state


@pytest.mark.parametrize("method", FAST_METHODS)
def test_round_invariants(setup, round_fns, method):
    _check_invariants(setup, round_fns, method)


@pytest.mark.slow
@pytest.mark.parametrize("method", SLOW_METHODS)
def test_round_invariants_baselines(setup, round_fns, method):
    _check_invariants(setup, round_fns, method, rounds=3)


def test_rewafl_never_selects_infeasible(setup, round_fns):
    """Energy-utility hard zero: REWAFL must not pick devices whose round
    energy exceeds available battery (while feasible candidates remain)."""
    model, fleet, cx, cy, cfg = setup
    # drain half the fleet to near-reserve
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    drained = state.residual_energy.at[:5].set(
        fleet.e0_reserve[:5] + 1.0)  # 1 J above reserve: infeasible
    state = state._replace(residual_energy=drained)
    rf = round_fns("rewafl")
    params = model.init(jax.random.PRNGKey(0))
    _, new_state, _, m = rf(params, state, init_env_state(fleet),
                            jax.random.PRNGKey(2),
                            jnp.asarray(0, jnp.int32))
    assert int(m["n_failed"]) == 0
    sel = np.asarray(m["selected"])
    assert not sel[:5].any()


def test_training_improves_loss(setup, round_fns):
    model, fleet, cx, cy, cfg = setup
    rf = round_fns("rewafl")
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet)
    key = jax.random.PRNGKey(3)
    losses = []
    for r in range(5):
        key, kr = jax.random.split(key)
        params, state, env, m = rf(params, state, env, kr,
                                   jnp.asarray(r, jnp.int32))
        losses.append(float(m["global_loss"]))
    assert losses[-1] < losses[0]


def test_under_k_selection_no_duplicate_weights(setup):
    """Regression (ISSUE 3 headline): with fewer than K selectable
    devices, `jnp.nonzero(..., size=K, fill_value=0)` pads the training
    slots with device index 0 — the old round body re-trained a
    participating device 0 once per pad slot, multiplied its FedAvg
    weight, and re-applied its state scatters. Each device's weight must
    enter the aggregate at most once: with only devices {0, 5} available
    (n_available=2 < K=4) the new params must equal the exact two-client
    FedAvg with each true weight appearing once."""
    from repro.core.round import _fedavg, _local_sgd
    model, fleet, cx, cy, cfg = setup
    # identical samples within each client -> the local SGD update is
    # independent of the per-slot PRNG key (any minibatch of identical
    # rows yields the same gradient), so the reference aggregate below
    # is exact without replaying the round's internal key folding
    cx = jnp.repeat(cx[:, :1], cx.shape[1], axis=1)
    cy = jnp.repeat(cy[:, :1], cy.shape[1], axis=1)
    # plenty of battery: both available devices must participate
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    state = state._replace(
        residual_energy=fleet.battery_j.astype(jnp.float32),
        dropped=jnp.ones(N, bool).at[jnp.array([0, 5])].set(False))
    # 'random' has the fixed-H policy: every slot trains exactly H0 steps
    rf = make_round_fn(model, fleet, cx, cy, cfg, METHODS["random"])
    params = model.init(jax.random.PRNGKey(0))
    new_params, new_state, _, m = rf(params, state, init_env_state(fleet),
                                     jax.random.PRNGKey(11),
                                     jnp.asarray(0, jnp.int32))
    sel = np.asarray(m["selected"])
    assert sel.sum() == 2 and sel[0] and sel[5]
    assert int(m["n_participating"]) == 2
    # reference: each client trained once, each weight used once
    H0 = jnp.asarray(cfg.policy.H0, jnp.int32)
    upd = [_local_sgd(model, params, cx[i], cy[i], H0, H0,
                      jax.random.PRNGKey(123), cfg) for i in (0, 5)]
    client_params = jax.tree.map(lambda a, b: jnp.stack([a, b]), *upd)
    weights = fleet.data_size[jnp.array([0, 5])].astype(jnp.float32)
    expected = _fedavg(params, client_params, weights)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(new_params)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   atol=1e-5, rtol=1e-5)
    # the duplicated pad slots also re-applied the per-slot scatters;
    # with the fix, untouched devices keep their exact prior stat/q state
    untouched = np.ones(N, bool)
    untouched[[0, 5]] = False
    np.testing.assert_array_equal(np.asarray(new_state.last_stat)[untouched],
                                  np.asarray(state.last_stat)[untouched])


def _static_local_sgd(model, params, x, y, H, key, cfg):
    """Reference: local SGD over all H_max iterations, those at or past
    the slot's H masked to no-ops."""
    n = x.shape[0]
    grad_fn = jax.grad(model.loss)

    def body(it, p):
        k = jax.random.fold_in(key, it)
        idx = jax.random.randint(k, (cfg.batch_size,), 0, n)
        g = grad_fn(p, {"x": x[idx], "y": y[idx]})
        live = (it < H).astype(jnp.float32)
        return jax.tree.map(lambda pp, gg: pp - cfg.lr * live * gg, p, g)

    return jax.lax.fori_loop(0, cfg.policy.H_max, body, params)


@pytest.mark.parametrize("task", ["cnn@mnist", "lstm@shakespeare"])
@pytest.mark.parametrize("H_slots,n_live", [
    ((1, 6, 3, 2, 4, 6), 4),   # H_max in a live slot: the bound is H_max
    ((1, 3, 2, 1, 6, 5), 4),   # the pad slots hold the largest H
], ids=["live_hmax", "pad_hmax"])
def test_cohort_bound_matches_static_hmax_loop(task, H_slots, n_live):
    """Local SGD bounded by the cohort's largest live H leaves every live
    slot's parameters bit-identical to the old loop over all H_max
    iterations: the iterations it drops were masked no-ops."""
    from repro.core.round import _local_sgd
    Kc = len(H_slots)
    model = make_fl_model(task, small=True)
    cx, cy, _ = build_task(task, Kc, 0.8, per_client=12, n_test=8)
    cfg = FLConfig(n_select=Kc, batch_size=4, lr=0.05,
                   policy=PolicyCfg(H0=2, H_max=6))
    params = model.init(jax.random.PRNGKey(0))
    Hk = jnp.asarray(H_slots, jnp.int32)
    slot_live = jnp.arange(Kc) < n_live
    keys = jax.random.split(jax.random.PRNGKey(3), Kc)

    @jax.jit
    def bounded(cx, cy, Hk, keys):
        n_iters = jnp.max(jnp.where(slot_live, Hk, 0))
        return jax.vmap(lambda x, y, H, kk: _local_sgd(
            model, params, x, y, H, n_iters, kk, cfg))(cx, cy, Hk, keys)

    @jax.jit
    def static(cx, cy, Hk, keys):
        return jax.vmap(lambda x, y, H, kk: _static_local_sgd(
            model, params, x, y, H, kk, cfg))(cx, cy, Hk, keys)

    got = bounded(cx, cy, Hk, keys)
    want = static(cx, cy, Hk, keys)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a)[:n_live],
                                      np.asarray(b)[:n_live])
    # slot 0 (H 1) moved off the initial model: the comparison bites
    moved = [np.any(np.asarray(a)[0] != np.asarray(p))
             for a, p in zip(jax.tree.leaves(got), jax.tree.leaves(params))]
    assert any(moved)


def test_local_iters_ignores_pad_slots(setup, round_fns):
    """With fewer than K selected, the pad slots gather device 0; its H
    must not set the loop's trip count, which is the largest H of the
    selected devices."""
    model, fleet, cx, cy, cfg = setup
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    # device 0 carries H_max but cannot be selected; only 3 and 5 can
    state = state._replace(
        H=state.H.at[0].set(cfg.policy.H_max),
        residual_energy=fleet.battery_j.astype(jnp.float32),
        dropped=jnp.ones(N, bool).at[jnp.array([3, 5])].set(False))
    rf = round_fns("rewafl")
    params = model.init(jax.random.PRNGKey(0))
    _, _, _, m = rf(params, state, init_env_state(fleet),
                    jax.random.PRNGKey(5), jnp.asarray(0, jnp.int32))
    sel = np.asarray(m["selected"])
    assert sel.sum() == 2 and not sel[0]
    h_sel = np.asarray(m["H"])[sel]
    assert int(m["local_iters"]) == h_sel.max() < cfg.policy.H_max


def test_fedavg_identity_when_no_participants(setup, round_fns):
    model, fleet, cx, cy, cfg = setup
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    # everyone dropped -> params must be unchanged
    state = state._replace(dropped=jnp.ones(N, bool))
    rf = round_fns("rewafl")
    params = model.init(jax.random.PRNGKey(0))
    p2, _, _, m = rf(params, state, init_env_state(fleet),
                     jax.random.PRNGKey(4), jnp.asarray(0, jnp.int32))
    assert int(m["n_participating"]) == 0
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_staleness_self_contained(setup, round_fns):
    """REWAFL's Sec. III-D claim: with heterogeneous rates, long-neglected
    devices eventually get selected WITHOUT any explicit staleness bonus."""
    model, fleet, cx, cy, cfg = setup
    rf = round_fns("rewafl")
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet)
    key = jax.random.PRNGKey(5)
    seen = np.zeros(N, bool)
    for r in range(12):
        key, kr = jax.random.split(key)
        params, state, env, m = rf(params, state, env, kr,
                                   jnp.asarray(r, jnp.int32))
        seen |= np.asarray(m["selected"])
    assert seen.sum() >= N - 2  # nearly everyone participated at least once
