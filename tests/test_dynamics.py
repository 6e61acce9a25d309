"""Fleet-dynamics subsystem tests: scenario registry, static-paper
parity (golden pre-dynamics values + bitwise static≡None), Markov
transition invariants, battery bounds/recovery, availability gating, and
end-to-end dynamic runs through the scan engine."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLConfig, METHODS
from repro.core.policy import PolicyCfg
from repro.launch import engine as eng
from repro.launch.fl_run import build_task, run_fl
from repro.models.fl_models import make_fl_model
from repro.sim.devices import build_fleet
from repro.sim.dynamics import (SCENARIOS, get_scenario, init_env_state,
                                step_env)
from repro.sim.dynamics.battery import charge_and_drain, plug_step
from repro.sim.dynamics.channel import channel_step, effective_rate_mean
from repro.sim.dynamics.diurnal import (day_of_week, diurnal_markov_step,
                                        is_weekend, night_weight,
                                        time_of_day)

N, K = 10, 4

# Engine history of the pre-dynamics simulator with exactly the `setup`
# config below (rewafl, rounds=4, chunk=2, loop key PRNGKey(7), init key
# PRNGKey(0)). static-paper must keep reproducing these numbers — the
# scenario's whole contract. Captured under jax's default
# `jax_threefry_partitionable=True` (jax 0.9); the earlier capture, made
# under the old default False, is still reproduced bitwise by this code
# with JAX_THREEFRY_PARTITIONABLE=0 — only the PRNG stream moved.
GOLDEN = {
    "global_loss": [2.921046018600464, 2.4449946880340576,
                    2.3848109245300293, 2.279622793197632],
    "round_energy": [130.37168884277344, 157.3203582763672,
                     270.2777404785156, 185.8375701904297],
    "round_latency": [5.702662467956543, 22.91344451904297,
                      44.106239318847656, 6.278830051422119],
    "n_participating": [4, 4, 4, 4],
    "residual_sum": 445649.90625,
    "selected": [[1, 0, 0, 1, 0, 0, 0, 0, 1, 1],
                 [1, 1, 1, 0, 0, 0, 1, 0, 0, 0],
                 [1, 0, 0, 0, 1, 0, 0, 1, 0, 1],
                 [1, 1, 0, 0, 0, 0, 0, 0, 1, 1]],
}


@pytest.fixture(scope="module")
def setup():
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(N, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", N, 0.8, per_client=16, n_test=32)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=6))
    return model, fleet, cx, cy, cfg


def _engine_run(setup, scenario, rounds=4):
    model, fleet, cx, cy, cfg = setup
    return eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                          rounds=rounds, key=jax.random.PRNGKey(7),
                          params=model.init(jax.random.PRNGKey(0)),
                          ecfg=eng.EngineCfg(chunk_size=2),
                          scenario=scenario,
                          env_key=jax.random.PRNGKey(3))


# ------------------------------------------------------------- registry

def test_registry_has_required_scenarios():
    for name in ("static-paper", "commuter-diurnal", "congested-urban",
                 "overnight-charging", "churn-heavy"):
        assert name in SCENARIOS
    assert get_scenario(None).static
    assert get_scenario("static-paper").static
    assert get_scenario("commuter-diurnal").dynamic
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("no-such-scenario")


# ------------------------------------------------- static-paper parity

@pytest.mark.skipif(os.environ.get("REPRO_SKIP_GOLDEN") == "1",
                    reason="machine-captured golden values: skipped on "
                           "hosts/jax builds that differ from the capture "
                           "(the bitwise static≡None test still runs)")
def test_static_paper_matches_pre_dynamics_golden(setup):
    """static-paper reproduces the engine history captured before the
    dynamics subsystem existed (same machine, same config)."""
    res = _engine_run(setup, get_scenario("static-paper"))
    h = res.history
    for k in ("global_loss", "round_energy", "round_latency"):
        np.testing.assert_allclose(np.asarray(h[k], np.float64), GOLDEN[k],
                                   rtol=1e-3, err_msg=k)
    np.testing.assert_array_equal(np.asarray(h["n_participating"]),
                                  GOLDEN["n_participating"])
    np.testing.assert_array_equal(np.asarray(h["selected"]).astype(int),
                                  GOLDEN["selected"])
    np.testing.assert_allclose(
        float(np.asarray(res.state.residual_energy).sum()),
        GOLDEN["residual_sum"], rtol=1e-3)


@pytest.mark.skipif(os.environ.get("REPRO_SKIP_GOLDEN") == "1",
                    reason="machine-captured golden values: skipped on "
                           "hosts/jax builds that differ from the capture")
def test_static_paper_golden_tight_through_closure_free_engine(setup):
    """ISSUE 3 acceptance, extended golden parity: the closure-free round
    signature (fleet/data as chunk *arguments* instead of trace-time
    constants) must not perturb the static-paper engine history.

    Selection masks and participation counts are asserted exactly;
    floats at rtol=1e-6 — three orders tighter than the original golden
    test. Strict float-bitwise-vs-capture is not assertable even for
    unmodified code: XLA CPU reduction partitioning is machine-state
    dependent (the pre-PR HEAD reproduces the captured residual_sum only
    to ~4e-8 relative, run-to-run). Pre/post-refactor code was verified
    to produce identical histories side-by-side in one process."""
    res = _engine_run(setup, get_scenario("static-paper"))
    h = res.history
    np.testing.assert_array_equal(np.asarray(h["selected"]).astype(int),
                                  GOLDEN["selected"])
    np.testing.assert_array_equal(np.asarray(h["n_participating"]),
                                  GOLDEN["n_participating"])
    for k in ("global_loss", "round_energy", "round_latency"):
        np.testing.assert_allclose(np.asarray(h[k], np.float64), GOLDEN[k],
                                   rtol=1e-6, err_msg=k)
    np.testing.assert_allclose(
        float(np.asarray(res.state.residual_energy, np.float64).sum()),
        GOLDEN["residual_sum"], rtol=1e-6)


def test_static_paper_bitwise_identical_to_scenario_none(setup):
    """scenario='static-paper' and scenario=None must share the exact
    trace — bitwise-equal histories and final state."""
    a = _engine_run(setup, get_scenario("static-paper"))
    b = _engine_run(setup, None)
    for k in a.history:
        np.testing.assert_array_equal(np.asarray(a.history[k]),
                                      np.asarray(b.history[k]), err_msg=k)
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_static_metrics_report_full_availability(setup):
    res = _engine_run(setup, None)
    h = res.history
    np.testing.assert_array_equal(np.asarray(h["n_charging"]), 0)
    np.testing.assert_array_equal(np.asarray(h["n_online"]), N)
    np.testing.assert_array_equal(
        np.asarray(h["n_available"]),
        N - np.concatenate([[0], np.asarray(h["n_dropped"])[:-1]]))


# --------------------------------------------------- transition kernels

def test_step_env_deterministic_under_fixed_key():
    fleet = build_fleet(20, seed=1)
    sc = get_scenario("commuter-diurnal")
    env = init_env_state(fleet, sc, key=jax.random.PRNGKey(0))
    from repro.core import init_fleet_state
    state = init_fleet_state(fleet)
    outs = [step_env(sc, fleet, env, state, jnp.asarray(3, jnp.int32),
                     jax.random.PRNGKey(9), 16e6) for _ in range(2)]
    for x, y in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_channel_step_edge_probabilities():
    key = jax.random.PRNGKey(0)
    good = jnp.array([True] * 50 + [False] * 50)
    # p_gb=0, p_bg=1: everyone good next step
    out = channel_step(key, good, 0.0, 1.0)
    assert bool(np.asarray(out).all())
    # p_gb=1, p_bg=0: everyone bad next step
    out = channel_step(key, good, 1.0, 0.0)
    assert not bool(np.asarray(out).any())


def test_channel_migration_moves_devices():
    """With nonzero transition rates devices actually migrate between
    environments (the static model never does)."""
    fleet = build_fleet(100, seed=0)
    sc = get_scenario("congested-urban")
    good = init_env_state(fleet, sc, key=jax.random.PRNGKey(0)).channel_good
    start = np.asarray(good).copy()
    key = jax.random.PRNGKey(1)
    for i in range(20):
        key, k = jax.random.split(key)
        good = channel_step(k, good, sc.p_good_to_bad, sc.p_bad_to_good)
    moved = (np.asarray(good) != start).sum()
    assert moved > 10
    rm = np.asarray(effective_rate_mean(good, fleet))
    assert ((rm == np.asarray(fleet.rate_high))
            | (rm == np.asarray(fleet.rate_low))).all()


def test_charge_and_drain_bounds():
    fleet = build_fleet(10, seed=0)
    sc = get_scenario("overnight-charging")
    full = fleet.battery_j
    # charging from full never exceeds capacity
    out = charge_and_drain(full, jnp.ones(10, bool), fleet, sc)
    assert (np.asarray(out) <= np.asarray(full) + 1e-3).all()
    # draining from empty never goes negative
    out = charge_and_drain(jnp.zeros(10), jnp.zeros(10, bool), fleet, sc)
    assert (np.asarray(out) >= 0.0).all()


def test_recovery_clears_dropped_when_charged():
    """A depleted+dropped device plugged in long enough rejoins."""
    fleet = build_fleet(10, seed=0)
    sc = dataclasses.replace(get_scenario("overnight-charging"),
                             plug_off_day=0.0, plug_off_night=0.0,
                             plug_on_day=1.0, plug_on_night=1.0,
                             p_offline_day=0.0, p_offline_night=0.0)
    from repro.core import init_fleet_state
    state = init_fleet_state(fleet)
    state = state._replace(residual_energy=jnp.zeros(10),
                           dropped=jnp.ones(10, bool))
    env = init_env_state(fleet, sc, key=jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    for r in range(200):
        key, k = jax.random.split(key)
        env, state = step_env(sc, fleet, env, state,
                              jnp.asarray(r, jnp.int32), k, 16e6)
        if not np.asarray(state.dropped).any():
            break
    assert not np.asarray(state.dropped).any()
    assert (np.asarray(state.residual_energy)
            <= np.asarray(fleet.battery_j) + 1e-3).all()


def test_diurnal_clock():
    tod = time_of_day(jnp.asarray(0, jnp.int32), 2.0, jnp.asarray([0.0, 23.5]))
    np.testing.assert_allclose(np.asarray(tod), [0.0, 23.5])
    # 30 rounds * 2 min = 1 h
    tod = time_of_day(jnp.asarray(30, jnp.int32), 2.0, jnp.asarray([23.5]))
    np.testing.assert_allclose(np.asarray(tod), [0.5], atol=1e-5)
    w = np.asarray(night_weight(jnp.asarray([0.0, 12.0])))
    np.testing.assert_allclose(w, [1.0, 0.0], atol=1e-6)


# ------------------------------------------- weekday/weekend structure

def _round_at_day(day, minutes_per_round=2.0):
    """First round index whose sim clock (phase 0) is inside `day`."""
    return int(day * 24 * 60 / minutes_per_round)


def test_day_of_week_clock():
    """Campaign starts 00:00 Monday (day 0); days advance every 24 sim
    hours, wrap at 7, and the per-device phase shifts the boundary."""
    mpr = 2.0
    for day in (0, 1, 4, 5, 6):
        dow = day_of_week(jnp.asarray(_round_at_day(day), jnp.int32),
                          mpr, jnp.asarray([0.0]))
        np.testing.assert_allclose(np.asarray(dow), [float(day)])
    # day 7 wraps back to Monday
    dow = day_of_week(jnp.asarray(_round_at_day(7), jnp.int32), mpr,
                      jnp.asarray([0.0]))
    np.testing.assert_allclose(np.asarray(dow), [0.0])
    # a +24 h phase pushes a device one day ahead of the global clock
    dow = day_of_week(jnp.asarray(0, jnp.int32), mpr,
                      jnp.asarray([0.0, 24.0]))
    np.testing.assert_allclose(np.asarray(dow), [0.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(is_weekend(jnp.asarray([0.0, 4.0, 5.0, 6.0]))),
        [False, False, True, True])


def test_weekend_multiplier_reshapes_plug_probability():
    """weekend_plug_on_mult=0 must freeze weekend plug-ins entirely
    while weekday behavior is untouched (same key, same chain)."""
    S = 2000
    sc = dataclasses.replace(
        get_scenario("commuter-diurnal"), name="wk-test",
        plug_on_day=0.5, plug_on_night=0.5,
        weekend_plug_on_mult=0.0, weekend_plug_off_mult=1.0)
    key = jax.random.PRNGKey(0)
    unplugged = jnp.zeros((S,), bool)
    tod = jnp.full((S,), 12.0)
    weekday = plug_step(key, unplugged, tod, sc,
                        weekend=jnp.zeros((S,), bool))
    weekend = plug_step(key, unplugged, tod, sc,
                        weekend=jnp.ones((S,), bool))
    assert int(np.asarray(weekday).sum()) > 0.3 * S   # p_on = 0.5
    assert int(np.asarray(weekend).sum()) == 0        # p_on *= 0
    # weekend=None ≡ all-weekday: bitwise-identical transition
    np.testing.assert_array_equal(np.asarray(plug_step(key, unplugged,
                                                       tod, sc)),
                                  np.asarray(weekday))


def test_weekend_multiplier_clips_to_valid_probability():
    """A large on-multiplier saturates at p=1: every unplugged weekend
    device plugs in."""
    S = 500
    out = diurnal_markov_step(
        jax.random.PRNGKey(1), jnp.zeros((S,), bool),
        jnp.full((S,), 0.0), 0.4, 0.4, 0.1, 0.1,
        weekend=jnp.ones((S,), bool), weekend_on_mult=100.0)
    assert bool(np.asarray(out).all())


def test_commuter_diurnal_weekend_in_step_env():
    """commuter-diurnal exercises the weekly clock end-to-end: stepping
    the env inside a weekend raises the charging fraction vs the same
    transition on a weekday (plug-on up, unplug down)."""
    from repro.core import init_fleet_state
    sc = get_scenario("commuter-diurnal")
    assert sc.has_weekend
    assert not get_scenario("static-paper").has_weekend
    fleet = build_fleet(2000, seed=0)
    env = init_env_state(fleet, sc, key=jax.random.PRNGKey(0))
    env = env._replace(phase_h=jnp.zeros_like(env.phase_h))  # one clock
    state = init_fleet_state(fleet)
    charging = {}
    for label, day in (("weekday", 1), ("weekend", 5)):
        n = 0
        key = jax.random.PRNGKey(42)
        e, s = env, state
        # start at midday (night probs saturate both regimes toward 1);
        # burn in 60 rounds (~10 chain mixing times), then average 1 h
        r0 = _round_at_day(day, sc.minutes_per_round) + _round_at_day(
            0.5, sc.minutes_per_round)
        for i in range(90):
            key, k = jax.random.split(key)
            e, s = step_env(sc, fleet, e, s, jnp.asarray(r0 + i, jnp.int32),
                            k, 16e6)
            if i >= 60:
                n += int(np.asarray(e.charging).sum())
        charging[label] = n
    assert charging["weekend"] > 1.5 * charging["weekday"]


# --------------------------------------------- end-to-end dynamic runs

@pytest.mark.parametrize("name", ["commuter-diurnal", "churn-heavy"])
def test_dynamic_scenario_engine_run(setup, name):
    """Dynamic scenarios run end-to-end through the scan engine with
    finite metrics, availability gating, and bounded energy."""
    res = _engine_run(setup, get_scenario(name), rounds=4)
    h = res.history
    assert res.rounds_run == 4
    assert np.isfinite(np.asarray(h["global_loss"], np.float64)).all()
    n_avail = np.asarray(h["n_available"])
    assert n_avail.shape == (4,)
    assert ((0 <= n_avail) & (n_avail <= N)).all()
    assert ((0 <= np.asarray(h["n_charging"]))
            & (np.asarray(h["n_charging"]) <= N)).all()
    # participants never exceed availability
    assert (np.asarray(h["n_participating"]) <= n_avail).all()
    _, fleet, _, _, _ = setup
    E = np.asarray(res.state.residual_energy)
    assert (E >= 0).all() and (E <= np.asarray(fleet.battery_j) + 1e-3).all()


def test_dynamic_scenario_differs_from_static(setup):
    a = _engine_run(setup, None)
    b = _engine_run(setup, get_scenario("congested-urban"))
    assert not np.allclose(np.asarray(a.history["round_energy"]),
                           np.asarray(b.history["round_energy"]))


def test_offline_devices_never_selected(setup):
    """Churn gating: a device that is offline this round must not be
    selected, even if its utility is high."""
    model, fleet, cx, cy, cfg = setup
    from repro.core import init_fleet_state, make_round_fn
    # freeze availability: nobody changes state, half the fleet offline
    sc = dataclasses.replace(
        get_scenario("churn-heavy"), name="frozen-churn",
        p_offline_day=0.0, p_offline_night=0.0,
        p_online_day=0.0, p_online_night=0.0)
    rf = make_round_fn(model, fleet, cx, cy, cfg, METHODS["rewafl"], sc)
    env = init_env_state(fleet, sc, key=jax.random.PRNGKey(0))
    offline = jnp.arange(N) < N // 2
    env = env._replace(online=~offline)
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    params = model.init(jax.random.PRNGKey(0))
    _, _, env2, m = rf(params, state, env, jax.random.PRNGKey(2),
                       jnp.asarray(0, jnp.int32))
    sel = np.asarray(m["selected"])
    assert not sel[:N // 2].any()
    assert int(m["n_online"]) == N - N // 2


def test_churn_under_k_selection_bounded_by_availability(setup):
    """Churn so heavy that n_online < n_select most rounds: the selection
    mask must never exceed availability, never pick an offline device,
    and the under-K padding must not inflate participation counts."""
    model, fleet, cx, cy, cfg = setup
    sc = dataclasses.replace(
        get_scenario("churn-heavy"), name="churn-storm",
        p_offline_day=0.8, p_offline_night=0.8,
        p_online_day=0.1, p_online_night=0.1, frac_online0=0.3)
    cfg8 = dataclasses.replace(cfg, n_select=8)
    res = eng.run_rounds(model, fleet, cx, cy, cfg8, METHODS["rewafl"],
                         rounds=6, key=jax.random.PRNGKey(7),
                         params=model.init(jax.random.PRNGKey(0)),
                         ecfg=eng.EngineCfg(chunk_size=3),
                         scenario=sc, env_key=jax.random.PRNGKey(3))
    sel = np.asarray(res.history["selected"])          # (R, S)
    n_avail = np.asarray(res.history["n_available"])
    assert (sel.sum(1) <= n_avail).all()
    assert (sel.sum(1) <= 8).all()
    assert (n_avail < 8).any()  # the regime actually exercises under-K
    # each device participates at most once per round
    assert (np.asarray(res.state.n_participations) <= res.rounds_run).all()
    assert np.isfinite(np.asarray(res.history["global_loss"],
                                  np.float64)).all()


def test_run_fl_scenario_end_to_end():
    """`run_fl(scenario=...)` drives a dynamic campaign through the scan
    engine and reports the dynamics metrics per round."""
    res = run_fl("cnn@mnist", "rewafl", rounds=4, n_clients=N, n_select=K,
                 per_client=8, target_acc=2.0, eval_every=2,
                 scenario="commuter-diurnal")
    assert res.rounds_run == 4
    for k in ("n_available", "n_charging", "n_online"):
        assert res.history[k].shape == (4,)
    assert np.isfinite(res.history["global_loss"]).all()


def test_build_fleet_arbitrary_sizes():
    """Non-multiples of 5 build with the remainder spread round-robin;
    divisible sizes keep the exact legacy layout."""
    for n in (7, 128):
        f = build_fleet(n, seed=0)
        assert f.n == n
        counts = np.bincount(np.asarray(f.type_id), minlength=5)
        assert counts.sum() == n
        assert counts.max() - counts.min() <= 1
    f10 = build_fleet(10, seed=0)
    np.testing.assert_array_equal(np.asarray(f10.type_id),
                                  np.repeat(np.arange(5), 2))
