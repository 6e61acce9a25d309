"""Async (FedBuff) engine-mode tests: M=K/zero-jitter sync equivalence
(bitwise, dense + streaming telemetry, and against the pre-dynamics
golden history), fixed-seed determinism across fresh jit executions,
chunk-length invariance of the final carry, staleness/conservation
invariants at M<K, the mixed sync×async one-compile grid, the
`sample_round_rates` hoist regression, and a `run_fl` CLI-path smoke."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ASYNC_SPECS, AsyncCfg, FLConfig, METHODS,
                        TelemetryCfg, async_variant, sample_round_rates)
from repro.core.policy import PolicyCfg
from repro.launch import engine as eng
from repro.launch.fl_run import ASYNC_HIST_KEYS, build_task, run_fl
from repro.models.fl_models import make_fl_model
from repro.sim.devices import build_fleet
from repro.sim.dynamics import get_scenario, init_env_state
from repro.sim.dynamics.channel import effective_rate_mean
from repro.sim.wireless import sample_rates, sample_rates_from_mean
from tests.test_dynamics import GOLDEN

N, K = 10, 4

SYNC_KEYS = ("global_loss", "round_latency", "round_energy",
             "n_participating", "n_failed", "mean_H_selected", "local_iters")


@pytest.fixture(scope="module")
def setup():
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(N, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", N, 0.8, per_client=16, n_test=32)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=6))
    return model, fleet, cx, cy, cfg


def _run(setup, *, async_cfg=None, rounds=4, chunk=2, telemetry=None,
         collect_per_device=True):
    model, fleet, cx, cy, cfg = setup
    return eng.run_rounds(
        model, fleet, cx, cy, cfg, METHODS["rewafl"], rounds=rounds,
        key=jax.random.PRNGKey(7), params=model.init(jax.random.PRNGKey(0)),
        ecfg=eng.EngineCfg(chunk_size=chunk, async_cfg=async_cfg,
                           collect_per_device=collect_per_device,
                           telemetry=telemetry or TelemetryCfg()))


def _assert_trees_equal(a, b, msg=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# --------------------------------------- M=K sync equivalence (golden)

def test_async_mk_zero_jitter_bitwise_sync_dense(setup):
    """The tentpole parity contract: async with buffer_m=K and
    deterministic delays reproduces the sync engine history bitwise —
    every shared per-round scalar, the selection masks, final params and
    fleet state. The delay model is irrelevant at M=K (wall and unit
    both land the full cohort before the next dispatch)."""
    sync = _run(setup)
    for delay in ("wall", "unit"):
        acfg = AsyncCfg(buffer_m=K, delay=delay)
        asyn = _run(setup, async_cfg=acfg)
        for k in SYNC_KEYS:
            np.testing.assert_array_equal(
                np.asarray(sync.history[k]), np.asarray(asyn.history[k]),
                err_msg=f"{delay}:{k}")
        np.testing.assert_array_equal(np.asarray(sync.history["selected"]),
                                      np.asarray(asyn.history["selected"]))
        _assert_trees_equal(sync.params, asyn.params, f"{delay}:params")
        _assert_trees_equal(sync.state, asyn.state, f"{delay}:state")
        # every round drains the whole cohort in one aggregation
        np.testing.assert_array_equal(
            np.asarray(asyn.history["n_aggregations"]), np.ones(4))
        np.testing.assert_array_equal(
            np.asarray(asyn.history["n_pending"]), np.zeros(4))
        np.testing.assert_array_equal(
            np.asarray(asyn.history["mean_update_staleness"]), np.zeros(4))


@pytest.mark.skipif(os.environ.get("REPRO_SKIP_GOLDEN") == "1",
                    reason="machine-captured golden values: skipped on "
                           "hosts/jax builds that differ from the capture "
                           "(the bitwise async≡sync test still runs)")
def test_async_mk_matches_pre_dynamics_golden(setup):
    """Anchor the equivalence to the seed numbers, not just to today's
    sync path: async M=K reproduces the PR-1 golden engine history."""
    res = _run(setup, async_cfg=AsyncCfg(buffer_m=K))
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


def test_async_mk_bitwise_sync_streaming_telemetry(setup):
    """Same parity under streaming telemetry: scalar history and the
    shared reducer outputs are bitwise, and the async-only reducers
    (wall_clock/last, update_staleness) come out populated."""
    tcfg = TelemetryCfg(mode="streaming", specs=ASYNC_SPECS)
    sync = _run(setup, telemetry=TelemetryCfg(mode="streaming"),
                collect_per_device=False)
    asyn = _run(setup, async_cfg=AsyncCfg(buffer_m=K), telemetry=tcfg,
                collect_per_device=False)
    for k in SYNC_KEYS:
        np.testing.assert_array_equal(np.asarray(sync.history[k]),
                                      np.asarray(asyn.history[k]),
                                      err_msg=k)
    _assert_trees_equal(sync.params, asyn.params, "params")
    for k in sync.telemetry:
        np.testing.assert_array_equal(np.asarray(sync.telemetry[k]),
                                      np.asarray(asyn.telemetry[k]),
                                      err_msg=k)
    assert float(asyn.telemetry["tel/wall_clock/last"]) == \
        float(asyn.history["wall_clock"][-1])
    # M=K: no update ever waits for a later aggregation
    np.testing.assert_array_equal(
        np.asarray(asyn.telemetry["tel/update_staleness/max"]),
        np.zeros(N, np.int32))


# --------------------------------------------- determinism and chunking

def test_async_deterministic_across_fresh_jits(setup):
    """Fixed-seed async runs are identical across two independent jit
    executions (caches dropped in between): PRNG folding and the masked
    buffer scatters are fully deterministic."""
    acfg = AsyncCfg(buffer_m=2, delay_jitter=0.1)
    a = _run(setup, async_cfg=acfg)
    jax.clear_caches()
    b = _run(setup, async_cfg=acfg)
    for k in SYNC_KEYS + ASYNC_HIST_KEYS:
        np.testing.assert_array_equal(np.asarray(a.history[k]),
                                      np.asarray(b.history[k]), err_msg=k)
    _assert_trees_equal(a.params, b.params, "params")
    _assert_trees_equal(a.async_state, b.async_state, "astate")


def test_async_chunk_length_invariant_final_carry(setup):
    """chunk=1 and chunk=8 partition the same scan body differently but
    must agree on the final carry: params, fleet state, and the whole
    async buffer state (pending slots included)."""
    acfg = AsyncCfg(buffer_m=3)
    a = _run(setup, async_cfg=acfg, rounds=8, chunk=1)
    b = _run(setup, async_cfg=acfg, rounds=8, chunk=8)
    _assert_trees_equal(a.params, b.params, "params")
    _assert_trees_equal(a.state, b.state, "state")
    _assert_trees_equal(a.async_state, b.async_state, "astate")
    for k in SYNC_KEYS + ASYNC_HIST_KEYS:
        np.testing.assert_array_equal(np.asarray(a.history[k]),
                                      np.asarray(b.history[k]), err_msg=k)


# ------------------------------------------------ M<K invariants, e2e

def test_async_m_lt_k_staleness_and_conservation(setup):
    """M<K end-to-end: the virtual clock is nondecreasing, per-round
    staleness is nonnegative, aggregations advance the server version,
    and device-rounds are conserved — every dispatched update either
    landed or still occupies a live buffer slot."""
    res = _run(setup, async_cfg=AsyncCfg(buffer_m=2), rounds=6, chunk=3)
    h = res.history
    wc = np.asarray(h["wall_clock"], np.float64)
    assert np.all(np.diff(wc) >= 0) and wc[0] > 0
    assert np.all(np.asarray(h["mean_update_staleness"]) >= 0)
    np.testing.assert_array_equal(np.asarray(h["server_version"]),
                                  np.cumsum(np.asarray(h["n_aggregations"])))
    ast = res.async_state
    assert int(ast.n_dispatched) == 6 * K
    assert int(ast.n_landed) + int(np.asarray(ast.slot_live).sum()) \
        == int(ast.n_dispatched)
    assert np.all(np.asarray(h["n_pending"])
                  <= np.asarray(ast.slot_live).shape[0])
    # per-device landed staleness is reducer-only (core.metrics
    # ASYNC_SPECS) — the dense host schema keeps its legacy keys
    assert "update_staleness" not in h


def test_async_staleness_power_changes_trajectory(setup):
    """The staleness weight is live: damping a=2 must steer the model
    away from the a=0 trajectory once an aggregation mixes staleness
    levels. buffer_m=3 with K=4 leaves a carryover update each round, so
    later buffers blend fresh and stale updates — where γ=(1+s)^-a stops
    cancelling in the weight normalization. (Staleness-uniform buffers,
    e.g. M=2 with a full K=4 drain per round, are γ-invariant by
    construction: a common factor divides out.)"""
    a0 = _run(setup, async_cfg=AsyncCfg(buffer_m=3, staleness_power=0.0),
              rounds=6)
    a2 = _run(setup, async_cfg=AsyncCfg(buffer_m=3, staleness_power=2.0),
              rounds=6)
    # same selections on round 0 (same PRNG stream) ...
    np.testing.assert_array_equal(np.asarray(a0.history["selected"])[0],
                                  np.asarray(a2.history["selected"])[0])
    # ... but different aggregated params
    diff = [not np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a0.params),
                            jax.tree.leaves(a2.params))]
    assert any(diff)


# --------------------------------------------------- mixed-regime grid

def test_mixed_sync_async_grid_one_compile(setup):
    """run_campaign_grid with sync and async specs in ONE batched
    program: the sync cell stays bitwise-identical to a pure sync
    campaign, the async cell reports wall clock."""
    model, fleet, cx, cy, cfg = setup
    methods = {"rewafl": METHODS["rewafl"],
               "rewafl_async": async_variant(METHODS["rewafl"], buffer_m=2)}
    grid = eng.run_campaign_grid(model, fleet, cx, cy, cfg, methods,
                                 seeds=[0, 1], rounds=4, chunk_size=2)
    pure = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                  METHODS["rewafl"], seeds=[0, 1],
                                  rounds=4, chunk_size=2)
    for k in ("global_loss", "round_latency", "round_energy"):
        np.testing.assert_array_equal(
            np.asarray(grid["rewafl"][k]), np.asarray(pure[k]),
            err_msg=k)
    assert grid["rewafl_async"]["final_wall_clock"].shape == (2,)
    assert np.all(grid["rewafl_async"]["final_wall_clock"] > 0)
    # the async cell actually buffered: some rounds aggregate twice
    assert np.any(np.asarray(grid["rewafl_async"]["n_aggregations"]) > 1)


# ------------------------------------- buffer-op invariants (no deps)

def test_buffer_invariants_seeded_schedule():
    """Deterministic counterpart of tests/test_async_property.py (which
    needs the optional `hypothesis` dep): drive push_cohort/land_once
    over a seeded random schedule of cohorts and check the buffer
    invariants — disjoint landings, staleness ≥ 0, post-step occupancy
    < M, device-round conservation, monotone clock."""
    from repro.core.async_agg import land_once, push_cohort
    from repro.core.state import init_async_state
    rng = np.random.default_rng(0)
    params = {"w": jnp.zeros((2,), jnp.float32)}
    S = 12
    for m, k in ((1, 3), (2, 5), (3, 4), (4, 4)):
        cap, n_lands = m + k, -(-k // m)
        ast = init_async_state(params, S, cap)
        p = params
        for step in range(5):
            idx = jnp.asarray(rng.permutation(S)[:k], jnp.int32)
            live = jnp.asarray(rng.random(k) < 0.8)
            deltas = {"w": jnp.asarray(rng.normal(size=(k, 2)),
                                       jnp.float32)}
            ast, n_pushed = push_cohort(
                ast, deltas, idx, live,
                jnp.asarray(rng.random(k) + 0.1, jnp.float32),
                jnp.asarray(rng.random(k) * 5 + 0.1, jnp.float32))
            assert int(n_pushed) == int(live.sum())
            union = np.zeros(cap, bool)
            for _ in range(n_lands):
                live_before = np.asarray(ast.slot_live)
                stale_now = np.asarray(ast.server_version
                                       - ast.slot_version)
                t_before = float(ast.t_now)
                p, ast, info = land_once(p, ast, m, staleness_power=0.5)
                landed = np.asarray(info["landed"])
                assert not (landed & ~live_before).any()
                assert not (landed & union).any()
                union |= landed
                assert (stale_now[landed] >= 0).all()
                assert float(ast.t_now) >= t_before
            occ = int(np.asarray(ast.slot_live).sum())
            assert occ < m
            assert int(ast.n_dispatched) == int(ast.n_landed) + occ


# ------------------------------------------- sample_round_rates (hoist)

def test_sample_round_rates_hoist():
    """Regression for the duplicated rate-sampling branch hoisted out of
    core.round: the helper must be bitwise-identical to the two inlined
    forms it replaced — plain fleet sampling (static scenarios) and the
    channel-state-modulated form (dynamic scenarios)."""
    fleet = build_fleet(N, seed=3)
    key = jax.random.PRNGKey(11)
    np.testing.assert_array_equal(
        np.asarray(sample_round_rates(key, fleet)),
        np.asarray(sample_rates(key, fleet)))
    env = init_env_state(fleet, get_scenario("commuter-diurnal"),
                         key=jax.random.PRNGKey(5))
    np.testing.assert_array_equal(
        np.asarray(sample_round_rates(key, fleet, env)),
        np.asarray(sample_rates_from_mean(
            key, effective_rate_mean(env.channel_good, fleet),
            fleet.rate_sigma)))


# ------------------------------------------------------- run_fl (slow)

@pytest.mark.slow
def test_run_fl_async_end_to_end():
    """CLI-path smoke: run_fl(aggregation='async') returns the async
    history keys, a wall clock, and M=n_select parity with sync."""
    kw = dict(rounds=6, n_clients=10, n_select=4, per_client=16,
              target_acc=2.0, eval_every=3)
    sync = run_fl("cnn@mnist", "rewafl", **kw)
    asyn = run_fl("cnn@mnist", "rewafl", aggregation="async", buffer_m=4,
                  **kw)
    for k in ASYNC_HIST_KEYS:
        assert k in asyn.history and k not in sync.history
    assert asyn.wall_clock_s == float(asyn.history["wall_clock"][-1])
    np.testing.assert_array_equal(sync.history["global_loss"],
                                  asyn.history["global_loss"])
    np.testing.assert_array_equal(sync.acc_curve, asyn.acc_curve)
    buf = run_fl("cnn@mnist", "rewafl", aggregation="async", buffer_m=2,
                 **kw)
    assert np.all(np.asarray(buf.history["n_aggregations"]) >= 1)
    with pytest.raises(ValueError, match="needs engine='scan'"):
        run_fl("cnn@mnist", "rewafl", engine="loop", aggregation="async",
               rounds=1, n_clients=10, per_client=16)
