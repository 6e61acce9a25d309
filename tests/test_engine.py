"""Scan-engine tests: chunked-scan ≡ sequential round loop (PRNG folding
and numerics), campaign vmap batching, method-axis batching (one-compile
grids), async history off-load + carry donation, streaming telemetry
(on-device reducers ≡ dense-history reductions), early stop, fleet
sharding, and a mega-fleet compile/run smoke."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FLConfig, METHODS, MetricSpec, TelemetryCfg,
                        init_env_state, init_fleet_state, make_round_body,
                        make_round_fn, replicate_state)
from repro.core.metrics import DEFAULT_SPECS
from repro.core.policy import PolicyCfg
from repro.launch import engine as eng
from repro.launch.fl_run import build_task, build_task_batch
from repro.launch.mesh import make_fleet_mesh
from repro.models.fl_models import make_fl_model
from repro.sim.devices import build_fleet, build_fleet_batch

N, K = 10, 4


@pytest.fixture(scope="module")
def setup():
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(N, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", N, 0.8, per_client=16, n_test=32)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=6))
    return model, fleet, cx, cy, cfg


def _sequential(model, fleet, cx, cy, cfg, method, rounds, key, params):
    """Reference: per-round jitted dispatch, the seed driver's loop."""
    rf = make_round_fn(model, fleet, cx, cy, cfg, METHODS[method])
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet)
    hist = []
    for r in range(rounds):
        key, kr = jax.random.split(key)
        params, state, env, m = rf(params, state, env, kr,
                                   jnp.asarray(r, jnp.int32))
        hist.append(jax.device_get(m))
    return params, state, hist


def _assert_trees_close(a, b, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64), atol=atol)


def _parity(setup, rounds, chunk_size, atol=1e-5):
    model, fleet, cx, cy, cfg = setup
    key = jax.random.PRNGKey(7)
    params0 = model.init(jax.random.PRNGKey(0))
    res = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=rounds, key=key, params=params0,
                         ecfg=eng.EngineCfg(chunk_size=chunk_size))
    p_seq, s_seq, h_seq = _sequential(model, fleet, cx, cy, cfg, "rewafl",
                                      rounds, key, params0)
    assert res.rounds_run == rounds
    _assert_trees_close(res.params, p_seq, atol)
    _assert_trees_close(res.state, s_seq, atol)
    for k in ("global_loss", "round_latency", "round_energy",
              "n_participating", "n_failed", "mean_H_selected",
              "local_iters"):
        seq = np.asarray([h[k] for h in h_seq], np.float64)
        np.testing.assert_allclose(np.asarray(res.history[k], np.float64),
                                   seq, atol=atol, err_msg=k)
    sel_seq = np.stack([np.asarray(h["selected"]) for h in h_seq])
    np.testing.assert_array_equal(np.asarray(res.history["selected"]),
                                  sel_seq)


def test_scan_matches_sequential_rounds(setup):
    """Engine chunks (incl. a remainder chunk) ≡ N make_round_fn calls:
    same PRNG key folding, identical FleetState and metrics."""
    _parity(setup, rounds=5, chunk_size=3)


@pytest.mark.parametrize("method", ["rewafl", "oort"])
def test_local_iters_is_cohort_max_h(setup, method):
    """`local_iters`, the local-SGD loop's trip count, is the largest H
    over the round's selected devices: adaptive under REWAFL, H0 under
    a fixed-H method."""
    model, fleet, cx, cy, cfg = setup
    res = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS[method],
                         rounds=8, key=jax.random.PRNGKey(7),
                         params=model.init(jax.random.PRNGKey(0)),
                         ecfg=eng.EngineCfg(chunk_size=4))
    h = res.history
    # with no failures every selected device trained at its history H
    assert not np.asarray(h["n_failed"]).any()
    sel = np.asarray(h["selected"])
    H = np.asarray(h["H"])
    want = np.where(sel, H, 0).max(axis=1)
    np.testing.assert_array_equal(np.asarray(h["local_iters"]), want)
    if method == "oort":
        assert (want == cfg.policy.H0).all()
    else:
        assert want.max() > cfg.policy.H0


@pytest.mark.slow
def test_scan_matches_sequential_20_rounds(setup):
    """Acceptance-scale parity: ≥ 20 rounds on cnn@mnist."""
    _parity(setup, rounds=20, chunk_size=8)


def test_early_stop_at_chunk_boundary(setup):
    model, fleet, cx, cy, cfg = setup
    res = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=12, key=jax.random.PRNGKey(1),
                         init_key=jax.random.PRNGKey(0),
                         ecfg=eng.EngineCfg(chunk_size=3),
                         eval_fn=lambda p: 1.0, target_acc=0.5)
    assert res.rounds_run == 3            # stopped after the first chunk
    assert res.reached_round == 2
    assert len(res.history["global_loss"]) == 3


@pytest.mark.slow
def test_campaign_batch_matches_individual_runs(setup):
    """vmapped (seed-axis) campaigns ≡ per-seed engine runs."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 3)
    rounds = 4
    batch = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                   METHODS["rewafl"], seeds=seeds,
                                   rounds=rounds, chunk_size=2)
    assert batch["global_loss"].shape == (len(seeds), rounds)
    for i, s in enumerate(seeds):
        solo = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                              rounds=rounds, key=jax.random.PRNGKey(s + 1),
                              params=model.init(jax.random.PRNGKey(s + 2)),
                              ecfg=eng.EngineCfg(chunk_size=2))
        np.testing.assert_allclose(batch["global_loss"][i],
                                   solo.history["global_loss"], atol=1e-5)
        np.testing.assert_allclose(
            batch["final_residual_energy"][i],
            np.asarray(solo.state.residual_energy), atol=1e-3)


def test_round_body_closure_free_matches_bound_view(setup):
    """The closure-free round(params, state, env, fleet, cx, cy, key, r)
    and its bound legacy view share one computation graph. XLA may
    constant-fold a fleet that enters as a trace-time constant slightly
    differently than one passed as an argument (observed: a single-ulp
    difference in one latency element), so floats compare to 1e-4 —
    the selection masks and the engine-path golden history stay exact
    (tests/test_dynamics.py golden tests)."""
    model, fleet, cx, cy, cfg = setup
    body = jax.jit(make_round_body(model, cfg, METHODS["rewafl"]))
    bound = make_round_fn(model, fleet, cx, cy, cfg, METHODS["rewafl"])
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet)
    key = jax.random.PRNGKey(9)
    r = jnp.asarray(0, jnp.int32)
    pa, sa, ea, ma = body(params, state, env, fleet, cx, cy, key, r)
    pb, sb, eb, mb = bound(params, state, env, key, r)
    np.testing.assert_array_equal(np.asarray(ma["selected"]),
                                  np.asarray(mb["selected"]))
    for x, y in zip(jax.tree.leaves((pa, sa, ea, ma)),
                    jax.tree.leaves((pb, sb, eb, mb))):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=1e-6, atol=1e-4)


def test_build_fleet_batch_stacks_per_seed_draws():
    """(B, S) leaves; seed b reproduces build_fleet(seed=seeds[b]) and
    the cross-seed draws actually differ (the heterogeneity error bars
    the per-seed grids exist for)."""
    seeds = (0, 3, 7)
    fb = build_fleet_batch(seeds, N, init_energy_mean=0.3)
    assert fb.type_id.shape == (len(seeds), N)
    for b, s in enumerate(seeds):
        solo = build_fleet(N, seed=s, init_energy_mean=0.3)
        for bx, sx in zip(jax.tree.leaves(jax.tree.map(lambda x: x[b], fb)),
                          jax.tree.leaves(solo)):
            np.testing.assert_array_equal(np.asarray(bx), np.asarray(sx))
    init = np.asarray(fb.init_energy)
    assert not np.allclose(init[0], init[1])  # per-seed battery draws


def test_build_task_batch_stacks_per_seed_partitions():
    seeds = (0, 2)
    cxb, cyb, test = build_task_batch("cnn@mnist", seeds, N, 0.8,
                                      per_client=8, n_test=16)
    assert cxb.shape[:2] == (len(seeds), N) and cyb.shape[:2] == (2, N)
    assert test["x"].shape[0] == 2 and test["y"].shape == (2, 16)
    cx0, cy0, t0 = build_task("cnn@mnist", N, 0.8, per_client=8,
                              n_test=16, seed=2)
    np.testing.assert_array_equal(np.asarray(cxb[1]), np.asarray(cx0))
    assert not np.array_equal(np.asarray(cxb[0]), np.asarray(cxb[1]))


def test_per_seed_fleet_batch_matches_individual_runs(setup):
    """per_seed_fleets=True: seed i of the vmapped batch reproduces a solo
    engine run on that seed's own fleet/partition — and the cross-seed
    histories actually differ through the fleet draw."""
    model, _, _, _, cfg = setup
    seeds = (0, 3)
    rounds = 3
    fleetb = build_fleet_batch(seeds, N, init_energy_mean=0.3)
    cxb, cyb, _ = build_task_batch("cnn@mnist", seeds, N, 0.8,
                                   per_client=16, n_test=16)
    batch = eng.run_campaign_batch(model, fleetb, cxb, cyb, cfg,
                                   METHODS["rewafl"], seeds=seeds,
                                   rounds=rounds, chunk_size=2,
                                   per_seed_fleets=True)
    assert batch["global_loss"].shape == (len(seeds), rounds)
    assert not np.allclose(batch["round_energy"][0],
                           batch["round_energy"][1])
    for i, s in enumerate(seeds):
        fleet_i = build_fleet(N, seed=s, init_energy_mean=0.3)
        cx_i, cy_i, _ = build_task("cnn@mnist", N, 0.8, per_client=16,
                                   n_test=16, seed=s)
        solo = eng.run_rounds(model, fleet_i, cx_i, cy_i, cfg,
                              METHODS["rewafl"], rounds=rounds,
                              key=jax.random.PRNGKey(s + 1),
                              params=model.init(jax.random.PRNGKey(s + 2)),
                              ecfg=eng.EngineCfg(chunk_size=2))
        np.testing.assert_allclose(batch["global_loss"][i],
                                   solo.history["global_loss"], atol=1e-5)
        np.testing.assert_allclose(batch["final_residual_energy"][i],
                                   np.asarray(solo.state.residual_energy),
                                   atol=1e-3)


@pytest.mark.slow
def test_per_seed_fleet_variance_exceeds_shared(setup):
    """ISSUE 3 acceptance: per-seed fleets yield materially larger
    cross-seed spread of energy/final-loss than the legacy shared-fleet
    batch, whose variance covers init/round noise only (measured ≈3–4×
    at this scale; asserted at 1.5× for headroom)."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 1, 2, 3)
    shared = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                    METHODS["rewafl"], seeds=seeds,
                                    rounds=4, chunk_size=2)
    fleetb = build_fleet_batch(seeds, N, init_energy_mean=0.3)
    cxb, cyb, _ = build_task_batch("cnn@mnist", seeds, N, 0.8,
                                   per_client=16, n_test=16)
    per_seed = eng.run_campaign_batch(model, fleetb, cxb, cyb, cfg,
                                      METHODS["rewafl"], seeds=seeds,
                                      rounds=4, chunk_size=2,
                                      per_seed_fleets=True)
    e_sh = shared["round_energy"].sum(1)
    e_ps = per_seed["round_energy"].sum(1)
    assert e_ps.std() > 0
    assert e_ps.std() > 1.5 * e_sh.std()
    l_sh = shared["global_loss"][:, -1]
    l_ps = per_seed["global_loss"][:, -1]
    assert l_ps.std() > 1.5 * l_sh.std()


GRID_METHODS = ("random", "oort", "autofl", "rewafl")


def test_method_batched_grid_matches_per_method(setup):
    """ISSUE 4 tentpole acceptance: the one-compile (method × seed) grid
    (MethodParams + lax.switch dispatch, method axis vmapped over the
    seed vmap) reproduces the per-method `run_campaign_batch` histories —
    selection masks exactly, floats to tolerance — for every method and
    seed."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 3)
    rounds = 3
    kw = dict(seeds=seeds, rounds=rounds, chunk_size=2,
              collect_per_device=True)
    methods = {m: METHODS[m] for m in GRID_METHODS}
    batched = eng.run_campaign_grid(model, fleet, cx, cy, cfg, methods,
                                    method_batched=True, **kw)
    for m in GRID_METHODS:
        solo = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                      METHODS[m], **kw)
        hb = batched[m]
        np.testing.assert_array_equal(
            np.asarray(hb["selected"]), np.asarray(solo["selected"]),
            err_msg=f"{m}: selection masks diverged")
        for k in ("global_loss", "round_energy", "round_latency",
                  "mean_H_selected", "local_iters", "n_participating"):
            np.testing.assert_allclose(
                np.asarray(hb[k], np.float64),
                np.asarray(solo[k], np.float64), atol=1e-5, err_msg=f"{m}/{k}")
        np.testing.assert_allclose(hb["final_residual_energy"],
                                   solo["final_residual_energy"], atol=1e-3)


def test_method_batched_grid_per_seed_fleets_and_eval(setup):
    """Batched grid with per-seed fleets + chunk-boundary eval: history
    axes are (B, R), acc_curve (n_chunks, B), reached_round (B,) per
    method, matching the per-method fallback."""
    model, _, _, _, cfg = setup
    seeds = (0, 2)
    fleetb = build_fleet_batch(seeds, N, init_energy_mean=0.3)
    cxb, cyb, _ = build_task_batch("cnn@mnist", seeds, N, 0.8,
                                   per_client=16, n_test=16)
    kw = dict(seeds=seeds, rounds=4, chunk_size=2, per_seed_fleets=True,
              eval_fn=lambda p: jnp.full((len(seeds),), 0.7),
              target_acc=0.5)
    methods = {m: METHODS[m] for m in ("random", "rewafl")}
    grid = eng.run_campaign_grid(model, fleetb, cxb, cyb, cfg, methods,
                                 method_batched=True, **kw)
    for m, h in grid.items():
        assert h["global_loss"].shape == (2, 4)
        assert h["acc_curve"].shape == (2, 2)
        np.testing.assert_array_equal(h["reached_round"], [1, 1])
        solo = eng.run_campaign_batch(model, fleetb, cxb, cyb, cfg,
                                      METHODS[m], **kw)
        np.testing.assert_allclose(h["global_loss"], solo["global_loss"],
                                   atol=1e-5)


def test_method_batched_grid_zero_rounds(setup):
    model, fleet, cx, cy, cfg = setup
    methods = {m: METHODS[m] for m in ("random", "rewafl")}
    grid = eng.run_campaign_grid(model, fleet, cx, cy, cfg, methods,
                                 seeds=(0, 1), rounds=0, chunk_size=2)
    for h in grid.values():
        assert h["global_loss"].shape == (2, 0)
        assert h["final_residual_energy"].shape == (2, N)


def test_single_method_grid_uses_fallback(setup):
    """A 1-method grid keeps the static-dispatch path (the bitwise-golden
    MethodSpec branch) and still returns the same schema."""
    model, fleet, cx, cy, cfg = setup
    grid = eng.run_campaign_grid(model, fleet, cx, cy, cfg,
                                 {"rewafl": METHODS["rewafl"]},
                                 seeds=(0, 1), rounds=2, chunk_size=2)
    assert grid["rewafl"]["global_loss"].shape == (2, 2)


def test_donate_matches_non_donate(setup):
    """EngineCfg(donate=True) (the default) must agree with donate=False
    and must not consume the caller's params/state (run_rounds copies
    before the first donated chunk)."""
    model, fleet, cx, cy, cfg = setup
    key = jax.random.PRNGKey(7)
    params0 = model.init(jax.random.PRNGKey(0))
    don = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=5, key=key, params=params0,
                         ecfg=eng.EngineCfg(chunk_size=2, donate=True))
    # caller's buffers must still be alive after the donated run
    _ = [np.asarray(x) for x in jax.tree.leaves(params0)]
    ref = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=5, key=key, params=params0,
                         ecfg=eng.EngineCfg(chunk_size=2, donate=False))
    np.testing.assert_array_equal(np.asarray(don.history["selected"]),
                                  np.asarray(ref.history["selected"]))
    for k in ("global_loss", "round_energy", "round_latency"):
        np.testing.assert_allclose(np.asarray(don.history[k], np.float64),
                                   np.asarray(ref.history[k], np.float64),
                                   atol=1e-6, err_msg=k)
    _assert_trees_close(don.state, ref.state, 1e-5)


def test_probe_every_amortizes_global_loss(setup):
    """probe_every=2: non-probe rounds reuse the carried g_loss — the
    global_loss metric repeats the last probed value — while selection
    and training still run every round."""
    model, fleet, cx, cy, cfg = setup
    cfg2 = dataclasses.replace(cfg, probe_every=2)
    res = eng.run_rounds(model, fleet, cx, cy, cfg2, METHODS["rewafl"],
                         rounds=4, key=jax.random.PRNGKey(7),
                         init_key=jax.random.PRNGKey(0),
                         ecfg=eng.EngineCfg(chunk_size=2))
    gl = np.asarray(res.history["global_loss"], np.float64)
    assert gl[1] == gl[0] and gl[3] == gl[2]  # carried between probes
    assert gl[2] != gl[0]                     # refreshed at round 2
    assert (np.asarray(res.history["n_participating"]) > 0).all()


def test_probe_every_one_is_exact(setup):
    """probe_every=1 (the default) is the exact paper semantics: history
    identical to an explicit probe_every=1 config and g_loss refreshed
    every round (global_loss strictly follows the fresh probe)."""
    model, fleet, cx, cy, cfg = setup
    kw = dict(rounds=3, key=jax.random.PRNGKey(7),
              init_key=jax.random.PRNGKey(0),
              ecfg=eng.EngineCfg(chunk_size=2))
    a = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"], **kw)
    b = eng.run_rounds(model, fleet, cx, cy,
                       dataclasses.replace(cfg, probe_every=1),
                       METHODS["rewafl"], **kw)
    np.testing.assert_array_equal(np.asarray(a.history["global_loss"]),
                                  np.asarray(b.history["global_loss"]))
    np.testing.assert_array_equal(np.asarray(a.state.g_loss),
                                  np.asarray(b.state.g_loss))


# ------------------------------------------------- streaming telemetry

def _ring_specs(rounds):
    """DEFAULT_SPECS plus full-trace rings (ring(every=1, cap=R) ≡ the
    dense (R, S) trace), so reducers can be checked against the exact
    per-round values they folded."""
    return DEFAULT_SPECS + (
        MetricSpec("H", "ring", every=1, cap=rounds),
        MetricSpec("residual_energy", "ring", every=1, cap=rounds),
        MetricSpec("round_energy", "sum"),
    )


def test_streaming_matches_dense_history_reductions(setup):
    """ISSUE 5 tentpole acceptance: streaming reducers on static-paper
    must match the dense-history reductions — selection counts and H
    traces exactly, float aggregates to fp tolerance — while the dense
    scalar history stays bitwise-identical between modes and the (R, S)
    leaves vanish from the streaming history."""
    model, fleet, cx, cy, cfg = setup
    R = 5
    kw = dict(rounds=R, key=jax.random.PRNGKey(7),
              init_key=jax.random.PRNGKey(0))
    dense = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                           ecfg=eng.EngineCfg(chunk_size=3), **kw)
    tcfg = TelemetryCfg(mode="streaming", specs=_ring_specs(R))
    stream = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                            ecfg=eng.EngineCfg(chunk_size=3,
                                               collect_per_device=False,
                                               telemetry=tcfg), **kw)
    # dense-mode scalar history is bitwise-unchanged by the refactor
    for k in ("global_loss", "round_energy", "round_latency",
              "n_participating", "mean_H_selected", "local_iters"):
        np.testing.assert_array_equal(np.asarray(dense.history[k]),
                                      np.asarray(stream.history[k]),
                                      err_msg=k)
    assert "selected" not in stream.history
    assert "H" not in stream.history
    t = stream.telemetry
    H = np.asarray(dense.history["H"])          # (R, S)
    sel = np.asarray(dense.history["selected"])
    np.testing.assert_array_equal(t["tel/H/ring"], H)
    np.testing.assert_array_equal(t["tel/selected/count"], sel.sum(0))
    np.testing.assert_array_equal(t["tel/H/last"], H[-1])
    np.testing.assert_allclose(t["tel/H/mean"], H.mean(0), rtol=1e-6)
    # residual energy: the streamed ring IS the dense trace; mean/std/
    # max reducers must match its float64 reductions (tolerances scale
    # with the ~1e4 J magnitudes: f32 ulp there is ~2e-3)
    rE = np.asarray(t["tel/residual_energy/ring"], np.float64)
    scale = np.abs(rE).max()
    np.testing.assert_allclose(t["tel/residual_energy/mean"], rE.mean(0),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(t["tel/residual_energy/std"], rE.std(0),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(t["tel/residual_energy/max"], rE.max(0),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(t["tel/round_energy/sum"],
                               np.asarray(dense.history["round_energy"],
                                          np.float64).sum(),
                               rtol=1e-5)
    # final state agrees between modes (same compiled math)
    np.testing.assert_allclose(np.asarray(stream.state.residual_energy),
                               np.asarray(dense.state.residual_energy),
                               atol=1e-3)


def test_streaming_campaign_batch_per_seed(setup):
    """Streaming reducers under the seed vmap: (B, S) outputs in the
    history, each seed's aggregates matching its solo streaming run."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 3)
    R = 4
    tcfg = TelemetryCfg(mode="streaming")
    batch = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                   METHODS["rewafl"], seeds=seeds,
                                   rounds=R, chunk_size=2,
                                   telemetry=tcfg)
    assert batch["tel/selected/count"].shape == (len(seeds), N)
    assert batch["tel/residual_energy/mean"].shape == (len(seeds), N)
    for i, s in enumerate(seeds):
        solo = eng.run_rounds(
            model, fleet, cx, cy, cfg, METHODS["rewafl"], rounds=R,
            key=jax.random.PRNGKey(s + 1),
            params=model.init(jax.random.PRNGKey(s + 2)),
            ecfg=eng.EngineCfg(chunk_size=2, collect_per_device=False,
                               telemetry=tcfg))
        np.testing.assert_array_equal(batch["tel/selected/count"][i],
                                      solo.telemetry["tel/selected/count"])
        np.testing.assert_allclose(
            batch["tel/residual_energy/mean"][i],
            solo.telemetry["tel/residual_energy/mean"], atol=1e-2)
        np.testing.assert_array_equal(batch["tel/H/last"][i],
                                      solo.telemetry["tel/H/last"])


def test_streaming_method_batched_grid_matches_fallback(setup):
    """Streaming telemetry through the one-compile (method × seed) grid:
    per-method tel outputs slice correctly off the flattened cell axis
    and match the per-method fallback path."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 3)
    tcfg = TelemetryCfg(mode="streaming")
    kw = dict(seeds=seeds, rounds=3, chunk_size=2, telemetry=tcfg)
    methods = {m: METHODS[m] for m in ("random", "oort", "rewafl")}
    grid = eng.run_campaign_grid(model, fleet, cx, cy, cfg, methods,
                                 method_batched=True, **kw)
    for m in methods:
        solo = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                                      METHODS[m], **kw)
        np.testing.assert_array_equal(
            grid[m]["tel/selected/count"], solo["tel/selected/count"],
            err_msg=f"{m}: selection counts diverged")
        np.testing.assert_allclose(
            grid[m]["tel/residual_energy/mean"],
            solo["tel/residual_energy/mean"], atol=1e-2, err_msg=m)
        np.testing.assert_array_equal(grid[m]["tel/H/last"],
                                      solo["tel/H/last"], err_msg=m)


def test_run_fl_streaming_telemetry():
    """run_fl(telemetry='streaming'): per-round scalars equal the dense
    run, sel_count comes from the count reducer, H_trace is gone, and
    RunResult.telemetry carries the per-device aggregates."""
    from repro.launch.fl_run import run_fl
    kw = dict(rounds=4, n_clients=N, n_select=K, per_client=8,
              target_acc=2.0, eval_every=2)
    dense = run_fl("cnn@mnist", "rewafl", **kw)
    stream = run_fl("cnn@mnist", "rewafl", telemetry="streaming", **kw)
    np.testing.assert_array_equal(dense.history["global_loss"],
                                  stream.history["global_loss"])
    np.testing.assert_array_equal(dense.history["sel_count"],
                                  stream.history["sel_count"])
    assert "H_trace" in dense.history and "H_trace" not in stream.history
    assert stream.telemetry is not None
    assert stream.telemetry["tel/staleness/max"].shape == (N,)
    with pytest.raises(ValueError, match="needs engine='scan'"):
        run_fl("cnn@mnist", "rewafl", engine="loop",
               telemetry="streaming", **kw)


def test_campaign_batch_eval_curve_and_reached_round(setup):
    """Chunk-boundary eval: acc_curve is (n_chunks, B); reached_round
    records the first chunk-end round per seed meeting the target."""
    model, fleet, cx, cy, cfg = setup
    seeds = (0, 1)
    accs = iter([np.array([0.2, 0.6]), np.array([0.7, 0.9])])
    h = eng.run_campaign_batch(model, fleet, cx, cy, cfg,
                               METHODS["rewafl"], seeds=seeds, rounds=4,
                               chunk_size=2,
                               eval_fn=lambda p: next(accs),
                               target_acc=0.5)
    assert h["acc_curve"].shape == (2, 2)
    np.testing.assert_array_equal(h["reached_round"], [3, 1])
    assert h["chunk_wall_s"].shape == (2,)
    np.testing.assert_array_equal(h["chunk_rounds"], [2, 2])


def test_run_rounds_zero_rounds_empty_history(setup):
    """rounds=0 must not IndexError: empty but correctly-keyed history."""
    model, fleet, cx, cy, cfg = setup
    res = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=0, key=jax.random.PRNGKey(1),
                         init_key=jax.random.PRNGKey(0))
    assert res.rounds_run == 0
    for k in ("global_loss", "round_energy", "n_participating",
              "n_available", "selected"):
        assert k in res.history, k
        assert len(res.history[k]) == 0
    assert res.history["selected"].shape == (0, N)


def test_campaign_batch_zero_rounds_empty_history(setup):
    model, fleet, cx, cy, cfg = setup
    h = eng.run_campaign_batch(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                               seeds=(0, 1), rounds=0, chunk_size=2)
    assert h["global_loss"].shape == (2, 0)
    assert h["final_residual_energy"].shape == (2, N)


def test_replicate_state_shape(setup):
    _, fleet, _, _, cfg = setup
    st = init_fleet_state(fleet, H0=cfg.policy.H0)
    st3 = replicate_state(st, 3)
    assert st3.residual_energy.shape == (3, N)
    assert st3.dropped.shape == (3, N)


def test_shard_over_fleet_places_fleet_axis(setup):
    """The sharding layer must shard exactly the (S, ...) leaves and
    replicate the rest — runs on any device count (mesh of 1 here)."""
    model, fleet, cx, cy, cfg = setup
    mesh = make_fleet_mesh(1)
    sharded = eng.shard_over_fleet(fleet, mesh, fleet.n)
    P = jax.sharding.PartitionSpec
    for leaf in jax.tree.leaves(sharded):
        assert leaf.sharding.spec == P("fleet")
    params = eng.replicate(model.init(jax.random.PRNGKey(0)), mesh)
    for leaf in jax.tree.leaves(params):
        assert leaf.sharding.spec == P()


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device for a real fleet shard")
def test_sharded_run_matches_unsharded(setup):
    model, fleet, cx, cy, cfg = setup
    key = jax.random.PRNGKey(7)
    params0 = model.init(jax.random.PRNGKey(0))
    base = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                          rounds=2, key=key, params=params0,
                          ecfg=eng.EngineCfg(chunk_size=2))
    shard = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                           rounds=2, key=key, params=params0,
                           ecfg=eng.EngineCfg(chunk_size=2, fleet_shards=2))
    np.testing.assert_allclose(base.history["global_loss"],
                               shard.history["global_loss"], atol=1e-5)


@pytest.mark.slow
def test_mega_fleet_round_compiles_and_runs(setup):
    """10k-device fleet: one engine round must compile and run on CPU
    (selection, utility, energy, and state updates are all (S,) ops)."""
    S = 10_000
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(S, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", S, 0.8, per_client=4, n_test=32)
    cfg = FLConfig(n_select=20, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=4))
    res = eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=1, key=jax.random.PRNGKey(1),
                         init_key=jax.random.PRNGKey(0),
                         ecfg=eng.EngineCfg(chunk_size=1))
    assert res.rounds_run == 1
    assert np.isfinite(res.history["global_loss"]).all()
    n_sel = int(np.asarray(res.history["selected"]).sum())
    assert 0 < n_sel <= 20
    assert np.asarray(res.state.residual_energy).shape == (S,)
