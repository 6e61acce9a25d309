"""Scan-engine throughput benchmark -> BENCH_engine.json.

Measures warm compiled-chunk throughput (rounds/s, device-rounds/s) of
the FL engine at fleet scales S ∈ {100, 1k, 10k} plus one dynamic
scenario at the largest scale, and writes the machine-readable
`BENCH_engine.json` the ROADMAP perf trajectory gates on. The dynamic
row doubles as the dynamics-overhead regression check: `dyn_overhead`
is the fractional slowdown of commuter-diurnal vs static at S=10k
(acceptance: < 0.10).

Full runs additionally measure the `campaign_grid_4x5` row — a 4-method
× 5-seed campaign grid through the one-compile method-batched engine
(`run_campaign_grid(method_batched=True)`) against the per-method
fallback, reporting grid wall-clock, total compile seconds both ways,
and the compile-amortization ratio (ISSUE 4 acceptance: ≥ 3×) — plus
the streaming-telemetry rows: `scan_round_S100000_streaming` runs
per-device telemetry (DEFAULT_SPECS reducers in the scan carry) at a
fleet scale where dense (R, S) collection would OOM/thrash the host,
and `telemetry_host_bytes_S10000` records the measured dense-vs-
streaming host history footprint with mega-fleet projections.

The `async_round_S{min,max}` rows run the FedBuff buffered-aggregation
round body (`core.async_agg`, buffer_m=10) at the smallest and largest
scales; `async_overhead` is the fractional us_per_round cost of the
pending-buffer carry + masked land steps vs the paired sync row.

The `fault_round_S{min}` row runs a static scenario with the chaos
layer on (`sim.faults`: aborts/uplink loss/corruption/stragglers, and
the `core.resilience` robust screen auto-enabled); `fault_overhead` is
the fractional us_per_round cost vs the paired same-scale static row —
the CI bench-gate bounds its throughput like the async row.

The `fused_select_S*` rows time the fused utility→top-K→FedAvg pass
(`kernels/rewafl_select.select_aggregate`) against the XLA reference
composition at S ∈ {10k, 100k} (plus 1M in full sweeps); CI gates the
fused path's `device_rounds_s` ratio AND the absolute acceptance floor
`speedup_vs_xla ≥ 1.5` at S=100k via `check_regression --min-spec`.

The `engine_phases_S*` rows (repro.obs) run a short campaign through
`run_rounds` under a span tracer + fleet-health monitors and report
per-phase wall attribution — compile / dispatch / history-drain / eval
/ transfer seconds — plus the flat-battery count and whole-campaign
staleness P95 from the streaming quantile reducers. `compile_s` of the
small row gates in CI with `--direction lower`.

  make bench-engine            # or: python -m benchmarks.engine_bench

CLI (for the CI regression gate, which measures the cheap S=100 scale
plus the batched-only grid row, then gates everything in ONE
check_regression invocation so all failures report together):

  python -m benchmarks.engine_bench --scales 100 --no-dynamic \
      --no-streaming --grid-no-per-method --out /tmp/bench_fresh.json
  python -m benchmarks.check_regression BENCH_engine.json \
      /tmp/bench_fresh.json \
      --spec scan_round_S100,async_round_S100,fault_round_S100:device_rounds_s:higher:0.30 \
      --spec 'fused_select_*:device_rounds_s:higher:0.30' \
      --spec campaign_grid_4x5:grid_wall_s:lower:0.30 \
      --spec campaign_grid_4x5,engine_phases_S100:compile_s:lower:0.75 \
      --min-spec fused_select_S100000:speedup_vs_xla:1.5
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ROOT, _steady_timing, emit
from repro.launch import compile_cache
from repro.obs.log import configure_logging, get_logger

log = get_logger("benchmarks.engine_bench")

SCALES = (100, 1_000, 10_000)
DYNAMIC_SCENARIO = "commuter-diurnal"
GRID_METHODS = ("random", "oort", "autofl", "rewafl")
GRID_SEEDS = 5
OUT_PATH = os.path.join(ROOT, "BENCH_engine.json")


def measure_engine(S: int, scenario: str = "static-paper", *,
                   chunk: int = 0, timed_chunks: int = 1,
                   streaming: bool = False,
                   async_m: Optional[int] = None) -> Dict:
    """Warm compiled chunks at fleet scale S under `scenario`: fixed
    per-device work (tiny CNN, probe 2, batch 2) so the numbers isolate
    round dispatch + fleet-axis + dynamics overhead, not model FLOPs.

    With timed_chunks > 1 the reported throughput is the BEST chunk
    (timeit-style min): shared/contended hosts show ±40% wall-clock
    swings, and best-of-N approaches the machine's true capability so
    baseline-vs-fresh ratios reflect code, not contention spikes.

    `streaming=True` runs the chunk with the DEFAULT_SPECS telemetry
    reducers folded in the carry instead of dense (R, S) history — the
    regime that makes S ≥ 100k per-device telemetry feasible at all
    (dense collection is O(R·S) host bytes).

    `async_m=M` runs the FedBuff buffered-aggregation round body
    (`core.async_agg`, AsyncCfg(buffer_m=M)) instead of the sync
    barrier — the `async_round_S*` rows, measuring the cost of the
    pending-buffer carry + masked land/aggregate steps against the
    same-scale sync row."""
    from repro.core import (AsyncCfg, FLConfig, METHODS, TelemetryCfg,
                            init_fleet_state)
    from repro.core.policy import PolicyCfg
    from repro.core.round import make_round_body
    from repro.core.state import init_async_state
    from repro.launch.engine import _telemetry_carry, make_chunk_fn
    from repro.launch.fl_run import build_task
    from repro.models.fl_models import make_fl_model
    from repro.sim.devices import build_fleet
    from repro.sim.dynamics import Scenario, get_scenario, init_env_state

    scen = (scenario if isinstance(scenario, Scenario)
            else get_scenario(scenario))
    chunk = chunk or (8 if S <= 1_000 else 2)
    model = make_fl_model("cnn@mnist", small=True)
    cfg = FLConfig(n_select=20, batch_size=2, probe_size=2, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=4))
    fleet = build_fleet(S, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", S, 0.8, per_client=2, n_test=16)
    tcfg = TelemetryCfg(mode="streaming") if streaming else None
    acfg = AsyncCfg(buffer_m=async_m) if async_m else None
    ck = make_chunk_fn(model, cfg, METHODS["rewafl"],
                       chunk_size=chunk, scenario=scen,
                       collect_per_device=not streaming, telemetry=tcfg,
                       async_cfg=acfg)
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet, scen,
                         key=jax.random.PRNGKey(3) if scen.dynamic else None)
    key = jax.random.PRNGKey(1)
    lead = (params, state) + ((init_async_state(
        params, S, acfg.slots(cfg.n_select)),) if acfg else ())
    extra = ()
    if streaming:
        body = make_round_body(model, cfg, METHODS["rewafl"], scen)
        extra = (_telemetry_carry(tcfg, body,
                                  (params, state, env, fleet, cx, cy, key,
                                   jnp.asarray(0, jnp.int32))),)
    t0 = time.time()
    out = ck(*lead, env, fleet, cx, cy, key,
             jnp.asarray(0, jnp.int32), *extra)  # compile
    jax.block_until_ready(out[0])
    compile_s = time.time() - t0
    # output order: params, state, [astate,] env, key, [tel,] hist
    n_lead = 3 if acfg else 2
    chunk_walls = []
    for i in range(timed_chunks):
        t0 = time.time()
        extra = (out[n_lead + 2],) if streaming else ()
        out = ck(*out[:n_lead], out[n_lead], fleet, cx, cy,
                 out[n_lead + 1], jnp.asarray((i + 1) * chunk, jnp.int32),
                 *extra)
        jax.block_until_ready(out[0])
        chunk_walls.append(time.time() - t0)
    dt = min(chunk_walls)
    return {"S": S, "scenario": scen.name, "chunk": chunk,
            "telemetry": "streaming" if streaming else "dense",
            "aggregation": f"async_m{async_m}" if async_m else "sync",
            "us_per_round": dt / chunk * 1e6,
            "rounds_s": chunk / dt,
            "device_rounds_s": chunk / dt * S,
            "compile_s": compile_s,
            "timed_chunks": timed_chunks}


def measure_fused_select(S: int, *, P: int = 64, k: int = 20,
                         eps: float = 0.1, n: int = 10) -> Dict:
    """Fused utility→top-K→FedAvg pass vs the XLA reference composition
    at fleet scale S — the traced selection hot path the campaign-grid
    engine compiles (`core.round` traced dispatch, `kernel_backend`).

    Both backends run the identical composition — REWAFL utility from
    the `UtilityInputs` leaves, traced-ε ε-greedy selection, mask →
    K-row gather → `kernels/fedavg` weighted reduction — and differ
    only in the selection lowering: 'xla' answers the two rank queries
    with the (S,) stable-argsort rank space (`_desc_rank`, O(S log S)),
    the fused path with the static-k_cap `lax.top_k` candidate emission
    (`kernels/rewafl_select.select_traced`). ISSUE 10's acceptance
    gates `speedup_vs_xla ≥ 1.5` at S=100k via CI `--min-spec`."""
    from repro.core import utility as util
    from repro.kernels.fedavg import ops as fedavg_ops
    from repro.kernels.rewafl_select import ops as rsel

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    ui = util.UtilityInputs(
        stat=jax.random.uniform(ks[0], (S,)) * 3.0,
        t=jax.random.uniform(ks[1], (S,)) * 2.0 + 0.1,
        e=jax.random.uniform(ks[2], (S,)) * 0.05 + 0.01,
        residual=jax.random.uniform(ks[3], (S,)) * 0.5 + 0.1,
        e0=jnp.full((S,), 0.05))
    available = jax.random.uniform(ks[4], (S,)) < 0.8
    deltas = jax.random.normal(ks[5], (S, P), jnp.float32)
    weights = jax.random.uniform(ks[6], (S,)) + 0.5
    sel_key = ks[7]
    eps_t = jnp.asarray(eps, jnp.float32)

    def one(backend: str) -> float:
        def pass_(kk):
            utils = util.rewafl_utility_from(ui, T_round=1.0, alpha=2.0,
                                             beta=2.0)
            mask = rsel.select_traced(kk, utils, k, available, eps_t,
                                      backend=backend)
            idx = jnp.nonzero(mask, size=k, fill_value=0)[0]
            live = jnp.arange(k) < mask.sum()
            w = weights[idx] * live
            wn = w / jnp.maximum(w.sum(), 1e-9)
            return mask, fedavg_ops.weighted_aggregate(deltas[idx], wn)

        f = jax.jit(pass_)
        jax.block_until_ready(f(sel_key))  # compile
        t0 = time.time()
        for _ in range(n):
            out = f(sel_key)
        jax.block_until_ready(out[1])
        return (time.time() - t0) / n * 1e6

    us_xla = one("xla")
    us_fused = one("pallas")
    return {"S": S, "P": P, "k": k, "eps": eps,
            "us_fused": us_fused, "us_xla": us_xla,
            "device_rounds_s": S / us_fused * 1e6,
            "xla_device_rounds_s": S / us_xla * 1e6,
            "speedup_vs_xla": us_xla / us_fused}


def measure_host_bytes(S: int = 10_000, rounds: int = 8,
                       chunk: int = 2) -> Dict:
    """Host-side history footprint, dense vs streaming, at fleet scale S.

    Runs the same short campaign twice through `run_rounds` — once with
    dense per-device collection ((R, S) `selected`/`H` host buffers) and
    once with streaming DEFAULT_SPECS reducers — and reports the bytes
    the host actually holds at the end, plus the per-round growth rate
    of the dense path (the streaming footprint is R-independent). The
    projected columns extrapolate to the mega-fleet regime the ROADMAP
    targets (S=1M, R=500), where the dense per-device history alone is
    ~2.5 GB per metric pair and streaming stays O(S).

    The carry_bytes_* columns report the per-campaign scan-carry
    footprint of the FleetState/EnvState leaves at this S, full-precision
    vs `EngineCfg.compact_carry` (bf16 float leaves) — the saving the
    compact-carry mode buys per grid cell at mega-fleet scale."""
    from repro.core import (FLConfig, METHODS, TelemetryCfg,
                            init_fleet_state)
    from repro.core.policy import PolicyCfg
    from repro.launch.engine import (EngineCfg, _compact_pair, run_rounds)
    from repro.launch.fl_run import build_task
    from repro.models.fl_models import make_fl_model
    from repro.sim.devices import build_fleet
    from repro.sim.dynamics import init_env_state

    model = make_fl_model("cnn@mnist", small=True)
    cfg = FLConfig(n_select=20, batch_size=2, probe_size=2, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=4))
    fleet = build_fleet(S, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", S, 0.8, per_client=2, n_test=16)

    def tree_bytes(*trees):
        return sum(int(jnp.asarray(leaf).nbytes)
                   for t in trees for leaf in jax.tree.leaves(t))

    def one(streaming: bool):
        ecfg = EngineCfg(chunk_size=chunk,
                         collect_per_device=not streaming,
                         telemetry=TelemetryCfg(
                             mode="streaming" if streaming else "dense"))
        res = run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=rounds, key=jax.random.PRNGKey(1),
                         init_key=jax.random.PRNGKey(0), ecfg=ecfg)
        hist = sum(int(np.asarray(v).nbytes)
                   for v in res.history.values())
        tel = sum(int(np.asarray(v).nbytes)
                  for v in (res.telemetry or {}).values())
        per_dev = sum(int(np.asarray(res.history[k]).nbytes)
                      for k in ("selected", "H") if k in res.history)
        return hist + tel, per_dev

    dense_total, dense_per_dev = one(streaming=False)
    stream_total, _ = one(streaming=True)
    dense_rate = dense_per_dev / max(rounds, 1)        # bytes per round
    state0 = init_fleet_state(fleet, H0=cfg.policy.H0)
    env0 = init_env_state(fleet, None)
    carry_full = tree_bytes(state0, env0)
    carry_compact = tree_bytes(*_compact_pair(state0, env0))
    return {"S": S, "rounds": rounds,
            "carry_bytes_f32": carry_full,
            "carry_bytes_compact": carry_compact,
            "carry_saving_frac": 1.0 - carry_compact / carry_full,
            "dense_bytes": dense_total,
            "streaming_bytes": stream_total,
            "dense_per_device_bytes_per_round": dense_rate,
            # dense per-device history grows linearly in R and S;
            # streaming telemetry is O(S) however long the campaign
            "projected_dense_gb_S1M_R500":
                dense_rate / S * 1_000_000 * 500 / 1e9,
            "projected_streaming_gb_S1M_R500":
                stream_total / S * 1_000_000 / 1e9}


def measure_campaign_grid(S: int = 100, *, n_seeds: int = GRID_SEEDS,
                          rounds: int = 12, chunk: int = 4,
                          per_method: bool = True) -> Dict:
    """4-method × n_seeds campaign grid, method-batched vs per-method.

    Runs the same (method × seed) grid twice through
    `engine.run_campaign_grid`: once with `method_batched=True` (one
    MethodParams trace, one XLA compile for the whole grid) and once with
    the per-method fallback (one compile per method). Reports each path's
    wall-clock and total compile seconds (recovered per method from the
    chunk timing, as `benchmarks.common._steady_timing` does for the
    paper grids) plus the compile-amortization ratio the ISSUE-4
    acceptance gates on (≥ 3×).

    `per_method=False` measures only the batched path (grid_wall_s /
    compile_s / us_per_round): the CI bench-gate uses it so it can gate
    those keys with `check_regression --direction lower` without paying
    for the 4-compile fallback baseline on every PR."""
    from repro.core import FLConfig, METHODS
    from repro.core.policy import PolicyCfg
    from repro.launch.engine import run_campaign_grid
    from repro.launch.fl_run import build_task
    from repro.models.fl_models import make_fl_model
    from repro.sim.devices import build_fleet

    model = make_fl_model("cnn@mnist", small=True)
    cfg = FLConfig(n_select=20, batch_size=2, probe_size=2, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=4))
    fleet = build_fleet(S, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", S, 0.8, per_client=2, n_test=16)
    methods = {m: METHODS[m] for m in GRID_METHODS}
    seeds = tuple(range(n_seeds))

    def one(batched: bool):
        t0 = time.time()
        grids = run_campaign_grid(model, fleet, cx, cy, cfg, methods,
                                  seeds=seeds, rounds=rounds,
                                  chunk_size=chunk, method_batched=batched)
        wall = time.time() - t0
        compile_total, us_cells = 0.0, []
        for h in grids.values():
            us, comp = _steady_timing(h["chunk_wall_s"], h["chunk_rounds"],
                                      wall, rounds, h["compile_s"])
            us_cells.append(us)
            compile_total += comp or 0.0
        return wall, compile_total, float(np.mean(us_cells))

    wall_b, compile_b, us_b = one(batched=True)
    out = {"S": S, "methods": list(GRID_METHODS), "n_seeds": n_seeds,
           "rounds": rounds, "chunk": chunk,
           "grid_wall_s": wall_b, "compile_s": compile_b,
           "us_per_round": us_b,
           "compile_s_per_cell": compile_b / (len(GRID_METHODS) * n_seeds)}
    if per_method:
        wall_p, compile_p, us_p = one(batched=False)
        out.update({
            "per_method_wall_s": wall_p,
            "per_method_compile_s": compile_p,
            "per_method_us_per_round": us_p,
            "compile_speedup": compile_p / max(compile_b, 1e-9)})
    return out


def measure_phases(S: int = 100, *, rounds: int = 16,
                   chunk: int = 4) -> Dict:
    """Per-phase wall attribution of a short `run_rounds` campaign.

    Installs a `repro.obs.trace.Tracer` and runs with streaming
    telemetry + fleet-health monitors on, then reports each engine
    phase's total seconds from the span summary: XLA compile, warm
    chunk dispatch, the deferred host-history drain, chunk-boundary
    eval, and the final device→host transfer. The health columns
    (flat_battery, staleness_p95) ride along from the HealthReport —
    CI gates `compile_s` of the S=100 row with `--direction lower` and
    keeps the health columns visible in BENCH_engine.json."""
    from repro.core import FLConfig, METHODS, TelemetryCfg, make_eval_fn
    from repro.core.policy import PolicyCfg
    from repro.launch.engine import EngineCfg, run_rounds
    from repro.launch.fl_run import build_task
    from repro.models.fl_models import make_fl_model
    from repro.obs.health import HealthCfg
    from repro.obs.trace import Tracer, tracing
    from repro.sim.devices import build_fleet

    model = make_fl_model("cnn@mnist", small=True)
    cfg = FLConfig(n_select=20, batch_size=2, probe_size=2, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=4))
    fleet = build_fleet(S, seed=0, init_energy_mean=0.3)
    cx, cy, test = build_task("cnn@mnist", S, 0.8, per_client=2, n_test=16)
    eval_fn = make_eval_fn(model, test["x"], test["y"])
    ecfg = EngineCfg(chunk_size=chunk, collect_per_device=False,
                     telemetry=TelemetryCfg(mode="streaming"),
                     health=HealthCfg())
    with tracing(Tracer()) as tracer:
        res = run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                         rounds=rounds, key=jax.random.PRNGKey(1),
                         init_key=jax.random.PRNGKey(0), ecfg=ecfg,
                         eval_fn=eval_fn)
    spans = tracer.summary()
    out = {"S": S, "rounds": rounds, "chunk": chunk}
    for phase in ("compile", "dispatch", "history_drain", "eval",
                  "transfer", "health"):
        s = spans.get(phase)
        out[f"{phase}_s"] = float(s["total_s"]) if s else 0.0
    hm = res.health.metrics if res.health is not None else {}
    out["flat_battery"] = hm.get("flat_battery")
    out["flat_frac"] = hm.get("flat_frac")
    out["staleness_p95"] = hm.get("staleness_p95")
    out["sel_gini"] = hm.get("sel_gini")
    out["health_ok"] = res.health.ok if res.health is not None else None
    return out


STREAMING_SCALE = 100_000
HOST_BYTES_SCALE = 10_000


ASYNC_BUFFER_M = 10  # half of n_select=20 — the default run_fl regime


def _fault_scenario():
    """The fault_round_S* bench scenario: a static-paper twin with the
    chaos layer on (aborts/loss/corruption/stragglers traced, and the
    robust screen auto-enabled), so `fault_overhead` vs the same-scale
    static row isolates the fault+screen cost from dynamics cost."""
    from repro.sim.dynamics import Scenario
    from repro.sim.faults import FaultCfg
    return Scenario(name="fault-bench", static=True,
                    faults=FaultCfg(abort_rate=0.1, loss_rate=0.2,
                                    corrupt_rate=0.05,
                                    straggler_rate=0.2))


def run(scales=SCALES, dynamic_scenario: Optional[str] = DYNAMIC_SCENARIO,
        out_path: str = OUT_PATH, timed_chunks: int = 1,
        grid: bool = True, grid_per_method: bool = True,
        streaming: bool = True, async_rows: bool = True,
        phases: bool = True, fault_rows: bool = True,
        fused_rows: bool = True):
    rows = []
    results: Dict[str, Dict] = {}
    # any scale that serves as the paired baseline of an overhead ratio
    # (dynamic / async / fault rows all divide by the same-scale static
    # row) is measured with the SAME timed_chunks=3 the overhead rows
    # use: best-of-3 vs single-shot would bias every ratio downward on
    # a contended host. Non-paired scales keep the caller's setting.
    paired = set()
    if dynamic_scenario is not None:
        paired.add(max(scales))
    if async_rows:
        paired |= {min(scales), max(scales)}
    if fault_rows:
        paired.add(min(scales))
    for S in scales:
        r = measure_engine(
            S, timed_chunks=3 if S in paired else timed_chunks)
        results[f"scan_round_S{S}"] = r
        rows.append((f"engine/scan_round_S{S}", r["us_per_round"],
                     f"rounds_s={r['rounds_s']:.2f};"
                     f"device_rounds_s={r['device_rounds_s']:.0f};"
                     f"chunk={r['chunk']}"))
    if async_rows:
        # FedBuff buffered aggregation at the smallest and largest
        # scales: async_overhead is the fractional us_per_round cost of
        # the pending-buffer carry + masked land steps vs the same-scale
        # sync row (paired back-to-back like the dynamics ratio)
        for S in {min(scales), max(scales)}:
            r = measure_engine(S, timed_chunks=3, async_m=ASYNC_BUFFER_M)
            results[f"async_round_S{S}"] = r
            overhead = (r["us_per_round"]
                        / results[f"scan_round_S{S}"]["us_per_round"]
                        - 1.0)
            r["async_overhead"] = overhead
            rows.append((f"engine/async_round_S{S}", r["us_per_round"],
                         f"rounds_s={r['rounds_s']:.2f};"
                         f"device_rounds_s={r['device_rounds_s']:.0f};"
                         f"buffer_m={ASYNC_BUFFER_M};"
                         f"async_overhead={overhead:+.3f}"))
    if fault_rows:
        # fault-injection + robust-screen overhead at the smallest
        # scale (the CI-gated row): fault_overhead is the fractional
        # us_per_round cost vs the paired same-scale static row
        S = min(scales)
        r = measure_engine(S, _fault_scenario(), timed_chunks=3)
        results[f"fault_round_S{S}"] = r
        overhead = (r["us_per_round"]
                    / results[f"scan_round_S{S}"]["us_per_round"] - 1.0)
        r["fault_overhead"] = overhead
        rows.append((f"engine/fault_round_S{S}", r["us_per_round"],
                     f"rounds_s={r['rounds_s']:.2f};"
                     f"device_rounds_s={r['device_rounds_s']:.0f};"
                     f"fault_overhead={overhead:+.3f}"))
    if dynamic_scenario is not None:
        S = max(scales)
        static = results[f"scan_round_S{S}"]
        r = measure_engine(S, dynamic_scenario, timed_chunks=3)
        results[f"scan_round_S{S}_{dynamic_scenario}"] = r
        overhead = r["us_per_round"] / static["us_per_round"] - 1.0
        results["dyn_overhead"] = overhead
        rows.append((f"engine/scan_round_S{S}_{dynamic_scenario}",
                     r["us_per_round"],
                     f"rounds_s={r['rounds_s']:.2f};"
                     f"dyn_overhead={overhead:+.3f}"))
    if fused_rows:
        # fused utility→top-K→FedAvg pass vs the XLA reference
        # composition (kernels/rewafl_select). Fixed scales independent
        # of --scales: the S=100k row carries the ISSUE-10 acceptance
        # (speedup_vs_xla ≥ 1.5, CI --min-spec); the S=1M row only runs
        # in full sweeps (it allocates a 256 MB delta stack)
        fused_scales = (10_000, 100_000) + (
            (1_000_000,) if 10_000 in scales else ())
        for S in fused_scales:
            r = measure_fused_select(S)
            results[f"fused_select_S{S}"] = r
            rows.append((f"engine/fused_select_S{S}",
                         r["us_fused"],
                         f"us_xla={r['us_xla']:.0f};"
                         f"device_rounds_s={r['device_rounds_s']:.0f};"
                         f"speedup_vs_xla={r['speedup_vs_xla']:.2f}x"))
    if grid:
        g = measure_campaign_grid(per_method=grid_per_method)
        results["campaign_grid_4x5"] = g
        derived = (f"grid_wall_s={g['grid_wall_s']:.1f};"
                   f"compile_s={g['compile_s']:.1f}")
        if grid_per_method:
            derived += (f";per_method_compile_s="
                        f"{g['per_method_compile_s']:.1f};"
                        f"compile_speedup={g['compile_speedup']:.1f}x")
        rows.append(("engine/campaign_grid_4x5", g["us_per_round"],
                     derived))
        if grid_per_method:
            cells = len(g["methods"]) * g["n_seeds"]
            log.info(
                f"# compile amortization ({len(g['methods'])} methods x "
                f"{g['n_seeds']} seeds = {cells} cells): "
                f"batched {g['compile_s']:.1f}s total "
                f"({g['compile_s_per_cell']:.2f}s/cell) vs per-method "
                f"{g['per_method_compile_s']:.1f}s "
                f"({g['per_method_compile_s'] / cells:.2f}s/cell) -> "
                f"{g['compile_speedup']:.1f}x")
    if phases:
        # per-phase wall attribution (repro.obs spans) at the smallest
        # scale always — the CI compile_s gate — and at S=10k when the
        # full scale sweep runs
        phase_scales = {min(scales)} | ({10_000} if 10_000 in scales
                                        else set())
        for S in sorted(phase_scales):
            p = measure_phases(S)
            results[f"engine_phases_S{S}"] = p
            rows.append((f"engine/engine_phases_S{S}",
                         p["dispatch_s"] * 1e6 / max(p["rounds"], 1),
                         f"compile_s={p['compile_s']:.2f};"
                         f"dispatch_s={p['dispatch_s']:.2f};"
                         f"drain_s={p['history_drain_s']:.3f};"
                         f"eval_s={p['eval_s']:.2f};"
                         f"transfer_s={p['transfer_s']:.3f};"
                         f"flat_battery={p['flat_battery']};"
                         f"staleness_p95={p['staleness_p95']}"))
    if streaming:
        # per-device telemetry at a fleet scale where dense (R, S)
        # collection would OOM/thrash the host: the S=100k row runs the
        # DEFAULT_SPECS reducers in the scan carry (O(S) state)
        r = measure_engine(STREAMING_SCALE, chunk=1, timed_chunks=1,
                           streaming=True)
        results[f"scan_round_S{STREAMING_SCALE}_streaming"] = r
        rows.append((f"engine/scan_round_S{STREAMING_SCALE}_streaming",
                     r["us_per_round"],
                     f"rounds_s={r['rounds_s']:.3f};"
                     f"device_rounds_s={r['device_rounds_s']:.0f};"
                     f"telemetry=streaming"))
        hb = measure_host_bytes(S=HOST_BYTES_SCALE)
        results[f"telemetry_host_bytes_S{HOST_BYTES_SCALE}"] = hb
        log.info(f"# host history bytes at S={HOST_BYTES_SCALE}, "
                 f"R={hb['rounds']}: dense {hb['dense_bytes']:,} vs "
                 f"streaming {hb['streaming_bytes']:,} "
                 f"(projected S=1M R=500: dense "
                 f"{hb['projected_dense_gb_S1M_R500']:.1f} GB vs streaming "
                 f"{hb['projected_streaming_gb_S1M_R500']:.2f} GB)")
    payload = {"bench": "engine", "backend": jax.default_backend(),
               "jax_version": jax.__version__,
               "results": results}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    emit(rows)
    log.info(f"# wrote {out_path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", default=None,
                    help="comma-separated fleet sizes (default 100,1000,10000)")
    ap.add_argument("--no-dynamic", action="store_true",
                    help="skip the dynamic-scenario overhead row")
    ap.add_argument("--no-grid", action="store_true",
                    help="skip the method-batched campaign-grid row "
                         "(the CI bench-gate measures S=100 only)")
    ap.add_argument("--grid-no-per-method", action="store_true",
                    help="grid row measures only the method-batched path "
                         "(grid_wall_s/compile_s) — what the CI gate "
                         "compares with --direction lower; skips the "
                         "expensive per-method fallback baseline")
    ap.add_argument("--no-streaming", action="store_true",
                    help="skip the S=100k streaming-telemetry row and "
                         "the dense-vs-streaming host-bytes comparison")
    ap.add_argument("--no-async", action="store_true",
                    help="skip the FedBuff async-aggregation rows "
                         "(async_round_S*)")
    ap.add_argument("--no-phases", action="store_true",
                    help="skip the span-traced per-phase attribution "
                         "rows (engine_phases_S*)")
    ap.add_argument("--no-fault", action="store_true",
                    help="skip the fault-injection overhead row "
                         "(fault_round_S<min scale>)")
    ap.add_argument("--no-fused", action="store_true",
                    help="skip the fused selection-pass rows "
                         "(fused_select_S*)")
    ap.add_argument("--out", default=OUT_PATH,
                    help="output JSON path (default BENCH_engine.json)")
    ap.add_argument("--timed-chunks", type=int, default=3,
                    help="warm chunks per scale; the best one is "
                         "reported (timeit-style), damping contention "
                         "noise on shared hosts")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress progress chatter (the CSV rows and "
                         "warnings still print)")
    ap.add_argument("-v", "--verbose", action="count", default=0,
                    help="debug-level logging")
    args = ap.parse_args()
    configure_logging(verbosity=args.verbose, quiet=args.quiet)
    compile_cache.configure()
    scales = (tuple(int(s) for s in args.scales.split(","))
              if args.scales else SCALES)
    run(scales=scales,
        dynamic_scenario=None if args.no_dynamic else DYNAMIC_SCENARIO,
        out_path=args.out, timed_chunks=args.timed_chunks,
        grid=not args.no_grid,
        grid_per_method=not args.grid_no_per_method,
        streaming=not args.no_streaming,
        async_rows=not args.no_async,
        phases=not args.no_phases,
        fault_rows=not args.no_fault,
        fused_rows=not args.no_fused)


if __name__ == "__main__":
    main()
