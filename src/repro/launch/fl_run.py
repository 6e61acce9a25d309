"""End-to-end REWAFL federated-training driver (paper Secs. IV–V).

Builds the synthetic task, the 100-device fleet, and runs FL rounds under
a chosen PS method until target accuracy or a round budget. Returns the
full metric history used by the paper-table benchmarks (DR/OL/OEC, H
dynamics, per-device selections/energy).

CLI:  PYTHONPATH=src python -m repro.launch.fl_run \
          --task cnn@mnist --method rewafl --rounds 100

Observability (repro.obs): `--trace out.trace.json` records host spans
per engine phase (compile / dispatch / history drain / eval / transfer)
as Perfetto-loadable Chrome trace JSON; `--health` samples fleet-health
monitors (flat batteries, near-depletion, selection Gini, staleness
tails) at chunk boundaries and `--health-strict` turns a tripped
threshold into exit code 3. Progress chatter goes through the `repro`
logger (`--quiet` / `-v`); the final JSON blob stays on stdout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (METHODS, FLConfig, init_fleet_state, make_eval_fn,
                        make_round_fn)
from repro.data.partition import client_datasets
from repro.data.synthetic import (make_char_dataset, make_har_dataset,
                                  make_image_dataset)
from repro.launch import compile_cache
from repro.models.fl_models import make_fl_model
from repro.obs.health import HealthCfg, HealthReport, format_health_table
from repro.obs.log import configure_logging, get_logger
from repro.obs.trace import Tracer, format_span_table, tracing
from repro.sim.devices import build_fleet
from repro.sim.dynamics import SCENARIOS, get_scenario, init_env_state

log = get_logger(__name__)


@dataclasses.dataclass
class RunResult:
    task: str
    method: str
    rounds_run: int
    reached_round: Optional[int]       # first round hitting target acc
    target_acc: float
    history: Dict[str, np.ndarray]     # per-round metric arrays
    final_state: object
    overall_latency_s: float           # Σ round latency up to target (or end)
    overall_energy_j: float
    dropout_ratio: float               # dropped / fleet at stop point
    acc_curve: np.ndarray
    final_params: object = None        # trained global model pytree
    # scan engine only: per-chunk wall clock (first entry includes JIT
    # compile) + rounds per chunk, for steady-state throughput reporting,
    # and the directly-measured jit trace+compile seconds
    chunk_wall_s: Optional[np.ndarray] = None
    chunk_rounds: Optional[np.ndarray] = None
    compile_s: Optional[float] = None
    # streaming telemetry only: finalized per-device reducer outputs
    # (`tel/<metric>/<reducer>` -> (S,) aggregates; see core.metrics)
    telemetry: Optional[Dict[str, np.ndarray]] = None
    # async aggregation only: final virtual wall clock (s) — the
    # simulated time at which the last buffered aggregation landed.
    # Sync campaigns report Σ round_latency as overall_latency_s
    # instead (barrier semantics).
    wall_clock_s: Optional[float] = None
    # fleet-health verdict (repro.obs.health), set when run_fl(health=
    # HealthCfg(...)) / `--health`: chunk-boundary flat-battery samples,
    # selection Gini, staleness / residual-energy tails
    health: Optional[HealthReport] = None
    # span aggregates ({name: {count, total_s, mean_s, max_s}}) when
    # run_fl(trace=...) recorded the campaign's engine phases
    spans: Optional[Dict[str, Dict[str, float]]] = None
    # checkpoint/resume only (scan engine): sha256 fingerprint of the
    # final carry (params, FleetState, EnvState, AsyncState when async)
    # — the bitwise resume-equivalence token the CI chaos-smoke gate
    # compares between an interrupted+resumed run and an uninterrupted
    # one — and the round this process actually started from
    carry_sha: Optional[str] = None
    start_round: int = 0


def build_task(task: str, n_clients: int, lam: float, *, per_client: int = 128,
               n_test: int = 512, seed: int = 0):
    if task in ("cnn@mnist", "cnn@cifar10"):
        kind = task.split("@")[1]
        x, y = make_image_dataset(kind, n_clients * per_client + n_test,
                                  seed=seed)
        n_classes = 10
    elif task == "cnn@har":
        x, y = make_har_dataset(n_clients * per_client + n_test, seed=seed)
        n_classes = 6
    elif task == "lstm@shakespeare":
        seqs, _ = make_char_dataset(n_clients + 4, per_role=per_client,
                                    seed=seed)
        cx = seqs[:n_clients]
        cy = np.zeros(cx.shape[:2], np.int32)  # unused by the LM loss
        tx = seqs[n_clients:].reshape(-1, seqs.shape[-1])[:n_test]
        ty = np.zeros((tx.shape[0],), np.int32)
        return (jnp.asarray(cx), jnp.asarray(cy),
                {"x": jnp.asarray(tx), "y": jnp.asarray(ty)})
    else:
        raise ValueError(task)
    tx, ty = x[-n_test:], y[-n_test:]
    cx, cy = client_datasets(x[:-n_test], y[:-n_test], n_clients, lam,
                             per_client, n_classes, seed=seed)
    return (jnp.asarray(cx), jnp.asarray(cy),
            {"x": jnp.asarray(tx), "y": jnp.asarray(ty)})


def build_task_batch(task: str, seeds, n_clients: int, lam: float, *,
                     per_client: int = 128, n_test: int = 512):
    """Per-seed stacked client data for vmapped campaign batches
    (`engine.run_campaign_batch(per_seed_fleets=True)`): seed s rebuilds
    the dataset and λ-partition exactly like `run_fl(seed=s)` does via
    `build_task(..., seed=s)`.

    Returns (cx, cy, test): cx (B, S, n, ...), cy (B, S, n) and the
    per-seed test sets test = {"x": (B, n_test, ...), "y": (B, n_test)},
    B = len(seeds)."""
    outs = [build_task(task, n_clients, lam, per_client=per_client,
                       n_test=n_test, seed=s) for s in seeds]
    cx = jnp.stack([o[0] for o in outs])
    cy = jnp.stack([o[1] for o in outs])
    test = {k: jnp.stack([o[2][k] for o in outs]) for k in outs[0][2]}
    return cx, cy, test


def quick_cfg(n_select: int = 20, alpha: float = 1.0,
              beta: float = 1.0) -> FLConfig:
    """Single-CPU-core benchmark scale: same algorithm, smaller loops."""
    from repro.core.policy import PolicyCfg
    return FLConfig(n_select=n_select, alpha=alpha, beta=beta,
                    batch_size=16, probe_size=16, lr=0.05,
                    uplink_bits=40e6,
                    policy=PolicyCfg(H0=5, H_max=16, dH=1.5))


# run_fl's fleet: the paper's low-initial-battery regime (Fig. 1 / Fig. 4
# use 6–30 kJ initial energies, not full batteries)
FLEET_KWARGS = {"init_energy_mean": 0.11, "init_energy_std": 0.04,
                "e0_frac": 0.08}

HIST_KEYS = ("round_latency", "round_energy", "n_dropped",
             "n_participating", "n_failed", "mean_H_selected", "local_iters",
             "global_loss", "n_available", "n_charging", "n_online")

# extra per-round scalars the async round body emits (core.async_agg)
ASYNC_HIST_KEYS = ("wall_clock", "server_version", "n_pending",
                   "n_aggregations", "n_landed", "mean_update_staleness")

# chaos/resilience counters (sim.faults / core.resilience) — present in
# the engine history only for the gates the run actually traced (fault
# scenario, deadline, screen, async TTL), so they are copied through
# opportunistically rather than listed in HIST_KEYS
FAULT_HIST_KEYS = ("n_aborted", "n_lost", "n_corrupted", "n_straggler",
                   "n_deadline_cut", "n_rejected", "n_retried", "n_expired")


def run_fl(task: str = "cnn@mnist", method: str = "rewafl", *,
           rounds: int = 100, n_clients: int = 100, n_select: int = 20,
           lam: float = 0.8, target_acc: float = 0.95,
           alpha: float = 1.0, beta: float = 1.0,
           seed: int = 0, per_client: int = 64, small: bool = True,
           fl_cfg: Optional[FLConfig] = None, fleet_kwargs: Optional[dict] = None,
           eval_every: int = 5, verbose: bool = False,
           engine: str = "scan", chunk_size: int = 8,
           fleet_shards: Optional[int] = None,
           scenario: str = "static-paper",
           probe_every: int = 1,
           telemetry: str = "dense",
           aggregation: str = "sync",
           buffer_m: Optional[int] = None,
           staleness_power: float = 0.5,
           delay_jitter: float = 0.0,
           async_delay: str = "wall",
           trace: Optional[str] = None,
           health: Optional[HealthCfg] = None,
           checkpoint_every: Optional[int] = None,
           checkpoint_dir: Optional[str] = None,
           resume: Optional[str] = None,
           kernel_backend: str = "auto") -> RunResult:
    """Run one FL campaign.

    engine="scan" (default) runs rounds in compiled `lax.scan` chunks via
    `launch.engine` — accuracy (and hence the early-stop check) happens at
    chunk boundaries, so the chunk length is clamped to `eval_every`:
    evaluation is never coarser than the caller asked for. engine="loop"
    is the legacy one-dispatch-per-round driver evaluating every
    `eval_every` rounds; both fold PRNG keys identically, so they agree
    to float tolerance round-for-round.

    `scenario` names a `sim.dynamics` fleet-dynamics preset (see
    `SCENARIOS`): "static-paper" (default) is the seed simulator
    bit-for-bit; dynamic presets (commuter-diurnal, congested-urban,
    overnight-charging, churn-heavy) evolve wireless environments,
    charging batteries, and availability between rounds.

    `probe_every=N` re-probes the global model every N rounds instead of
    every round, carrying `FleetState.g_loss` between probes (1 = exact
    paper semantics; see `FLConfig.probe_every`).

    `telemetry="dense"` (default) keeps the per-device history as dense
    (R, S) host arrays (`sel_count`/`H_trace` derived from them, exact
    paper semantics). `telemetry="streaming"` (scan engine only) folds
    `core.metrics.DEFAULT_SPECS` reducers on device instead: history
    drops the O(R·S) `H_trace`, `sel_count` comes from the `selected`
    count reducer, and the per-device aggregates land in
    `RunResult.telemetry` — O(S) host memory however long the campaign.

    `aggregation="async"` (scan engine only) switches to FedBuff-style
    buffered aggregation (`core.async_agg`): selected devices snapshot
    the global params at dispatch, their updates land on a virtual
    clock after their wireless/compute delay (`async_delay="wall"`) or
    one clock unit (`"unit"`), and the server aggregates
    staleness-weighted once `buffer_m` updates arrive (default
    max(1, n_select // 2)). History gains the `ASYNC_HIST_KEYS`
    per-round scalars and `RunResult.wall_clock_s` reports the final
    virtual time — the wall-clock axis of the sync-vs-async
    wall-clock-to-accuracy comparison
    (benchmarks/table5_async_wallclock.py). With `buffer_m=n_select`
    and no jitter the run reproduces the sync history bitwise.

    `trace="out.trace.json"` installs a `repro.obs.trace.Tracer` for the
    campaign, writes the engine-phase spans as Chrome trace-event JSON
    (Perfetto-loadable) and attaches the per-phase aggregates to
    `RunResult.spans`. Tracing is host-side only — the compiled round
    math and the golden history are bitwise-unchanged.

    `health=HealthCfg(...)` (scan engine only) samples the fleet-health
    monitors at every chunk boundary (flat-battery / near-depletion
    counts; selection Gini and staleness / residual-energy tails at the
    end), logs threshold violations as WARNINGs and attaches the
    `HealthReport` to `RunResult.health`.

    `kernel_backend` pins the selection/aggregation lowering
    (`FLConfig.kernel_backend`, see docs/kernels.md): "xla" is the
    reference composition (golden-bitwise), "pallas" the fused
    utility→top-K→FedAvg pass (`kernels/rewafl_select`), "auto"
    (default) resolves to pallas on TPU and xla elsewhere — so CPU runs
    stay bitwise-golden without asking.

    `checkpoint_every=N` (scan engine only) serializes the FULL scan
    carry to `checkpoint_dir/ckpt_r{round:08d}.npz` (+ sha256 sidecar)
    every N completed rounds; `resume=PATH` (file or directory —
    directories resume from the newest intact checkpoint) continues a
    crashed run bitwise from that boundary
    (`launch.engine.EngineCfg` / `training.checkpoint`). When either is
    set, `RunResult.carry_sha` fingerprints the final carry for the
    resume-equivalence gate.
    """
    if trace is not None:
        kw = dict(locals())
        kw.pop("trace")
        with tracing(Tracer()) as tracer:
            with tracer.span("run_fl", task=task, method=method):
                res = run_fl(trace=None, **kw)
        tracer.write(trace)
        res.spans = tracer.summary()
        return res
    model = make_fl_model(task, small=small)
    scen = get_scenario(scenario)
    fleet = build_fleet(n_clients, seed=seed,
                        **(FLEET_KWARGS | (fleet_kwargs or {})))
    cx, cy, test = build_task(task, n_clients, lam, per_client=per_client,
                              seed=seed)
    cfg = fl_cfg or (quick_cfg(n_select, alpha, beta) if small else
                     FLConfig(n_select=n_select, alpha=alpha, beta=beta))
    if probe_every != 1:
        cfg = dataclasses.replace(cfg, probe_every=probe_every)
    if kernel_backend != cfg.kernel_backend:
        cfg = dataclasses.replace(cfg, kernel_backend=kernel_backend)
    spec = METHODS[method]
    if task == "lstm@shakespeare":
        eval_fn = jax.jit(lambda p: model.accuracy(p, test))
    else:
        eval_fn = make_eval_fn(model, test["x"], test["y"])

    if telemetry not in ("dense", "streaming"):
        raise ValueError(f"unknown telemetry {telemetry!r} "
                         "(use 'dense' or 'streaming')")
    if aggregation not in ("sync", "async"):
        raise ValueError(f"unknown aggregation {aggregation!r} "
                         "(use 'sync' or 'async')")
    async_mode = aggregation == "async"
    if async_mode and engine != "scan":
        raise ValueError("aggregation='async' needs engine='scan' — the "
                         "legacy loop driver has no buffer carry")
    if health is not None and engine != "scan":
        raise ValueError("health monitoring needs engine='scan' — the "
                         "legacy loop driver has no chunk boundaries to "
                         "sample at")
    ckpt_mode = (checkpoint_every is not None or resume is not None)
    if ckpt_mode and engine != "scan":
        raise ValueError("checkpoint/resume needs engine='scan' — the "
                         "carry is serialized at chunk boundaries")
    if engine == "scan":
        from repro.core.async_agg import AsyncCfg
        from repro.core.metrics import ASYNC_SPECS, TelemetryCfg
        from repro.launch.engine import EngineCfg, run_rounds
        streaming = telemetry == "streaming"
        acfg = None
        if async_mode:
            acfg = AsyncCfg(
                buffer_m=(buffer_m if buffer_m is not None
                          else max(1, cfg.n_select // 2)),
                delay=async_delay, delay_jitter=delay_jitter,
                staleness_power=staleness_power)
        tcfg = TelemetryCfg(mode=telemetry,
                            specs=ASYNC_SPECS) if (streaming and async_mode
                                                   ) else TelemetryCfg(
                                                       mode=telemetry)
        # honor the caller's eval cadence: chunks never span more than
        # eval_every rounds, so early-stop granularity is preserved
        chunk_size = max(1, min(chunk_size, eval_every))
        res = run_rounds(
            model, fleet, cx, cy, cfg, spec, rounds=rounds,
            key=jax.random.PRNGKey(seed + 1),
            params=model.init(jax.random.PRNGKey(seed + 2)),
            ecfg=EngineCfg(chunk_size=chunk_size, fleet_shards=fleet_shards,
                           collect_per_device=not streaming,
                           telemetry=tcfg, async_cfg=acfg, health=health,
                           checkpoint_every=checkpoint_every,
                           checkpoint_dir=checkpoint_dir, resume=resume),
            eval_fn=eval_fn, target_acc=target_acc,
            scenario=scen, env_key=jax.random.PRNGKey(seed + 3))
        h = res.history
        state, params = res.state, res.params
        carry_sha = None
        if ckpt_mode:
            from repro.training.checkpoint import tree_digest
            carry = {"params": params, "state": state, "env": res.env}
            if res.async_state is not None:
                carry["astate"] = res.async_state
            carry_sha = tree_digest(carry)
        if verbose:
            for i, acc in enumerate(res.acc_curve):
                r_end = min((i + 1) * chunk_size, res.rounds_run) - 1
                log.info(f"r={r_end:4d} acc={acc:.4f} "
                         f"loss={h['global_loss'][r_end]:.4f} "
                         f"drop={int(h['n_dropped'][r_end])}")
        if streaming:  # per-device traces live in the O(S) reducers
            per_dev = {
                "sel_count": np.asarray(
                    res.telemetry["tel/selected/count"], np.int64),
            }
        else:
            per_dev = {
                "sel_count": np.asarray(h["selected"]).sum(0).astype(
                    np.int64),
                "H_trace": np.asarray(h["H"]),
            }
        hist_keys = HIST_KEYS + (ASYNC_HIST_KEYS if async_mode else ())
        return RunResult(
            task=task, method=method, rounds_run=res.rounds_run,
            reached_round=res.reached_round, target_acc=target_acc,
            history={k: np.asarray(h[k], np.float64) for k in hist_keys}
            | {k: np.asarray(h[k], np.float64) for k in FAULT_HIST_KEYS
               if k in h}
            | per_dev | {
                "residual_energy": np.asarray(state.residual_energy),
                "init_energy": np.asarray(fleet.init_energy),
                "type_id": np.asarray(fleet.type_id),
                "rate_mean": np.asarray(fleet.rate_mean),
            },
            final_state=state,
            overall_latency_s=float(np.sum(h["round_latency"])),
            overall_energy_j=float(np.sum(h["round_energy"])),
            dropout_ratio=(float(h["n_dropped"][-1]) / n_clients
                           if res.rounds_run else 0.0),
            acc_curve=res.acc_curve, final_params=params,
            chunk_wall_s=res.chunk_wall_s, chunk_rounds=res.chunk_rounds,
            compile_s=res.compile_s, telemetry=res.telemetry,
            wall_clock_s=(float(h["wall_clock"][-1])
                          if async_mode and res.rounds_run else None),
            health=res.health, carry_sha=carry_sha,
            start_round=res.start_round)
    if engine != "loop":
        raise ValueError(f"unknown engine {engine!r} (use 'scan' or 'loop')")
    if telemetry != "dense":
        raise ValueError("telemetry='streaming' needs engine='scan' — the "
                         "legacy loop driver has no on-device reducers")

    round_fn = make_round_fn(model, fleet, cx, cy, cfg, spec, scen)
    key = jax.random.PRNGKey(seed + 1)
    params = model.init(jax.random.PRNGKey(seed + 2))
    state = init_fleet_state(fleet, H0=cfg.policy.H0)
    env = init_env_state(fleet, scen,
                         key=jax.random.PRNGKey(seed + 3)
                         if scen.dynamic else None)

    hist: Dict[str, List] = {k: [] for k in HIST_KEYS}
    sel_count = np.zeros(n_clients, np.int64)
    H_trace: List[np.ndarray] = []
    acc_curve: List[float] = []
    reached = None
    cum_lat = cum_energy = 0.0
    stop_lat = stop_energy = None
    stop_drop = None
    r = -1  # rounds=0: loop never runs, rounds_run must come out 0

    for r in range(rounds):
        key, kr = jax.random.split(key)
        params, state, env, m = round_fn(params, state, env, kr,
                                         jnp.asarray(r, jnp.int32))
        for k in hist:
            hist[k].append(float(m[k]))
        sel_count += np.asarray(m["selected"])
        H_trace.append(np.asarray(state.H))
        cum_lat += float(m["round_latency"])
        cum_energy += float(m["round_energy"])
        if r % eval_every == 0 or r == rounds - 1:
            acc = float(eval_fn(params))
            acc_curve.append(acc)
            if verbose:
                log.info(f"r={r:4d} acc={acc:.4f} "
                         f"loss={m['global_loss']:.4f} "
                         f"drop={int(m['n_dropped'])} "
                         f"H={float(m['mean_H_selected']):.1f} "
                         f"lat={cum_lat/3600:.3f}h e={cum_energy/1e3:.1f}kJ")
            if reached is None and acc >= target_acc:
                reached = r
                stop_lat, stop_energy = cum_lat, cum_energy
                stop_drop = float(m["n_dropped"]) / n_clients
                break
    if stop_lat is None:
        stop_lat, stop_energy = cum_lat, cum_energy
        stop_drop = (hist["n_dropped"][-1] / n_clients
                     if hist["n_dropped"] else 0.0)
    return RunResult(
        task=task, method=method, rounds_run=r + 1, reached_round=reached,
        target_acc=target_acc,
        history={k: np.asarray(v) for k, v in hist.items()} | {
            "sel_count": sel_count, "H_trace": np.asarray(H_trace),
            "residual_energy": np.asarray(state.residual_energy),
            "init_energy": np.asarray(fleet.init_energy),
            "type_id": np.asarray(fleet.type_id),
            "rate_mean": np.asarray(fleet.rate_mean),
        },
        final_state=state, overall_latency_s=stop_lat,
        overall_energy_j=stop_energy, dropout_ratio=stop_drop,
        acc_curve=np.asarray(acc_curve), final_params=params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="cnn@mnist")
    ap.add_argument("--method", default="rewafl", choices=sorted(METHODS))
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--select", type=int, default=20)
    ap.add_argument("--lam", type=float, default=0.8)
    ap.add_argument("--target-acc", type=float, default=0.9)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--beta", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="scan", choices=("scan", "loop"))
    ap.add_argument("--kernel-backend", default="auto",
                    choices=("xla", "pallas", "auto"),
                    help="selection/aggregation lowering "
                         "(FLConfig.kernel_backend): xla = reference "
                         "composition (golden-bitwise), pallas = fused "
                         "utility→top-K→FedAvg pass, auto = pallas on "
                         "TPU else xla (docs/kernels.md)")
    ap.add_argument("--chunk-size", type=int, default=8)
    ap.add_argument("--fleet-shards", type=int, default=None)
    ap.add_argument("--scenario", default="static-paper",
                    choices=sorted(SCENARIOS))
    ap.add_argument("--probe-every", type=int, default=1,
                    help="re-probe the global model every N rounds "
                         "(1 = every round, the paper's exact semantics)")
    ap.add_argument("--telemetry", default="dense",
                    choices=("dense", "streaming"),
                    help="per-device history: 'dense' keeps (R, S) host "
                         "buffers; 'streaming' folds O(S) on-device "
                         "reducers instead (mega-fleet safe)")
    ap.add_argument("--aggregation", default="sync",
                    choices=("sync", "async"),
                    help="'sync' is the FedAvg round barrier; 'async' is "
                         "FedBuff-style buffered aggregation on a virtual "
                         "wall clock (scan engine only)")
    ap.add_argument("--buffer-m", type=int, default=None,
                    help="async: aggregate once M updates are buffered "
                         "(default n_select // 2)")
    ap.add_argument("--staleness-power", type=float, default=0.5,
                    help="async: staleness damping a in (1+stale)^-a")
    ap.add_argument("--delay-jitter", type=float, default=0.0,
                    help="async: lognormal sigma multiplying each "
                         "update's delay (0 = deterministic delays)")
    ap.add_argument("--async-delay", default="wall",
                    choices=("wall", "unit"),
                    help="async delay model: 'wall' uses each device's "
                         "simulated compute+uplink seconds, 'unit' lands "
                         "every update one clock tick after dispatch")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    metavar="N",
                    help="serialize the full scan carry every N completed "
                         "rounds (needs --checkpoint-dir; scan engine "
                         "only)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="directory for ckpt_r*.npz checkpoints (+ sha256 "
                         "sidecars)")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume bitwise from a checkpoint file, or from "
                         "the newest intact checkpoint in a directory "
                         "(corrupt files are skipped)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record engine-phase host spans to PATH as "
                         "Chrome trace-event JSON (open in "
                         "ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--health", action="store_true",
                    help="sample fleet-health monitors (flat batteries, "
                         "near-depletion, selection Gini, staleness "
                         "tails) at chunk boundaries; scan engine only")
    ap.add_argument("--health-strict", action="store_true",
                    help="imply --health and exit 3 when any health "
                         "threshold tripped (CI gate)")
    ap.add_argument("--max-flat-frac", type=float, default=0.10,
                    help="health: max tolerated fraction of the fleet "
                         "at/below the depletion floor")
    ap.add_argument("--max-near-frac", type=float, default=0.50,
                    help="health: max tolerated fraction of the fleet "
                         "within 50%% of the depletion floor (raise for "
                         "fleets that START in the low-battery regime, "
                         "like the benchmark default)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress progress chatter (warnings and the "
                         "final JSON blob still print)")
    ap.add_argument("-v", "--verbose", action="count", default=0,
                    help="debug-level logging")
    args = ap.parse_args()
    configure_logging(verbosity=args.verbose, quiet=args.quiet)
    compile_cache.configure()
    hcfg = (HealthCfg(max_flat_frac=args.max_flat_frac,
                      max_near_frac=args.max_near_frac)
            if args.health or args.health_strict else None)
    t0 = time.time()
    res = run_fl(args.task, args.method, rounds=args.rounds,
                 n_clients=args.clients, n_select=args.select, lam=args.lam,
                 target_acc=args.target_acc, alpha=args.alpha,
                 beta=args.beta, seed=args.seed, verbose=not args.quiet,
                 engine=args.engine, chunk_size=args.chunk_size,
                 fleet_shards=args.fleet_shards, scenario=args.scenario,
                 probe_every=args.probe_every, telemetry=args.telemetry,
                 aggregation=args.aggregation, buffer_m=args.buffer_m,
                 staleness_power=args.staleness_power,
                 delay_jitter=args.delay_jitter,
                 async_delay=args.async_delay,
                 trace=args.trace, health=hcfg,
                 checkpoint_every=args.checkpoint_every,
                 checkpoint_dir=args.checkpoint_dir, resume=args.resume,
                 kernel_backend=args.kernel_backend)
    if res.spans is not None:
        log.info("%s", format_span_table(res.spans))
        log.info("trace written to %s", args.trace)
    if res.health is not None:
        log.info("%s", format_health_table(res.health))
    print(json.dumps({  # noqa: bare-print — stdout JSON is the machine contract
        "task": res.task, "method": res.method,
        "scenario": args.scenario, "telemetry": args.telemetry,
        "aggregation": args.aggregation,
        "rounds": res.rounds_run, "reached_round": res.reached_round,
        "dropout_ratio": res.dropout_ratio,
        "overall_latency_h": res.overall_latency_s / 3600,
        "overall_energy_kj": res.overall_energy_j / 1e3,
        "wall_clock_s": res.wall_clock_s,
        "final_acc": (float(res.acc_curve[-1]) if len(res.acc_curve)
                      else None),
        "health_ok": res.health.ok if res.health is not None else None,
        "fault_totals": {k: float(np.sum(res.history[k]))
                         for k in ("n_aborted", "n_lost", "n_corrupted",
                                   "n_straggler", "n_deadline_cut",
                                   "n_rejected", "n_retried", "n_expired")
                         if k in res.history},
        "carry_sha": res.carry_sha, "start_round": res.start_round,
        "wall_s": round(time.time() - t0, 1),
    }, indent=1))
    if args.health_strict and res.health is not None and not res.health.ok:
        sys.exit(3)


if __name__ == "__main__":
    main()
