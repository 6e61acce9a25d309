"""Scan-compiled multi-round FL engine with sharded mega-fleets.

The seed driver (`launch/fl_run.py`) dispatched one jitted round per
Python-loop iteration — at benchmark scale the host round-trip and
dispatch overhead dominate the actual device work. This module lifts the
round into `jax.lax.scan` chunks so R rounds run as a single device
program with on-device metric accumulation, and makes the fleet axis `S`
shardable so 10k–100k-device fleets spread across available devices.

The round body is closure-free (`core.round.make_round_body`): the fleet
and client data enter every chunk as explicit pytree *arguments*, never
as trace-time constants. That is what lets the campaign layer vmap over
per-seed fleets/partitions (real fleet-heterogeneity error bars) and the
sharding layer place them as argument shardings.

Layers (each usable on its own):

  make_chunk_fn   — jit(scan(round_body, length=chunk)) with a
                    (params, FleetState, EnvState, key) carry and
                    (fleet, cx, cy) as loop-invariant arguments; the key
                    folds exactly like the sequential loop
                    (`key, kr = split(key)` per round), so engine ≡ loop
                    to float tolerance. EnvState carries the fleet
                    dynamics (sim.dynamics: Markov channels, charging,
                    churn) selected by a `Scenario`.
  EngineCfg/run_rounds
                  — chunked driver: runs chunks back-to-back with the
                    carry donated between chunks, streams each chunk's
                    history to preallocated host buffers *while the next
                    chunk runs*, and early-stops on target accuracy at
                    chunk boundaries. `EngineCfg(telemetry=
                    TelemetryCfg(mode="streaming"))` swaps dense (R, S)
                    per-device history for on-device metric reducers
                    folded in the scan carry (core.metrics): O(S)
                    telemetry state however long the campaign, drained
                    once into EngineResult.telemetry — what makes
                    per-device telemetry feasible at mega-fleet S.
  shard_over_fleet— place every array whose leading axis is S on a 1-D
                    "fleet" mesh (jax.sharding.NamedSharding); selection
                    top-k and the K-slot gathers stay global ops and are
                    partitioned by GSPMD.
  run_campaign_batch
                  — vmap independent campaigns (one per seed) through
                    the same chunk body for the benchmark grids. With
                    `per_seed_fleets=True` the fleet/data pytrees carry a
                    leading seed axis and every seed runs its own fleet
                    draw and λ-partition.
  run_campaign_grid
                  — (method × seed) grids. Batchable methods lower to a
                    `MethodParams` pytree (`core.methods`) and the whole
                    grid runs as ONE compiled program: the traced round
                    body (`make_round_body_mp`, lax.switch dispatch) is
                    vmapped over the seed axis and then over the method
                    axis — one trace, one XLA compile, M·B campaigns.
                    Structurally incompatible methods fall back to
                    per-method compilation (`run_campaign_batch`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.async_agg import AsyncCfg
from repro.core.methods import (MethodSpec, batchable, method_params_batch)
from repro.core.metrics import (DENSE_PER_DEVICE, PER_DEVICE_METRICS,
                                TelemetryCfg, finalize_telemetry,
                                init_telemetry, update_telemetry)
from repro.core.round import (FLConfig, make_async_round_body,
                              make_async_round_body_mp, make_round_body,
                              make_round_body_mp)
from repro.core.state import (AsyncState, FleetState, init_async_state,
                              init_fleet_state, replicate_state)
from repro.launch.mesh import make_fleet_mesh
from repro.models.fl_models import FLModel
from repro.obs.health import (HealthCfg, HealthReport, chunk_sample,
                              finalize_report, with_health_specs)
from repro.obs.log import get_logger
from repro.obs.trace import span, until_ready
from repro.sim.devices import DeviceFleet
from repro.sim.dynamics import EnvState, Scenario, init_env_state
from repro.training import checkpoint as ckpt

log = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class EngineCfg:
    chunk_size: int = 8          # rounds per compiled scan chunk
    collect_per_device: bool = True   # keep (R, S) traces (selected, H)
    fleet_shards: Optional[int] = None  # shard S over this many devices
    # telemetry regime (core.metrics.TelemetryCfg): "dense" keeps the
    # legacy (R, S) per-device host history; "streaming" folds the
    # declared MetricSpec reducers in the scan carry instead — O(S)
    # reducer state per metric, drained once into
    # EngineResult.telemetry, unblocking mega-fleet campaigns whose
    # dense history would OOM the host
    telemetry: TelemetryCfg = TelemetryCfg()
    # donate params/state between chunks so XLA reuses the carry buffers
    # in place. Safe by default: run_rounds hands the first chunk private
    # copies of params/state, so the caller's arrays survive and the
    # fresh-init state leaves that alias fleet buffers (residual_energy /
    # last_energy ARE fleet.init_energy) are never both donated and
    # passed as an un-donated fleet argument.
    donate: bool = True
    # async (FedBuff-style) buffered aggregation: an `AsyncCfg` switches
    # the round body to dispatch/land form (core.async_agg) and threads
    # an `AsyncState` (virtual clock + pending-update buffer) through
    # the scan carry and across chunk boundaries. None = sync FedAvg
    # barrier, bitwise-unchanged.
    async_cfg: Optional[AsyncCfg] = None
    # fleet-health monitors (repro.obs.health): when set, run_rounds
    # samples flat-battery / near-depletion counts at every chunk
    # boundary (the same host-sync point as the accuracy eval), logs
    # threshold violations as WARNINGs, auto-extends a streaming
    # telemetry cfg with the staleness / residual-energy P50/P95
    # reducers, and attaches a `HealthReport` to EngineResult.health.
    health: Optional[HealthCfg] = None
    # exact checkpoint/resume (repro.training.checkpoint): every
    # `checkpoint_every` completed rounds, run_rounds serializes the FULL
    # scan carry — params, FleetState, EnvState, AsyncState (async mode),
    # TelemetryCarry (streaming mode), the loop PRNG key, and the round
    # counter — to `checkpoint_dir/ckpt_r{round:08d}.npz` with a sha256
    # sidecar, at the first chunk boundary crossing each multiple.
    # `resume` names a checkpoint file, or a directory to resume from the
    # newest *intact* checkpoint (corrupt/torn files are skipped with a
    # warning). Resume is bitwise: because chunking is scan partitioning
    # (round r's math never depends on chunk alignment), a resumed run's
    # carry equals the uninterrupted run's at every subsequent boundary
    # (tests/test_checkpoint_resume.py).
    checkpoint_every: Optional[int] = None
    checkpoint_dir: Optional[str] = None
    resume: Optional[str] = None
    # carry-compaction: hold the FleetState/EnvState float leaves as
    # bfloat16 inside the scan carry (expand → round math in f32 →
    # recompact every round). Halves the float carry bytes per fleet
    # device — the engine_bench `telemetry_host_bytes` rows report the
    # saving — at the cost of bf16 rounding of the carried statistics
    # (residual energy, cached utilities, bandit values, diurnal phase).
    # Off by default: the default path is byte-identical to not having
    # the flag, keeping golden histories bitwise.
    compact_carry: bool = False


# --------------------------------------------------------------- sharding

def shard_over_fleet(tree, mesh, S: int):
    """device_put every leaf (all must have leading axis S) with a
    fleet-axis NamedSharding. Use `replicate` for global trees (params):
    deciding by shape is unsound — a bias of length S would alias."""
    fleet_s = jax.sharding.NamedSharding(mesh,
                                         jax.sharding.PartitionSpec("fleet"))

    def place(x):
        assert x.ndim >= 1 and x.shape[0] == S, (
            f"fleet-sharded leaf must lead with S={S}, got {x.shape}")
        return jax.device_put(x, fleet_s)

    return jax.tree.map(place, tree)


def replicate(tree, mesh):
    """device_put every leaf fully replicated on the fleet mesh."""
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, repl), tree)


def _copy_tree(tree):
    """Leaf-wise defensive copy: every leaf gets its own buffer (breaks
    caller aliasing before donation). asarray first — pytrees may carry
    Python-scalar leaves, which have no .copy()."""
    return jax.tree.map(lambda x: jnp.asarray(x).copy(), tree)


# ---------------------------------------------------- carry compaction

# the f32 leaves squeezed to bf16 when EngineCfg.compact_carry is on.
# int/bool leaves (H, u, last_round, dropped, counters, channel/plug/
# online masks) are already minimal and stay untouched.
_COMPACT_FLEET = ("residual_energy", "last_stat", "last_local_loss",
                  "last_ecp", "last_energy", "q_value", "g_loss")
_COMPACT_ENV = ("phase_h",)


def _cast_leaves(t, names, dtype):
    return t._replace(**{n: getattr(t, n).astype(dtype) for n in names})


def _compact_pair(state, env):
    return (_cast_leaves(state, _COMPACT_FLEET, jnp.bfloat16),
            _cast_leaves(env, _COMPACT_ENV, jnp.bfloat16))


def _expand_pair(state, env):
    return (_cast_leaves(state, _COMPACT_FLEET, jnp.float32),
            _cast_leaves(env, _COMPACT_ENV, jnp.float32))


def _compact_round_body(round_body, async_mode: bool):
    """Round body operating on a bf16-compacted state/env carry: expand
    to f32, run the (unchanged, f32) round math, recompact. Params and
    AsyncState pass through untouched — only the fleet-statistics carry
    is squeezed."""
    if async_mode:
        def body(p, s, a, e, *args):
            s, e = _expand_pair(s, e)
            p, s, a, e, m = round_body(p, s, a, e, *args)
            s, e = _compact_pair(s, e)
            return p, s, a, e, m

        return body

    def body(p, s, e, *args):
        s, e = _expand_pair(s, e)
        p, s, e, m = round_body(p, s, e, *args)
        s, e = _compact_pair(s, e)
        return p, s, e, m

    return body


def _compact_chunk(chunk, async_mode: bool):
    """Keep the chunk's external interface full-precision: compact the
    state/env arguments on entry (so the scan carry holds bf16 leaves)
    and expand the outputs on exit. Callers (run_rounds, checkpointing)
    never see a compacted pytree. Arg/output positions are fixed by the
    chunk variants: state at 1, env at 2 (sync) / 3 (async)."""
    ei = 3 if async_mode else 2

    def wrapped(*args):
        args = list(args)
        args[1], args[ei] = _compact_pair(args[1], args[ei])
        out = list(chunk(*args))
        out[1], out[ei] = _expand_pair(out[1], out[ei])
        return tuple(out)

    return wrapped


# ------------------------------------------------------------ chunked scan

def _strip_per_device(m: Dict, collect_per_device: bool, streaming: bool):
    """Drop the raw per-device leaves that must not stream to the host
    as dense (R, S) history: all of them when streaming (the reducers
    already folded them), the non-legacy ones always, and the legacy
    pair (selected, H) too when `collect_per_device` is off. Runs at
    trace time — unconsumed leaves never reach the compiled program, so
    the dense-mode ys schema (and golden history) is unchanged."""
    m = dict(m)
    for k in PER_DEVICE_METRICS:
        if streaming or not collect_per_device or k not in DENSE_PER_DEVICE:
            m.pop(k, None)  # async-only keys are absent from sync bodies
    return m


def _chunk_body(round_body, length: int, collect_per_device: bool,
                telemetry: Optional[TelemetryCfg] = None,
                async_mode: bool = False, compact: bool = False):
    """`_chunk_variants` plus the optional bf16 carry compaction
    (`EngineCfg.compact_carry`): with `compact` the scan carry holds the
    bf16-squeezed state/env while the chunk's own signature stays
    full-precision. `compact=False` returns the variant closure
    untouched — bitwise-identical to the pre-flag engine."""
    if not compact:
        return _chunk_variants(round_body, length, collect_per_device,
                               telemetry, async_mode)
    chunk = _chunk_variants(_compact_round_body(round_body, async_mode),
                            length, collect_per_device, telemetry,
                            async_mode)
    return _compact_chunk(chunk, async_mode)


def _chunk_variants(round_body, length: int, collect_per_device: bool,
                    telemetry: Optional[TelemetryCfg] = None,
                    async_mode: bool = False):
    """R-round scan body: carry (params, state, env, key); fleet/cx/cy
    are loop-invariant arguments threaded to the closure-free round body;
    ys = metric pytree.

    PRNG folding matches the sequential driver exactly: one
    `jax.random.split` of the carried key per round.

    With a streaming `telemetry` cfg the chunk takes (and returns) a
    `TelemetryCarry` as a trailing argument: every round's raw metrics
    dict is folded into the reducer states inside the scan, and the
    per-device leaves are dropped from ys — history stays O(R) scalars
    while per-device aggregates accumulate on device in O(S).

    `async_mode` expects an async round body
    (`core.round.make_async_round_body`): the chunk signature gains an
    `AsyncState` argument/output after `state`, carried through the scan
    exactly like FleetState — the pending buffer and virtual clock
    survive chunk boundaries bit-exactly (the resume test's subject).
    The sync closures below are untouched byte-for-byte, keeping the
    golden dense history bitwise-stable."""
    streaming = telemetry is not None and telemetry.streaming

    if async_mode and not streaming:
        def chunk(params, state: FleetState, astate: AsyncState,
                  env: EnvState, fleet: DeviceFleet, cx, cy, key,
                  start_round):
            rounds = jnp.arange(length, dtype=jnp.int32) + start_round

            def step(carry, r):
                p, s, a, e, k = carry
                k, kr = jax.random.split(k)
                p, s, a, e, m = round_body(p, s, a, e, fleet, cx, cy, kr, r)
                m = _strip_per_device(m, collect_per_device, False)
                return (p, s, a, e, k), m

            (params, state, astate, env, key), hist = jax.lax.scan(
                step, (params, state, astate, env, key), rounds)
            return params, state, astate, env, key, hist

        return chunk

    if async_mode:
        def chunk(params, state: FleetState, astate: AsyncState,
                  env: EnvState, fleet: DeviceFleet, cx, cy, key,
                  start_round, tel):
            rounds = jnp.arange(length, dtype=jnp.int32) + start_round

            def step(carry, r):
                p, s, a, e, k, t = carry
                k, kr = jax.random.split(k)
                p, s, a, e, m = round_body(p, s, a, e, fleet, cx, cy, kr, r)
                t = update_telemetry(telemetry, t, m, r)
                m = _strip_per_device(m, collect_per_device, True)
                return (p, s, a, e, k, t), m

            (params, state, astate, env, key, tel), hist = jax.lax.scan(
                step, (params, state, astate, env, key, tel), rounds)
            return params, state, astate, env, key, tel, hist

        return chunk

    if not streaming:
        def chunk(params, state: FleetState, env: EnvState,
                  fleet: DeviceFleet, cx, cy, key, start_round):
            rounds = jnp.arange(length, dtype=jnp.int32) + start_round

            def step(carry, r):
                p, s, e, k = carry
                k, kr = jax.random.split(k)
                p, s, e, m = round_body(p, s, e, fleet, cx, cy, kr, r)
                m = _strip_per_device(m, collect_per_device, False)
                return (p, s, e, k), m

            (params, state, env, key), hist = jax.lax.scan(
                step, (params, state, env, key), rounds)
            return params, state, env, key, hist

        return chunk

    def chunk(params, state: FleetState, env: EnvState,
              fleet: DeviceFleet, cx, cy, key, start_round, tel):
        rounds = jnp.arange(length, dtype=jnp.int32) + start_round

        def step(carry, r):
            p, s, e, k, t = carry
            k, kr = jax.random.split(k)
            p, s, e, m = round_body(p, s, e, fleet, cx, cy, kr, r)
            t = update_telemetry(telemetry, t, m, r)
            m = _strip_per_device(m, collect_per_device, True)
            return (p, s, e, k, t), m

        (params, state, env, key, tel), hist = jax.lax.scan(
            step, (params, state, env, key, tel), rounds)
        return params, state, env, key, tel, hist

    return chunk


def _chunk_body_mp(round_body_mp, length: int, collect_per_device: bool,
                   telemetry: Optional[TelemetryCfg] = None,
                   async_mode: bool = False):
    """`_chunk_body` for the traced-method round: the `MethodParams`
    pytree leads the signature as a loop-invariant argument, so the
    campaign grid can vmap it over the method axis."""
    if async_mode:
        def chunk(mp, *args):
            inner = _chunk_body(
                lambda p, s, a, e, f, x, y, k, r:
                    round_body_mp(mp, p, s, a, e, f, x, y, k, r),
                length, collect_per_device, telemetry, async_mode=True)
            return inner(*args)

        return chunk

    def chunk(mp, *args):
        inner = _chunk_body(
            lambda p, s, e, f, x, y, k, r:
                round_body_mp(mp, p, s, e, f, x, y, k, r),
            length, collect_per_device, telemetry)
        return inner(*args)

    return chunk


def make_chunk_fn(model: FLModel, cfg: FLConfig, method: MethodSpec, *,
                  chunk_size: int = 8, collect_per_device: bool = True,
                  donate: bool = False, scenario: Optional[Scenario] = None,
                  telemetry: Optional[TelemetryCfg] = None,
                  async_cfg: Optional[AsyncCfg] = None,
                  compact_carry: bool = False):
    """jitted chunk(params, state, env, fleet, cx, cy, key, start_round)
    -> (params', state', env', key', history) running `chunk_size` rounds
    on device. Closure-free like the round body: one compiled chunk
    serves any same-shaped fleet/dataset. `history` leaves have leading
    axis chunk_size. With `donate=True` the params/state inputs are
    consumed (aliased into the outputs) — callers must not reuse them.
    A streaming `telemetry` cfg appends a `TelemetryCarry` argument and
    output: chunk(..., start_round, tel) -> (..., key', tel', history)
    (see `core.metrics` for building/draining the carry).
    An `async_cfg` switches to the buffered-aggregation round body and
    inserts an `AsyncState` argument/output after `state`:
    chunk(params, state, astate, env, ...) -> (..., astate', ...).
    `compact_carry` squeezes the state/env float leaves to bf16 inside
    the scan carry (`EngineCfg.compact_carry`); the chunk's arguments
    and outputs stay full-precision either way."""
    if async_cfg is not None:
        body = make_async_round_body(model, cfg, method, scenario,
                                     async_cfg)
        chunk = _chunk_body(body, chunk_size, collect_per_device,
                            telemetry, async_mode=True,
                            compact=compact_carry)
    else:
        body = make_round_body(model, cfg, method, scenario)
        chunk = _chunk_body(body, chunk_size, collect_per_device, telemetry,
                            compact=compact_carry)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(chunk, donate_argnums=donate_argnums)


def _telemetry_carry(tcfg: TelemetryCfg, body, args, batch: Optional[int] = None):
    """Fresh reducer carry for a round body: abstract-trace one (cell's)
    round for its metric shapes (no compile), init every spec'd reducer,
    and broadcast the states over a leading `batch` axis when the caller
    vmaps the carry (seeds / grid cells). The single construction point —
    if reducer states ever need fleet-mesh sharding, it happens here."""
    shapes = jax.eval_shape(body, *args)[-1]  # metrics are the last output
    tel = init_telemetry(tcfg, shapes)
    return tel if batch is None else replicate_state(tel, batch)


def _empty_history(chunk_fn, args) -> Dict[str, np.ndarray]:
    """Correctly-keyed zero-round history via abstract tracing (no
    compile): used when `rounds=0` so callers always get every metric
    key with a length-0 leading axis. The history pytree is the last of
    the chunk's outputs in every variant (sync/async × dense/stream)."""
    shapes = jax.eval_shape(chunk_fn, *args)[-1]
    return {k: np.zeros((0,) + tuple(v.shape[1:]), v.dtype)
            for k, v in shapes.items()}


# ----------------------------------------------------- async history fetch

class _HostHistory:
    """Preallocated host-side history buffers with deferred device fetch.

    The old drivers called `jax.device_get(hist)` right after each chunk
    dispatch — a host-sync stall for the full chunk execution — and then
    paid an O(R) `np.concatenate` over all chunks at the end. Here the
    fetch of chunk *i* is deferred until chunk *i+1* has been dispatched
    (`push` then `drain` next iteration), so the host copies one chunk's
    history while the device runs the next, and every chunk lands
    directly in its slice of a preallocated per-metric buffer (allocated
    lazily from the first fetched chunk's shapes, `round_axis` scaled to
    the campaign length — no concatenate churn)."""

    def __init__(self, total_rounds: int, round_axis: int):
        self.total = total_rounds
        self.axis = round_axis
        self.bufs: Optional[Dict[str, np.ndarray]] = None
        self._pending: List = []

    def push(self, hist, offset: int, length: int) -> None:
        """Register a chunk's on-device history for a later fetch."""
        self._pending.append((hist, offset, length))

    def drain(self) -> None:
        """Fetch every pending chunk into the host buffers (blocks only
        on those chunks' completion, not on anything dispatched after)."""
        if not self._pending:
            return
        with span("history_drain", chunks=len(self._pending)):
            self._drain_pending()

    def _drain_pending(self) -> None:
        for hist, off, length in self._pending:
            h = jax.device_get(hist)
            if self.bufs is None:
                self.bufs = {}
                for k, v in h.items():
                    shape = list(v.shape)
                    shape[self.axis] = self.total
                    self.bufs[k] = np.empty(shape, v.dtype)
            for k, v in h.items():
                sl = [slice(None)] * v.ndim
                sl[self.axis] = slice(off, off + length)
                self.bufs[k][tuple(sl)] = v
        self._pending.clear()

    def finalize(self, rounds_done: int) -> Optional[Dict[str, np.ndarray]]:
        """Drain and return the buffers truncated to `rounds_done` (early
        stop). None when no chunk ever ran (rounds=0)."""
        self.drain()
        if self.bufs is None:
            return None
        if rounds_done == self.total:
            return self.bufs
        out = {}
        for k, v in self.bufs.items():
            sl = [slice(None)] * v.ndim
            sl[self.axis] = slice(0, rounds_done)
            out[k] = v[tuple(sl)]
        return out


@dataclasses.dataclass
class EngineResult:
    params: object
    state: FleetState
    history: Dict[str, np.ndarray]   # per-round arrays, length rounds_run
    rounds_run: int
    reached_round: Optional[int]     # first chunk-boundary round ≥ target
    acc_curve: np.ndarray            # one accuracy per completed chunk
    env: Optional[EnvState] = None   # final environment state
    # streaming telemetry only: finalized reducer outputs keyed by
    # `tel/<metric>/<reducer>` (per-device aggregates, O(S) each)
    telemetry: Optional[Dict[str, np.ndarray]] = None
    # per-chunk wall clock (first entry includes JIT compile) + rounds per
    # chunk: lets callers report steady-state throughput separately from
    # compile time (benchmarks.common.cached_run). With the async history
    # off-load, chunk i's wall covers its dispatch, the fetch of chunk
    # i−1's history, and the chunk-boundary eval (which blocks on chunk
    # i) when eval_fn is given; the final fetch is folded into the last
    # entry, so the sum still tracks total loop wall and
    # (sum − compile_s) / rounds is the steady campaign throughput.
    chunk_wall_s: Optional[np.ndarray] = None
    chunk_rounds: Optional[np.ndarray] = None
    # host-side wall of the chunk dispatches that triggered a fresh jit
    # (first chunk + any remainder length): with async dispatch the call
    # returns right after trace+compile without waiting on execution, so
    # this isolates compile time directly instead of inferring it from
    # the wall of a chunk that mixes compile and execution
    compile_s: float = 0.0
    # async engine mode only: final virtual clock + pending-update
    # buffer (core.state.AsyncState)
    async_state: Optional[AsyncState] = None
    # fleet-health verdict (repro.obs.health), populated when
    # EngineCfg.health is set: chunk-boundary flat-battery /
    # near-depletion samples, selection Gini, staleness / energy tails
    health: Optional[HealthReport] = None
    # checkpoint/resume only: the round this run started from (0 unless
    # EngineCfg.resume loaded a checkpoint). history rows [0, start_round)
    # were not run here and are zero-filled.
    start_round: int = 0


def _carry_payload(params, state, astate, env, tel, key, done: int) -> Dict:
    """The full scan carry as a flat checkpoint payload. Everything round
    `done+1` depends on is in here — params, fleet/env/async/telemetry
    state, and the loop PRNG key — so load-and-continue is bitwise equal
    to never having stopped. Keys are stable: they are the npz tree paths
    (`training.checkpoint`)."""
    payload = {"params": params, "state": state, "env": env, "key": key,
               "round": jnp.asarray(done, jnp.int32)}
    if astate is not None:
        payload["astate"] = astate
    if tel is not None:
        payload["tel"] = tel
    return payload


def run_rounds(model: FLModel, fleet: DeviceFleet, cx, cy, cfg: FLConfig,
               method: MethodSpec, *, rounds: int, key, params=None,
               state: Optional[FleetState] = None,
               ecfg: EngineCfg = EngineCfg(),
               eval_fn=None, target_acc: Optional[float] = None,
               init_key=None, scenario: Optional[Scenario] = None,
               env: Optional[EnvState] = None,
               env_key=None) -> EngineResult:
    """Chunked multi-round driver. Early-stops on `target_acc` (needs
    `eval_fn`) at chunk boundaries — accuracy is never evaluated inside
    a compiled chunk, so a campaign overshoots the target by at most
    chunk_size − 1 rounds. `scenario` selects the fleet-dynamics regime
    (None ≡ static-paper); dynamic scenarios draw the initial EnvState
    from `env_key` (default: fold_in of the loop key — does not perturb
    the round PRNG stream)."""
    if ecfg.chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {ecfg.chunk_size}")
    S = fleet.n
    if params is None:
        params = model.init(init_key if init_key is not None
                            else jax.random.PRNGKey(0))
    if state is None:
        state = init_fleet_state(fleet, H0=cfg.policy.H0)
    if env is None:
        dyn = scenario is not None and scenario.dynamic
        if dyn and env_key is None:
            env_key = jax.random.fold_in(key, 0x0d1f)
        env = init_env_state(fleet, scenario, key=env_key if dyn else None)

    acfg = ecfg.async_cfg
    astate = (init_async_state(params, S, acfg.slots(cfg.n_select))
              if acfg is not None else None)

    if ecfg.donate:
        # the first chunk consumes (donates) its params/state inputs:
        # private copies keep the caller's arrays alive and un-alias the
        # fresh-init state leaves that share buffers with the fleet
        params = _copy_tree(params)
        state = _copy_tree(state)

    # chunks trace under the fleet mesh, so the Pallas kernels (which
    # GSPMD cannot partition) run replicated (`kernels.mesh.replicated`)
    mesh = None
    if ecfg.fleet_shards and ecfg.fleet_shards > 1:
        mesh = make_fleet_mesh(ecfg.fleet_shards)
        fleet = shard_over_fleet(fleet, mesh, S)
        state = shard_over_fleet(state, mesh, S)
        env = shard_over_fleet(env, mesh, S)
        cx = shard_over_fleet(cx, mesh, S)
        cy = shard_over_fleet(cy, mesh, S)
        params = replicate(params, mesh)

    tcfg = ecfg.telemetry
    streaming = tcfg.streaming
    hcfg = ecfg.health
    if hcfg is not None and streaming:
        # the health monitors read whole-campaign staleness / energy
        # tails off the streaming quantile reducers — declare them
        # before the carry is built (dense runs fall back to exact
        # end-state percentiles in finalize_report)
        tcfg = with_health_specs(tcfg, hcfg, rounds, fleet)
    tel = None
    if streaming:
        if acfg is not None:
            tel = _telemetry_carry(
                tcfg, make_async_round_body(model, cfg, method, scenario,
                                            acfg),
                (params, state, astate, env, fleet, cx, cy, key,
                 jnp.asarray(0, jnp.int32)))
        else:
            tel = _telemetry_carry(
                tcfg, make_round_body(model, cfg, method, scenario),
                (params, state, env, fleet, cx, cy, key,
                 jnp.asarray(0, jnp.int32)))

    if ecfg.checkpoint_every is not None:
        if ecfg.checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got "
                             f"{ecfg.checkpoint_every}")
        if ecfg.checkpoint_dir is None:
            raise ValueError("checkpoint_every needs checkpoint_dir")
    start = 0
    if ecfg.resume is not None:
        # the freshly-initialized carry is the structural `like` tree —
        # resume must match the run's exact configuration (same model /
        # fleet size / async & telemetry modes), or load fails loudly
        like = _carry_payload(params, state, astate, env, tel, key, 0)
        loaded, ck_path = ckpt.load_latest(ecfg.resume, like)
        params, state = loaded["params"], loaded["state"]
        env, key = loaded["env"], loaded["key"]
        if acfg is not None:
            astate = loaded["astate"]
        if streaming:
            tel = loaded["tel"]
        start = int(loaded["round"])
        log.info("resumed from %s at round %d", ck_path, start)
        if start > rounds:
            raise ValueError(f"checkpoint round {start} is beyond the "
                             f"requested {rounds} rounds")

    chunk_fns: Dict[int, object] = {}

    def chunk_fn(length: int):
        if length not in chunk_fns:
            chunk_fns[length] = make_chunk_fn(
                model, cfg, method, chunk_size=length,
                collect_per_device=ecfg.collect_per_device,
                donate=ecfg.donate, scenario=scenario,
                telemetry=tcfg if streaming else None,
                async_cfg=acfg, compact_carry=ecfg.compact_carry)
        return chunk_fns[length]

    hh = _HostHistory(rounds, round_axis=0)
    acc_curve: List[float] = []
    chunk_wall: List[float] = []
    chunk_len: List[int] = []
    health_samples: List[Dict[str, float]] = []
    health_warnings: List[str] = []
    compile_s = 0.0
    reached = None
    done = start
    ci = 0
    while done < rounds:
        length = min(ecfg.chunk_size, rounds - done)
        fresh = length not in chunk_fns
        t0 = time.time()
        with span("chunk", ci, rounds=length, start=done) as chunk_span:
            lead = ((params, state, astate) if acfg is not None
                    else (params, state))
            args = lead + (env, fleet, cx, cy, key, jnp.asarray(done,
                                                                jnp.int32))
            on_mesh = (jax.set_mesh(mesh) if mesh is not None
                       else contextlib.nullcontext())
            with span("compile" if fresh else "dispatch", ci), on_mesh:
                out = chunk_fn(length)(*args
                                       + ((tel,) if streaming else ()))
            # the history is never donated, unlike the carry
            until_ready("chunk.device", out[-1], chunk_span, index=ci)
            params, state = out[0], out[1]
            i = 2
            if acfg is not None:
                astate = out[i]
                i += 1
            env, key = out[i], out[i + 1]
            if streaming:
                tel = out[-2]
            hist = out[-1]
            if fresh:                # dispatch wall ≈ trace + compile
                compile_s += time.time() - t0
            hh.drain()               # fetch chunk i−1 while chunk i runs
            hh.push(hist, done, length)
            chunk_len.append(length)
            done += length
            every = ecfg.checkpoint_every
            if every is not None and (done // every) > ((done - length)
                                                        // every):
                # serialize at the boundary crossing the multiple. The
                # np.asarray copies inside save() read the chunk outputs
                # BEFORE the next dispatch donates them — host copies,
                # so donation stays safe.
                with span("checkpoint", ci, round=done):
                    path = os.path.join(ecfg.checkpoint_dir,
                                        f"ckpt_r{done:08d}.npz")
                    ckpt.save_checkpoint(path, _carry_payload(
                        params, state, astate, env, tel, key, done))
                    log.info("checkpoint written: %s", path)
            stop = False
            if eval_fn is not None:  # blocks on this chunk — timed in,
                with span("eval", ci):     # so chunk walls keep covering
                    acc = float(eval_fn(params))  # the execution they
                acc_curve.append(acc)             # used to
                if target_acc is not None and acc >= target_acc:
                    reached = done - 1
                    stop = True
            if hcfg is not None:     # chunk-boundary fleet-health sample
                with span("health", ci):   # (host sync, like the eval)
                    sample, warns = chunk_sample(hcfg, state, fleet,
                                                 done - 1)
                health_samples.append(sample)
                for w in warns:
                    log.warning(w)
                health_warnings.extend(warns)
        chunk_wall.append(time.time() - t0)
        ci += 1
        if stop:
            break
    t0 = time.time()
    with span("transfer"):
        history = hh.finalize(done)
        telemetry_out = None
        if streaming:                # one O(S) drain for the whole run
            telemetry_out = {k: np.asarray(v) for k, v in jax.device_get(
                finalize_telemetry(tcfg, tel)).items()}
    if chunk_wall:                   # last fetch blocks on the last chunk
        chunk_wall[-1] += time.time() - t0
    if history is None:  # rounds=0: empty but correctly-keyed history
        lead = ((params, state, astate) if acfg is not None
                else (params, state))
        args = lead + (env, fleet, cx, cy, key, jnp.asarray(0, jnp.int32))
        if streaming:
            args = args + (tel,)
        history = _empty_history(chunk_fn(1), args)
    elif start > 0:
        # rows before the resume point were run by the checkpointing
        # process, not this one — the preallocated buffers hold garbage
        # there, so zero-fill to keep downstream reductions deterministic
        for v in history.values():
            v[:start] = 0
    health = None
    if hcfg is not None:
        health = finalize_report(hcfg, health_samples, health_warnings,
                                 state=state, fleet=fleet,
                                 telemetry=telemetry_out,
                                 rounds_run=done, history=history)
    return EngineResult(params=params, state=state, history=history,
                        rounds_run=done, reached_round=reached,
                        acc_curve=np.asarray(acc_curve, np.float64),
                        env=env, telemetry=telemetry_out,
                        chunk_wall_s=np.asarray(chunk_wall, np.float64),
                        chunk_rounds=np.asarray(chunk_len, np.int64),
                        compile_s=compile_s, async_state=astate,
                        health=health, start_round=start)


# ------------------------------------------------------- campaign batching

def _campaign_init(model: FLModel, fleet: DeviceFleet, cfg: FLConfig,
                   seeds: Sequence[int], scenario: Optional[Scenario],
                   per_seed_fleets: bool):
    """Per-seed init params / state / env / loop keys for a vmapped
    campaign batch (the key derivation matches run_fl's `PRNGKey(seed+2)`
    init / `PRNGKey(seed+1)` loop-key / `PRNGKey(seed+3)` env
    convention)."""
    B = len(seeds)
    params = jax.vmap(model.init)(
        jnp.stack([jax.random.PRNGKey(s + 2) for s in seeds]))
    H0 = cfg.policy.H0
    dyn = scenario is not None and scenario.dynamic
    env_keys = jnp.stack([jax.random.PRNGKey(s + 3) for s in seeds])
    if per_seed_fleets:
        state = jax.vmap(lambda f: init_fleet_state(f, H0=H0))(fleet)
        if dyn:
            env = jax.vmap(
                lambda f, k: init_env_state(f, scenario, key=k))(
                    fleet, env_keys)
        else:
            env = jax.vmap(lambda f: init_env_state(f, scenario))(fleet)
    else:
        state = replicate_state(init_fleet_state(fleet, H0=H0), B)
        if dyn:
            env = jax.vmap(lambda k: init_env_state(fleet, scenario,
                                                    key=k))(env_keys)
        else:
            env = replicate_state(init_env_state(fleet, scenario), B)
    keys = jnp.stack([jax.random.PRNGKey(s + 1) for s in seeds])
    return params, state, env, keys


def run_campaign_batch(model: FLModel, fleet: DeviceFleet, cx, cy,
                       cfg: FLConfig, method: MethodSpec, *,
                       seeds: Sequence[int], rounds: int,
                       chunk_size: int = 8,
                       collect_per_device: bool = False,
                       scenario: Optional[Scenario] = None,
                       per_seed_fleets: bool = False,
                       eval_fn: Optional[Callable] = None,
                       target_acc: Optional[float] = None,
                       telemetry: Optional[TelemetryCfg] = None,
                       async_cfg: Optional[AsyncCfg] = None
                       ) -> Dict[str, np.ndarray]:
    """vmap independent campaigns over the seed axis. Per-seed init params
    and PRNG streams always.

    Async aggregation: an `async_cfg` (or `method.aggregation ==
    "async"`, which derives one from `method.buffer_m`) switches every
    seed's campaign to the buffered dispatch/land round body; each seed
    carries its own `AsyncState` and the history gains the per-round
    async scalars plus `final_wall_clock` (B,).

    `per_seed_fleets=False` (legacy): one shared fleet/dataset — cross-seed
    variance covers init + round randomness only, and results differ from
    per-seed `run_fl(seed=s)` calls (which rebuild fleet and data).
    `per_seed_fleets=True`: fleet/cx/cy leaves carry a leading seed axis
    B = len(seeds) (`sim.devices.build_fleet_batch` /
    `launch.fl_run.build_task_batch`) and the vmap runs every seed on its
    own fleet draw and λ-partition — cross-seed variance then includes the
    fleet/data heterogeneity the paper's rankings are about, and seed i
    reproduces `run_fl(seed=seeds[i])` round-for-round.

    `eval_fn(params_batch) -> (B,)` is evaluated at every chunk boundary
    (batched campaigns never early-stop — all seeds run all rounds);
    with `target_acc` the history gains `reached_round` (B,), the first
    chunk-end round index where a seed's accuracy met the target (-1 if
    never), mirroring run_rounds' chunk-granular early-stop semantics.

    Per-chunk histories stream into preallocated host buffers while the
    next chunk runs (`_HostHistory`) — no end-of-campaign concatenate.

    A streaming `telemetry` cfg folds the declared per-device reducers
    inside every seed's scan carry (the carry gains a leading seed axis
    like params/state) and merges the finalized `tel/...` outputs into
    the returned history as (B, ...) arrays — dense per-device history
    is then typically disabled via `collect_per_device=False`.

    Returns history with leading axes (n_seeds, rounds), plus
    `final_residual_energy`/`final_H` (B, S), `chunk_wall_s`/`chunk_rounds`
    (n_chunks,) timing, and `acc_curve` (n_chunks, B) when `eval_fn` is
    given."""
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if async_cfg is None and method.aggregation == "async":
        async_cfg = AsyncCfg(buffer_m=method.buffer_m)
    is_async = async_cfg is not None
    if is_async:
        body = make_async_round_body(model, cfg, method, scenario,
                                     async_cfg)
    else:
        body = make_round_body(model, cfg, method, scenario)
    B = len(seeds)
    streaming = telemetry is not None and telemetry.streaming
    tcfg = telemetry if streaming else None
    fleet_ax = 0 if per_seed_fleets else None
    chunk = _chunk_body(body, chunk_size, collect_per_device, tcfg,
                        async_mode=is_async)
    in_axes = (0, 0) + ((0,) if is_async else ()) + (
        0, fleet_ax, fleet_ax, fleet_ax, 0, None)
    if streaming:
        in_axes = in_axes + (0,)
    batched = jax.jit(jax.vmap(chunk, in_axes=in_axes))

    params, state, env, keys = _campaign_init(model, fleet, cfg, seeds,
                                              scenario, per_seed_fleets)

    def cell(t):
        return jax.tree.map(lambda x: x[0], t)

    astate = None
    if is_async:
        S = state.residual_energy.shape[-1]
        astate = replicate_state(
            init_async_state(cell(params), S,
                             async_cfg.slots(cfg.n_select)), B)
    tel = None
    if streaming:
        # one (unbatched) cell's args, broadcast over the seed axis
        cell_args = (cell(params), cell(state))
        if is_async:
            cell_args = cell_args + (cell(astate),)
        tel = _telemetry_carry(
            tcfg, body,
            cell_args + (cell(env),
                         cell(fleet) if per_seed_fleets else fleet,
                         cx[0] if per_seed_fleets else cx,
                         cy[0] if per_seed_fleets else cy,
                         keys[0], jnp.asarray(0, jnp.int32)), batch=B)

    hh = _HostHistory(rounds, round_axis=1)
    acc_curve: List[np.ndarray] = []
    chunk_wall: List[float] = []
    chunk_len: List[int] = []
    compile_s = 0.0
    reached = np.full((B,), -1, np.int64)
    done = 0
    ci = 0
    while done < rounds:
        length = min(chunk_size, rounds - done)
        fresh = done == 0
        if length != chunk_size:  # remainder chunk: separate trace
            batched = jax.jit(jax.vmap(
                _chunk_body(body, length, collect_per_device, tcfg,
                            async_mode=is_async),
                in_axes=in_axes))
            fresh = True
        t0 = time.time()
        with span("chunk", ci, rounds=length, start=done,
                  seeds=B) as chunk_span:
            lead = ((params, state, astate) if is_async
                    else (params, state))
            args = lead + (env, fleet, cx, cy, keys,
                           jnp.asarray(done, jnp.int32))
            with span("compile" if fresh else "dispatch", ci):
                out = batched(*args + ((tel,) if streaming else ()))
            until_ready("chunk.device", out[-1], chunk_span, index=ci)
            params, state = out[0], out[1]
            i = 2
            if is_async:
                astate = out[i]
                i += 1
            env, keys = out[i], out[i + 1]
            if streaming:
                tel = out[-2]
            hist = out[-1]
            if fresh:                # dispatch wall ≈ trace + compile
                compile_s += time.time() - t0
            hh.drain()               # fetch chunk i−1 while chunk i runs
            hh.push(hist, done, length)
            chunk_len.append(length)
            done += length
            if eval_fn is not None:  # blocks on this chunk — timed in
                with span("eval", ci):
                    acc = np.asarray(eval_fn(params), np.float64)
                acc_curve.append(acc)
                if target_acc is not None:
                    newly = (acc >= target_acc) & (reached < 0)
                    reached[newly] = done - 1
        chunk_wall.append(time.time() - t0)
        ci += 1
    t0 = time.time()
    with span("transfer"):
        history = hh.finalize(done)
    if chunk_wall:
        chunk_wall[-1] += time.time() - t0
    if history is None:  # rounds=0: empty but correctly-keyed history
        lead = ((params, state, astate) if is_async
                else (params, state))
        args = lead + (env, fleet, cx, cy, keys,
                       jnp.asarray(0, jnp.int32))
        if streaming:
            args = args + (tel,)
        shapes = jax.eval_shape(batched, *args)[-1]
        history = {k: np.zeros((B, 0) + tuple(v.shape[2:]), v.dtype)
                   for k, v in shapes.items()}
    if streaming:                    # finalized (B, ...) reducer outputs
        history.update({k: np.asarray(v) for k, v in jax.device_get(
            finalize_telemetry(tcfg, tel)).items()})
    history["final_residual_energy"] = np.asarray(state.residual_energy)
    history["final_H"] = np.asarray(state.H)
    if is_async:
        history["final_wall_clock"] = np.asarray(astate.t_now)
    history["chunk_wall_s"] = np.asarray(chunk_wall, np.float64)
    history["chunk_rounds"] = np.asarray(chunk_len, np.int64)
    history["compile_s"] = np.float64(compile_s)
    if eval_fn is not None:
        history["acc_curve"] = (np.stack(acc_curve) if acc_curve
                                else np.zeros((0, B)))
        if target_acc is not None:
            history["reached_round"] = reached
    return history


def _run_grid_batched(model: FLModel, fleet: DeviceFleet, cx, cy,
                      cfg: FLConfig, methods: Dict[str, MethodSpec], *,
                      seeds: Sequence[int], rounds: int, chunk_size: int,
                      collect_per_device: bool,
                      scenario: Optional[Scenario],
                      per_seed_fleets: bool,
                      eval_fn: Optional[Callable],
                      target_acc: Optional[float],
                      telemetry: Optional[TelemetryCfg] = None,
                      async_cfg: Optional[AsyncCfg] = None
                      ) -> Dict[str, Dict[str, np.ndarray]]:
    """One-compile (method × seed) grid: the M×B grid cells flatten into
    ONE vmapped axis of length M·B — cell i·B+j runs method i on seed j —
    so the whole grid is a single XLA program with a single batching
    level (a nested method-over-seed vmap measures ~35% more compile for
    the same math). Per-cell `MethodParams` repeat each method B times;
    selector/policy dispatch via lax.switch on its ids, with all
    selectors sharing one rank-space ε-greedy mechanism. With per-seed
    fleets the (B,)-leaf fleet/data pytrees stay *unbatched* arguments
    and each cell gathers its seed's slice on device (`x[seed_idx]`) —
    the host never tiles the M× client-data copies. Returns the same
    per-method history dicts as the fallback path, with `chunk_wall_s` /
    `compile_s` divided by M (each method's share of the shared program)
    so per-method `us_per_round` stays comparable."""
    names = list(methods)
    M, B = len(names), len(seeds)
    mp = method_params_batch([methods[n] for n in names],
                             alpha=cfg.alpha, beta=cfg.beta,
                             autofl_eta=cfg.autofl_eta,
                             autofl_ema=cfg.autofl_ema,
                             fault_cfg=scenario.faults
                             if scenario is not None else None)
    # each cell's local-SGD loop runs to its own cohort's largest H; under
    # the grid vmap the shared loop runs to the largest over the cells,
    # and the cells already done keep their carry
    #
    # a grid with any async cell compiles the async round body for every
    # cell; sync cells ride along with buffer_m = 0 (the full-cohort
    # sentinel) and reproduce their sync selections/params through the
    # land fast path. The static buffer capacity / land count must cover
    # every cell: capacity fits the largest trigger, land count drains
    # the smallest.
    K = cfg.n_select
    m_effs = [methods[n].buffer_m if methods[n].aggregation == "async"
              else K for n in names]
    any_async = async_cfg is not None or any(
        methods[n].aggregation == "async" for n in names)
    if any_async:
        base = async_cfg if async_cfg is not None else AsyncCfg(buffer_m=K)
        acfg_shared = dataclasses.replace(
            base, capacity=max(max(m_effs), base.buffer_m) + K,
            n_lands=max(-(-K // m) for m in m_effs))
        body = make_async_round_body_mp(model, cfg, scenario, acfg_shared)
    else:
        acfg_shared = None
        body = make_round_body_mp(model, cfg, scenario)
    streaming = telemetry is not None and telemetry.streaming
    tcfg = telemetry if streaming else None
    # cell layout: method-major — mp leaves repeat per seed, seed_idx
    # tiles per method
    mp_cells = jax.tree.map(lambda x: jnp.repeat(x, B, axis=0), mp)
    seed_idx = jnp.tile(jnp.arange(B, dtype=jnp.int32), M)

    def cell_chunk(length: int):
        chunk = _chunk_body_mp(body, length, collect_per_device, tcfg,
                               async_mode=any_async)

        if any_async:
            def run(mp_c, sidx, params, state, astate, env, fleet, cx, cy,
                    key, start, *tel):
                if per_seed_fleets:
                    fleet = jax.tree.map(lambda x: x[sidx], fleet)
                    cx, cy = cx[sidx], cy[sidx]
                return chunk(mp_c, params, state, astate, env, fleet, cx,
                             cy, key, start, *tel)

            return run

        def run(mp_c, sidx, params, state, env, fleet, cx, cy, key, start,
                *tel):
            if per_seed_fleets:   # on-device per-cell gather of seed data
                fleet = jax.tree.map(lambda x: x[sidx], fleet)
                cx, cy = cx[sidx], cy[sidx]
            return chunk(mp_c, params, state, env, fleet, cx, cy, key,
                         start, *tel)

        return run

    cell_axes = (0, 0, 0, 0) + ((0,) if any_async else ()) + (
        0, None, None, None, 0, None)
    if streaming:
        cell_axes = cell_axes + (0,)

    def grid_fn(length: int):
        return jax.jit(jax.vmap(cell_chunk(length), in_axes=cell_axes))

    params, state, env, keys = _campaign_init(model, fleet, cfg, seeds,
                                              scenario, per_seed_fleets)
    # every method starts from the same per-seed init: tile the (B, ...)
    # carry leaves to (M·B, ...) cells
    def tile(t):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (M,) + x.shape).reshape(
                (M * B,) + x.shape[1:]), t)

    params, state, env, keys = (tile(params), tile(state), tile(env),
                                tile(keys))

    def cell(t):
        return jax.tree.map(lambda x: x[0], t)

    astate = None
    if any_async:
        S = state.residual_energy.shape[-1]
        astate = replicate_state(
            init_async_state(cell(params), S, acfg_shared.slots(K)),
            M * B)
    tel = None
    if streaming:
        # one cell's args, broadcast over the M·B flattened cell axis
        cell_args = (cell(mp_cells), cell(params), cell(state))
        if any_async:
            cell_args = cell_args + (cell(astate),)
        tel = _telemetry_carry(
            tcfg, body,
            cell_args + (cell(env),
                         cell(fleet) if per_seed_fleets else fleet,
                         cx[0] if per_seed_fleets else cx,
                         cy[0] if per_seed_fleets else cy,
                         keys[0], jnp.asarray(0, jnp.int32)),
            batch=M * B)

    batched = grid_fn(chunk_size)
    hh = _HostHistory(rounds, round_axis=1)
    acc_curve: List[np.ndarray] = []
    chunk_wall: List[float] = []
    chunk_len: List[int] = []
    compile_s = 0.0
    reached = np.full((M, B), -1, np.int64)
    done = 0
    ci = 0
    while done < rounds:
        length = min(chunk_size, rounds - done)
        fresh = done == 0
        if length != chunk_size:  # remainder chunk: separate trace
            batched = grid_fn(length)
            fresh = True
        t0 = time.time()
        with span("chunk", ci, rounds=length, start=done,
                  cells=M * B) as chunk_span:
            lead = (mp_cells, seed_idx, params, state) + (
                (astate,) if any_async else ())
            args = lead + (env, fleet, cx, cy, keys,
                           jnp.asarray(done, jnp.int32))
            with span("compile" if fresh else "dispatch", ci):
                out = batched(*args + ((tel,) if streaming else ()))
            until_ready("chunk.device", out[-1], chunk_span, index=ci)
            params, state = out[0], out[1]
            i = 2
            if any_async:
                astate = out[i]
                i += 1
            env, keys = out[i], out[i + 1]
            if streaming:
                tel = out[-2]
            hist = out[-1]
            if fresh:                # dispatch wall ≈ trace + compile
                compile_s += time.time() - t0
            hh.drain()               # fetch chunk i−1 while chunk i runs
            hh.push(hist, done, length)
            chunk_len.append(length)
            done += length
            if eval_fn is not None:  # blocks on this chunk — timed in;
                # eval_fn is per-batch ((B,) accuracies) — per method
                with span("eval", ci):
                    acc = np.stack([np.asarray(eval_fn(jax.tree.map(
                        lambda x: x[i * B:(i + 1) * B], params)),
                        np.float64) for i in range(M)])
                acc_curve.append(acc)
                if target_acc is not None:
                    newly = (acc >= target_acc) & (reached < 0)
                    reached[newly] = done - 1
        chunk_wall.append(time.time() - t0)
        ci += 1
    t0 = time.time()
    with span("transfer"):
        bufs = hh.finalize(done)
        tel_out: Dict[str, np.ndarray] = {}
        if streaming:                # (M·B, ...) reducer outputs
            tel_out = {k: np.asarray(v) for k, v in jax.device_get(
                finalize_telemetry(tcfg, tel)).items()}
    if chunk_wall:
        chunk_wall[-1] += time.time() - t0
    if bufs is None:  # rounds=0
        lead = (mp_cells, seed_idx, params, state) + (
            (astate,) if any_async else ())
        args = lead + (env, fleet, cx, cy, keys,
                       jnp.asarray(0, jnp.int32))
        if streaming:
            args = args + (tel,)
        shapes = jax.eval_shape(grid_fn(1), *args)[-1]
        bufs = {k: np.zeros((M * B, 0) + tuple(v.shape[2:]), v.dtype)
                for k, v in shapes.items()}
    final_E = np.asarray(state.residual_energy)
    final_H = np.asarray(state.H)
    final_wall = np.asarray(astate.t_now) if any_async else None
    wall = np.asarray(chunk_wall, np.float64) / M
    lens = np.asarray(chunk_len, np.int64)
    accs = np.stack(acc_curve) if acc_curve else np.zeros((0, M, B))
    out: Dict[str, Dict[str, np.ndarray]] = {}
    for i, name in enumerate(names):
        rows = slice(i * B, (i + 1) * B)
        h = {k: v[rows] for k, v in bufs.items()}
        h.update({k: v[rows] for k, v in tel_out.items()})
        h["final_residual_energy"] = final_E[rows]
        h["final_H"] = final_H[rows]
        if final_wall is not None:
            h["final_wall_clock"] = final_wall[rows]
        h["chunk_wall_s"] = wall
        h["chunk_rounds"] = lens
        h["compile_s"] = np.float64(compile_s / M)  # per-method share
        if eval_fn is not None:
            h["acc_curve"] = accs[:, i, :]
            if target_acc is not None:
                h["reached_round"] = reached[i]
        out[name] = h
    return out


def run_campaign_grid(model: FLModel, fleet: DeviceFleet, cx, cy,
                      cfg: FLConfig, methods: Dict[str, MethodSpec], *,
                      seeds: Sequence[int], rounds: int,
                      chunk_size: int = 8,
                      collect_per_device: bool = False,
                      scenario: Optional[Scenario] = None,
                      per_seed_fleets: bool = False,
                      eval_fn: Optional[Callable] = None,
                      target_acc: Optional[float] = None,
                      method_batched: bool = True,
                      telemetry: Optional[TelemetryCfg] = None,
                      async_cfg: Optional[AsyncCfg] = None
                      ) -> Dict[str, Dict[str, np.ndarray]]:
    """(method × seed) benchmark grid.

    Aggregation regimes mix freely: specs with `aggregation="async"`
    (see `core.methods.async_variant`) run FedBuff-style buffered
    aggregation at their own `buffer_m` while sync specs keep the FedAvg
    barrier — still ONE compiled program on the batched path (sync cells
    ride the async body with the full-cohort sentinel and keep their
    sync numerics through the land fast path). `async_cfg` supplies the
    shared static knobs (delay model, jitter, staleness weighting) and
    forces async even for an all-sync grid.

    `method_batched=True` (default): methods that lower to `MethodParams`
    (`core.methods.batchable`) run as ONE compiled program — the method
    axis is vmapped on top of the seed vmap, so a 4-method × 5-seed grid
    pays one trace and one XLA compile instead of four. Histories match
    the per-method path to float tolerance with bit-identical selection
    masks (`tests/test_engine.py::test_method_batched_grid_matches_per_
    method`). A single-method grid, `method_batched=False`, or any
    structurally incompatible method keeps the per-method fallback: each
    method compiles its own seed-vmapped program (the bitwise-golden
    static dispatch)."""
    if (method_batched and len(methods) > 1
            and batchable(list(methods.values()))):
        return _run_grid_batched(
            model, fleet, cx, cy, cfg, methods, seeds=seeds, rounds=rounds,
            chunk_size=chunk_size, collect_per_device=collect_per_device,
            scenario=scenario, per_seed_fleets=per_seed_fleets,
            eval_fn=eval_fn, target_acc=target_acc, telemetry=telemetry,
            async_cfg=async_cfg)

    def cell_acfg(spec: MethodSpec) -> Optional[AsyncCfg]:
        if spec.aggregation == "async":
            base = async_cfg if async_cfg is not None else AsyncCfg(
                buffer_m=spec.buffer_m)
            return dataclasses.replace(base, buffer_m=spec.buffer_m,
                                       capacity=None, n_lands=None)
        return async_cfg

    return {name: run_campaign_batch(model, fleet, cx, cy, cfg, spec,
                                     seeds=seeds, rounds=rounds,
                                     chunk_size=chunk_size,
                                     collect_per_device=collect_per_device,
                                     scenario=scenario,
                                     per_seed_fleets=per_seed_fleets,
                                     eval_fn=eval_fn, target_acc=target_acc,
                                     telemetry=telemetry,
                                     async_cfg=cell_acfg(spec))
            for name, spec in methods.items()}
