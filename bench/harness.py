"""One run of one cell: set-up, the measured window, the comparison.

Set-up makes the fleet, the client data and the global model from the
seed, builds the program's model and configuration from the cell's
files, and calls the program's public campaign entry point,
`launch.engine.run_rounds`, once, with an accuracy eval at every chunk
boundary (as `run_fl` does) and the engine's defaults (dense history,
donated carry). Its first chunk compiles or loads the chunk program from
the cache; the traffic's `warm_chunks` first chunks and their evals are
set-up. The window opens when the last of those evals returns and
closes at the first chunk boundary after `seconds`, timed on this
process's clock: the window holds whole chunks, their history drains
and their evals. The eval at each boundary
also closes the campaign (by reporting an accuracy above the target)
once the window is over, so one call holds set-up and window.

A tap on the engine's chunk function copies the carry (model, fleet
state, round key) to the host before the chunks the traffic names, so
that the reference can recompute their first round from the same carry
once the window has closed (`reference.py`, `compare.py`). Where the
configuration declares `trainable`, the tap copies the trainable leaves
only, and a fingerprint of each frozen leaf made on the device; the
reference rebuilds the frozen leaves from the init seed after the
window, and `compare.py` counts those the program moved.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import METHODS, FLConfig, make_eval_fn
from repro.core.policy import PolicyCfg
from repro.launch import engine
from repro.launch.engine import EngineCfg
from repro.models import fl_models
from repro.obs.trace import Tracer, tracing
from repro.sim.devices import DeviceFleet
from repro.sim.dynamics import get_scenario

from bench import compare, reference, spec
from bench import trace as trace_mod
from bench.data import make_client_data
from bench.fleet import FIELDS, draw_fleet
from bench.peaks import peaks_for

STOP_ACC = 2.0          # an eval above any accuracy ends the campaign
TARGET_ACC = 1.5
MAX_CHUNKS = 4096
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def configure_cache(root) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for every program of any size, so that later runs find
    every program the first one compiled."""
    cache = root / ".bench_cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ----------------------------------------------------------- seeds

def derive_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each draw, from any whole seed."""
    w = np.random.SeedSequence(seed & ((1 << 64) - 1)).generate_state(
        4, dtype=np.uint32)
    m = 0x7FFFFFFF
    return {"fleet": int(w[0]), "data": int(w[1]) & m,
            "init": int(w[2]) & m, "loop": int(w[3]) & m}


# ----------------------------------------------------------- compiles

class _CompileCounter:
    """Counts XLA compiles and persistent-cache loads in this process."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, name, **_):
        if name in COMPILE_EVENTS:
            self.n += 1

    def _dur(self, name, _secs, **_):
        if name in COMPILE_EVENTS:
            self.n += 1


_COUNTER: Optional[_CompileCounter] = None


def compile_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


# ----------------------------------------------------------- tap

@dataclasses.dataclass
class Snapshot:
    params: dict
    state: Dict[str, np.ndarray]
    key: np.ndarray
    round: int
    fingerprint: Optional[np.ndarray] = None   # of the frozen leaves


class Tap:
    """Wraps the engine's chunk-function factory: passes every call
    through, and copies the carry to the host before the chunks in
    `chunks` (0 = the first): with `trainable` given, its leaves and the
    frozen leaves' fingerprint only. Keeps the jitted function and the
    abstract arguments of its calls, for the program's compiled text."""

    def __init__(self, engine_module, chunks, trainable=None):
        self.engine = engine_module
        self.chunks = set(chunks)
        self.trainable = trainable
        self.snaps: Dict[int, Snapshot] = {}
        self.fn = None
        self.abstract = None
        self.calls = 0

    @contextlib.contextmanager
    def installed(self):
        real = self.engine.make_chunk_fn

        def make_chunk_fn(*a, **kw):
            fn = real(*a, **kw)

            def call(*args):
                self._before(fn, args)
                return fn(*args)

            return call

        self.engine.make_chunk_fn = make_chunk_fn
        try:
            yield self
        finally:
            self.engine.make_chunk_fn = real

    def _before(self, fn, args):
        if self.fn is None:
            self.fn = fn
            # an argument JAX may place freely (uncommitted) keeps no
            # sharding, as in the call
            self.abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None), args)
        if self.calls in self.chunks:
            params, state, _, _, _, _, key, start = args[:8]
            fp = None
            if self.trainable is not None:
                frozen, params = reference.split(params, self.trainable)
                fp = compare.fingerprint(frozen)
            host = jax.device_get((params, state._asdict(), key, start,
                                   fp))
            self.snaps[self.calls] = Snapshot(host[0], host[1],
                                              np.asarray(host[2]),
                                              int(host[3]), host[4])
        self.calls += 1

    def compiled(self):
        return self.fn.lower(*self.abstract).compile()


# ----------------------------------------------------------- window

class Window:
    """The eval at each chunk boundary: evaluates the model, and opens
    and closes the measured window (and the profiler, when tracing).
    The first `warm` chunks are set-up; the window opens at the eval
    after them."""

    def __init__(self, evaluate, seconds: float, warm: int,
                 min_chunks: int, trace_chunks: int, t0: float,
                 prof_dir: Optional[str], counter: _CompileCounter):
        self.evaluate = evaluate
        self.seconds = seconds
        self.warm = warm
        self.min_chunks = min_chunks
        self.trace_chunks = trace_chunks
        self.t0 = t0
        self.prof_dir = prof_dir
        self.counter = counter
        self.calls = 0
        self.setup_s = None
        self.t_start = self.t_end = None
        self.chunks = 0          # chunks in the window
        self.compiles = 0
        self.accs: List[float] = []
        self.bounds: List[float] = []   # chunk boundaries in the window
        self._ann = None
        self._c0 = 0

    def __call__(self, params):
        acc = float(self.evaluate(params))
        self.accs.append(acc)
        j = self.calls + 1 - self.warm     # chunks done in the window
        self.calls += 1
        if j < 0:
            return acc
        if j == 0:
            self.setup_s = time.time() - self.t0
            if self.prof_dir is not None:
                jax.profiler.start_trace(self.prof_dir)
                self._ann = jax.profiler.TraceAnnotation(
                    trace_mod.WINDOW_SPAN)
                self._ann.__enter__()
            self._c0 = self.counter.n
            self.t_start = time.perf_counter()
            self.bounds.append(self.t_start)
            return acc
        self.bounds.append(time.perf_counter())
        if self._ann is not None and j == self.trace_chunks:
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()
        if self.calls - 1 < self.min_chunks:
            return acc
        if self.prof_dir is not None:
            over = j >= self.trace_chunks
        else:
            over = time.perf_counter() - self.t_start >= self.seconds
        if not over:
            return acc
        self.t_end = time.perf_counter()
        self.chunks = j
        self.compiles = self.counter.n - self._c0
        return STOP_ACC


# ----------------------------------------------------------- the run

def _say(log, **kw):
    print(" ".join(f"{k}={v}" for k, v in kw.items()), file=log,
          flush=True)


def _program_model(cell):
    p = cell.config["program"]
    return getattr(fl_models, p["constructor"])(*p["args"], **p["kwargs"])


def _fl_config(traffic: dict):
    fl = traffic["fl"]
    return FLConfig(
        n_select=int(traffic["select"]), alpha=fl["alpha"], beta=fl["beta"],
        T_round=fl["T_round"], batch_size=fl["batch_size"],
        probe_size=fl["probe_size"], lr=fl["lr"],
        policy=PolicyCfg(H0=fl["H0"], H_max=fl["H_max"], dH=fl["dH"],
                         psi0=fl["psi0"], s_ref=fl["s_ref"],
                         eps_th=fl["eps_th"]),
        kernel_backend="auto")


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t0: float, *, require_tpu: bool = True, log=sys.stderr,
        prof_dir: Optional[str] = None, control: bool = False) -> dict:
    """Run the cell once; returns the result object (the last line).
    `control=True` also reads the control (the reference in bfloat16)
    against the reference on the same rounds, under `control_checks`."""
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     "device(s)")
    devs = devices[:cell.chips]
    kind = devs[0].device_kind
    marks = {"devices": time.time() - t0}

    tr, mc = cell.traffic, cell.config
    S, K, chunk = int(tr["clients"]), int(tr["select"]), int(tr["chunk"])
    if tr["method"] != "rewafl" or tr["aggregation"] != "sync" \
            or tr["scenario"] != "static-paper":
        raise ValueError("the reference round covers sync REWAFL on "
                         "static-paper only")
    seeds = derive_seeds(seed)
    ref_model = spec.reference_model(cell)
    counter = compile_counter()

    fleet_np = draw_fleet(S, seeds["fleet"], **tr["fleet"])
    fleet = DeviceFleet(**{k: jnp.asarray(fleet_np[k]) for k in FIELDS})
    dcfg = mc["data"]
    cx, cy, tx, ty = make_client_data(
        jax.random.PRNGKey(seeds["data"]), dcfg["kind"], S,
        int(tr["per_client"]), float(tr["lam"]), int(dcfg["n_test"]), dcfg)
    init = jax.jit(lambda k: ref_model.init(k, mc))
    params0 = init(jax.random.PRNGKey(seeds["init"]))
    trainable = mc.get("trainable")
    frozen0, train0 = reference.split(params0, trainable)
    leaf_sizes = [int(np.prod(a.shape)) for a in jax.tree.leaves(train0)]
    fp0 = None
    if trainable is not None:
        fp0 = np.asarray(compare.fingerprint(frozen0))
    del frozen0, train0
    jax.block_until_ready((cx, params0))
    marks["inputs"] = time.time() - t0
    model = _program_model(cell)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), params0)
    if want != have:
        raise ValueError(f"{mc['name']}: the reference's parameters "
                         f"{have} do not match the program's {want}")
    evaluate = make_eval_fn(model, tx, ty)
    compare_chunks = sorted(int(c) for c in tr["compare_chunks"])
    trace_chunks = int(tr["trace_chunks"]) if trace else 0
    tap = Tap(engine, compare_chunks, trainable)
    warm = int(tr["warm_chunks"])
    window = Window(evaluate, seconds, warm, max(compare_chunks + [warm]),
                    trace_chunks, t0, prof_dir if trace else None, counter)
    tracer = contextlib.nullcontext()
    if trace:
        tracer = tracing(Tracer(xla=True))
    with tap.installed(), tracer:
        res = engine.run_rounds(
            model, fleet, cx, cy, _fl_config(tr), METHODS[tr["method"]],
            rounds=chunk * MAX_CHUNKS,
            key=jax.random.PRNGKey(seeds["loop"]), params=params0,
            ecfg=EngineCfg(chunk_size=chunk),
            eval_fn=window, target_acc=TARGET_ACC,
            scenario=get_scenario(tr["scenario"]))
    if window.t_end is None:
        raise RuntimeError("the campaign ended before the window closed")
    marks["window_start"] = window.setup_s
    marks["window_end"] = window.setup_s + window.t_end - window.t_start
    marks["campaign_done"] = time.time() - t0
    wall = window.t_end - window.t_start
    rounds = chunk * window.chunks
    stats = [d.memory_stats() or {} for d in devs]
    peak = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    hist = {k: np.asarray(v) for k, v in res.history.items()}
    del res, params0
    _say(log, platform=devs[0].platform, device_kind=repr(kind),
         device_count=len(devs), compiles_in_window=window.compiles,
         peak_bytes_per_device=peak)
    compiled = tap.compiled()
    ma = compiled.memory_analysis()
    _say(log, chunk_program_memory_analysis="", **{
        k: getattr(ma, k, None) for k in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")})
    walls = np.diff(window.bounds)
    _say(log, setup_s=f"{window.setup_s:.4f}", window_s=f"{wall:.4f}",
         window_chunks=window.chunks, rounds=rounds, S=S, K=K,
         chunk_s_min=f"{walls.min():.4f}",
         chunk_s_median=f"{np.median(walls):.4f}",
         chunk_s_max=f"{walls.max():.4f}",
         accuracy_last=f"{window.accs[-1]:.4f}")

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": max(peak)}
    out = {"correct": None, "attempted": None, "failed": None}
    if trace:
        out["metrics"], breakdown, busy, win_s = _per_layer(
            cell, compiled.as_text(), prof_dir, hist, warm, trace_chunks,
            chunk, kind, len(devs), ref_model, leaf_sizes)
        device.update(busy_s=busy, window_s=win_s)
    else:
        out["metrics"] = {
            "device_rounds_per_s": {"value": S * rounds / wall,
                                    "unit": "device-rounds/s"},
            "setup_s": {"value": window.setup_s, "unit": "s"}}
    out["device"] = device
    if trace:
        out["breakdown"] = breakdown

    if trainable is not None:
        # the tap kept no frozen leaf and the carry was donated: they
        # come again from the init seed, checked against set-up's
        # fingerprint
        frozen = reference.split(init(jax.random.PRNGKey(seeds["init"])),
                                 trainable)[0]
        if not np.array_equal(np.asarray(compare.fingerprint(frozen)),
                              fp0):
            raise RuntimeError("the frozen leaves rebuilt from the init "
                               "seed differ from set-up's")
        for s in tap.snaps.values():
            s.params = reference.merge(frozen, s.params)
    if control:
        out["control_checks"] = compare.check(
            cell, ref_model, fleet_np, cx, cy, tap.snaps, hist, log=log,
            who="control")[0]
    checks, bad_rounds = compare.check(cell, ref_model, fleet_np, cx, cy,
                                       tap.snaps, hist, log=log, fp0=fp0)
    checks["compiles"] = {"value": window.compiles, "limit": 0}
    out["correct"] = not any(compare.over(c["value"], c["limit"])
                             for c in checks.values())
    out["attempted"] = len(tap.snaps)
    out["failed"] = bad_rounds
    marks["compared"] = time.time() - t0
    _say(log, seconds_since_start="", **{k: f"{v:.3f}"
                                         for k, v in marks.items()})
    for name, c in checks.items():
        _say(log, check=name, value=c["value"], limit=c["limit"])
    out["checks"] = checks
    return out


def _per_layer(cell, hlo_text, prof_dir, hist, warm, traced_chunks, chunk,
               kind, n_chips, ref_model, leaf_sizes):
    hlo = trace_mod.parse_hlo(hlo_text)
    tr = trace_mod.load(trace_mod.find_xplane(prof_dir), hlo)
    rounds = traced_chunks * chunk
    lo = warm * chunk                    # the traced chunks' rounds
    hi = lo + rounds
    t = cell.traffic
    ctx = SimpleNamespace(
        trace=tr, hlo=hlo, rounds=rounds, evals=traced_chunks,
        S=int(t["clients"]), K=int(t["select"]),
        probe=int(t["fl"]["probe_size"]), batch=int(t["fl"]["batch_size"]),
        n_test=int(cell.config["data"]["n_test"]),
        forward_flops=ref_model.forward_flops(cell.config),
        train_flops=ref_model.train_flops(cell.config),
        history={k: hist[k][lo:hi] for k in ("selected", "mean_H_selected")},
        leaf_sizes=leaf_sizes,
        peaks=peaks_for(kind), device_kind=kind, n_chips=n_chips)
    metrics = {}
    readers = spec.metric_readers(cell)
    for m in cell.per_layer:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {"device_ops": trace_mod.top_ops(tr),
                 "idle_gaps": trace_mod.idle_gaps(tr)}
    return metrics, breakdown, trace_mod.busy_s(tr), tr.window_s


def result_line(out: dict) -> str:
    return json.dumps(out)
