"""Host-side span tracer writing Chrome trace-event JSON.

The tracer breaks a campaign's wall clock down by phase and serializes
the spans as Chrome trace events, loadable in Perfetto
(https://ui.perfetto.dev) or chrome://tracing:

    from repro.obs.trace import Tracer, set_tracer, span

    tracer = Tracer()
    set_tracer(tracer)
    with span("chunk", 0):
        with span("dispatch", 0):
            ...
    tracer.write("out.trace.json")

Spans that exist (`launch.engine` opens them in all three drivers):

  * `chunk` (one per chunk), holding `compile` or `dispatch` (the chunk
    call: trace, lower and compile on a fresh jit, else the enqueue),
    `history_drain`, `checkpoint`, `eval` and `health`; `transfer` after
    the last chunk.
  * `chunk.device`: from the chunk's dispatch returning to its outputs
    being ready, recorded by a waiter thread (`until_ready`) with the
    chunk span as `parent`. The main thread never waits for it.
  * `jax.trace`, `jax.lower`, `jax.compile`: JAX's own compile phases
    (jaxpr tracing, lowering to an MLIR module, XLA compile or
    persistent-cache load), each with the jitted `fun_name`;
    `jax.compile` carries `cache`: "hit", "miss" or "off". The first
    `Tracer` made registers the process's one pair of `jax.monitoring`
    listeners (time spans, cache events), which forward them to the
    installed tracer.

Design constraints:

  * Zero-overhead no-op default. The process-global tracer slot holds a
    `NullTracer` unless a run opted in (`run_fl --trace`,
    `engine_bench` phase rows, the benchmark's traced runs); its
    `span()` returns one shared do-nothing context manager — no
    allocation, no clock read, no lock — so the hot engine loops pay
    one attribute lookup + two empty method calls per span, and one
    attribute check per chunk for `chunk.device`, when tracing is off
    (gated by the `scan_round_S*` throughput rows in `check_regression`
    and the no-op micro-benchmark in `tests/test_obs.py`). A process
    that never makes a `Tracer` registers no listener.
  * One clock. Every event's `ts` is microseconds since the tracer was
    made, on `perf_counter`; `record()` takes `time.time()` seconds
    (JAX's time spans use them) and maps them through the anchor taken
    at creation, exported as `otherData.unix_ns_at_ts0`. The profiler's
    host plane (`.xplane.pb`) counts Unix nanoseconds from its
    `profile_start_time`, so `unix_ns_at_ts0 + 1000 * ts` lines a span
    up with it.
  * Thread-safe, with explicit parents. Events append under a lock and
    carry their thread id, so same-thread nesting renders by ts/dur
    containment in Perfetto; every event has an `id`, and one recorded
    from another thread names its enclosing span's id as `parent`.
  * Alignable with XLA profiler traces. `Tracer(xla=True)` additionally
    enters a `jax.profiler.TraceAnnotation` per span (a
    `StepTraceAnnotation` numbered by the chunk index for `chunk`, for
    XProf's step view), so host spans appear on the TraceMe timeline
    when a `jax.profiler.trace(...)` capture is active; the
    `jax.named_scope` phase annotations inside `core.round` give the
    device-side ops matching names.
"""
from __future__ import annotations

import itertools
import json
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

STEP_SPAN = "chunk"   # opened as a profiler step with `xla=True`

# JAX's compile-phase time spans, and the names they are recorded under
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# persistent-cache events JAX records inside a backend compile
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
}


class _NullSpan:
    """Shared do-nothing context manager (the no-op tracer's span)."""
    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracing disabled: every span is the shared no-op context."""
    enabled = False

    def span(self, name: str, index: Optional[int] = None, **args):
        return _NULL_SPAN

    def record(self, name: str, start: float, end: float,
               parent: Optional[int] = None, **args) -> None:
        return None

    @property
    def events(self) -> List[Dict[str, Any]]:
        return []

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {}


class _Span:
    """One live span: records a Chrome 'X' (complete) event on exit."""
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann", "id")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = None
        self.id = next(tracer._ids)

    def __enter__(self):
        tr = self._tracer
        if tr._annotation is not None:
            if self._name == STEP_SPAN and "index" in self._args:
                self._ann = tr._step(self._name,
                                     step_num=self._args["index"])
            else:
                self._ann = tr._annotation(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tracer
        tr._append(self._name, (self._t0 - tr._epoch_ns) / 1e3,
                   (t1 - self._t0) / 1e3, self.id, None, self._args)
        return False


class Tracer:
    """Collects host spans; serializes to Chrome trace-event JSON.

    `span(name, index)` is a context manager; spans nest freely (the
    trace format reconstructs the stack from ts/dur containment per
    thread). `record(name, start, end)` adds a span known only after
    its end, on `time.time()` seconds. `xla=True` mirrors every
    context-manager span into a `jax.profiler.TraceAnnotation` so a
    concurrent XLA profiler capture shows the same phase boundaries."""
    enabled = True

    def __init__(self, *, xla: bool = False):
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        p0 = time.perf_counter_ns()
        self.unix_ns_at_ts0 = time.time_ns()
        self._epoch_ns = (p0 + time.perf_counter_ns()) // 2
        self._annotation = self._step = None
        if xla:
            import jax.profiler
            self._annotation = jax.profiler.TraceAnnotation
            self._step = jax.profiler.StepTraceAnnotation
        self._waits: Optional[queue.Queue] = None
        _listen_to_jax()

    def span(self, name: str, index: Optional[int] = None, **args):
        if index is not None:
            args["index"] = index
        return _Span(self, name, args)

    def record(self, name: str, start: float, end: float,
               parent: Optional[int] = None, **args) -> int:
        """Add a span from `start` to `end`, `time.time()` seconds, on
        the calling thread; returns its id."""
        i = next(self._ids)
        ts = (start - self.unix_ns_at_ts0 / 1e9) * 1e6
        self._append(name, ts, (end - start) * 1e6, i, parent, args)
        return i

    def until_ready(self, name: str, outputs, parent: Optional[int] = None,
                    **args) -> None:
        """Record `name` from now until every array of `outputs` is
        ready, from one waiter thread; the caller does not wait. Pass
        outputs no later call donates."""
        if self._waits is None:
            self._waits = queue.Queue()
            threading.Thread(target=self._wait_loop, daemon=True,
                             name="repro-trace-wait").start()
        self._waits.put((name, outputs, time.time(), parent, args))

    def _wait_loop(self) -> None:
        import jax
        while True:
            name, outputs, start, parent, args = self._waits.get()
            try:
                jax.block_until_ready(outputs)
                self.record(name, start, time.time(), parent, **args)
            except Exception:  # noqa: BLE001 — the engine's own read of
                pass           # these outputs raises on the main thread
            finally:
                del outputs
                self._waits.task_done()

    def _append(self, name: str, ts: float, dur: float, i: int,
                parent: Optional[int], args: Dict[str, Any]) -> None:
        ev = {"name": name, "ph": "X", "ts": ts, "dur": dur,
              "pid": os.getpid(), "tid": threading.get_ident(), "id": i}
        if parent is not None:
            ev["parent"] = parent
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    @property
    def events(self) -> List[Dict[str, Any]]:
        """Every event so far, once the pending `until_ready` waits have
        been recorded."""
        if self._waits is not None:
            self._waits.join()
        with self._lock:
            return list(self._events)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name aggregates: {name: {count, total_s, mean_s,
        max_s}} — the phase-attribution table `engine_bench` and
        `run_fl --trace` report."""
        out: Dict[str, Dict[str, float]] = {}
        for ev in self.events:
            s = out.setdefault(ev["name"],
                               {"count": 0, "total_s": 0.0, "max_s": 0.0})
            dur = ev["dur"] / 1e6
            s["count"] += 1
            s["total_s"] += dur
            s["max_s"] = max(s["max_s"], dur)
        for s in out.values():
            s["mean_s"] = s["total_s"] / max(s["count"], 1)
        return out

    def to_chrome(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        return {"traceEvents": self.events, "displayTimeUnit": "ms",
                "otherData": {"unix_ns_at_ts0": self.unix_ns_at_ts0}}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# Process-global tracer slot. Default: tracing off (NullTracer).
_TRACER = NullTracer()
_LAST: Optional[Tracer] = None


def get_tracer():
    return _TRACER


def last_tracer() -> Optional[Tracer]:
    """The tracer most recently installed that records (None if none
    was), kept after it is uninstalled so its events can be read once
    the traced region is over."""
    return _LAST


def set_tracer(tracer) -> Any:
    """Install `tracer` globally; returns the previous tracer so callers
    can restore it (`tracing(...)` does this automatically)."""
    global _TRACER, _LAST
    prev = _TRACER
    _TRACER = tracer
    if tracer.enabled:
        _LAST = tracer
    return prev


def span(name: str, index: Optional[int] = None, **args):
    """Open a span on the current global tracer (no-op by default)."""
    return _TRACER.span(name, index, **args)


def until_ready(name: str, outputs, parent, **args) -> None:
    """`Tracer.until_ready` on the current global tracer, under the
    enclosing span `parent`; one attribute check when tracing is off."""
    if _TRACER.enabled:
        _TRACER.until_ready(name, outputs, parent.id, **args)


# ------------------------------------------------ JAX's compile phases

_LISTENING = False
_LISTEN_LOCK = threading.Lock()
_CACHE_SEEN = threading.local()   # (time, outcome) per compiling thread


def _on_jax_event(event: str, **_) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None and _TRACER.enabled:
        seen = getattr(_CACHE_SEEN, "events", None)
        if seen is None:
            seen = _CACHE_SEEN.events = []
        seen.append((time.time(), outcome))


def _on_jax_time_span(event: str, start: float, end: float, **kw) -> None:
    name = _JAX_SPANS.get(event)
    if name is None or not _TRACER.enabled:
        return
    args = {"fun_name": kw.get("fun_name")}
    if name == "jax.compile":
        seen = getattr(_CACHE_SEEN, "events", None) or []
        _CACHE_SEEN.events = []
        inside = {o for t, o in seen if start <= t <= end}
        args["cache"] = ("hit" if "hit" in inside
                         else "miss" if "miss" in inside else "off")
    _TRACER.record(name, start, end, **args)


def _listen_to_jax() -> None:
    """Register the process's one pair of `jax.monitoring` listeners
    (the first `Tracer` made does this); they forward JAX's compile
    phases to whichever tracer is installed, and do nothing while it
    is the `NullTracer`."""
    global _LISTENING
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_jax_event)
        jax.monitoring.register_event_time_span_listener(_on_jax_time_span)
        _LISTENING = True


class tracing:
    """Context manager installing a tracer for a scoped region:

        with tracing(Tracer()) as t:
            run_fl(...)
        t.write("out.trace.json")
    """

    def __init__(self, tracer):
        self._tracer = tracer
        self._prev = None

    def __enter__(self):
        self._prev = set_tracer(self._tracer)
        return self._tracer

    def __exit__(self, *exc):
        set_tracer(self._prev)
        return False


def format_span_table(summary: Dict[str, Dict[str, float]]) -> str:
    """Fixed-width terminal table of a `Tracer.summary()` dict, widest
    total first."""
    if not summary:
        return "(no spans recorded)"
    rows = sorted(summary.items(), key=lambda kv: -kv[1]["total_s"])
    w = max(len("span"), *(len(k) for k in summary))
    lines = [f"{'span':<{w}}  {'count':>5}  {'total_s':>9}  "
             f"{'mean_s':>9}  {'max_s':>9}"]
    for name, s in rows:
        lines.append(f"{name:<{w}}  {s['count']:>5d}  {s['total_s']:>9.3f}"
                     f"  {s['mean_s']:>9.4f}  {s['max_s']:>9.4f}")
    return "\n".join(lines)
