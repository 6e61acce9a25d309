"""Set-up as the program's tracer saw it, for the `setup.*` readers.

In a `--trace 1` run the harness installs the program's
`Tracer(xla=True)` around its one `run_rounds` call. That tracer
records JAX's compile phases (`jax.trace`, `jax.lower`, `jax.compile`,
each with its `fun_name`, and the cache outcome of a compile), the
engine's spans, and each chunk's device time (`chunk.device`, from the
dispatch returning to the chunk's outputs being ready).

The readers clip every span to set-up as the tracer sees it: from the
tracer's creation (after imports, TPU start and the inputs) to the end
of chunk 0's span, which holds the first chunk boundary's eval; the
window opens when that eval returns (`warm_chunks` is 1 in every
cell). Spans come from `repro.obs.trace.last_tracer()`, on the
tracer's own clock anchor; where the program has no such tracer, or the
run made none, every reader returns None.

The first view of a tracer also prints one line on standard error:
set-up's traced seconds broken down by span, `fun_name` and cache
outcome (unions, so nested jits count once), and the part that no span
covers.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace import union

Interval = Tuple[float, float]
TOP = 16              # labels the breakdown line names one by one


@dataclasses.dataclass
class Span:
    name: str
    start: float          # Unix seconds, clipped to set-up
    end: float
    args: dict


@dataclasses.dataclass
class SetupView:
    start: float          # Unix seconds: the tracer's creation
    end: float            # the end of chunk 0's span
    spans: List[Span]     # every span that overlaps set-up, clipped


def from_events(events: Iterable[dict],
                unix_ns_at_ts0: int) -> Optional[SetupView]:
    """The set-up view of a tracer's Chrome events, or None when they
    hold no span of chunk 0."""
    t0 = unix_ns_at_ts0 / 1e9
    raw = [(e["name"], t0 + e["ts"] / 1e6,
            t0 + (e["ts"] + e["dur"]) / 1e6, e.get("args", {}))
           for e in events if e.get("ph") == "X"]
    ends = [b for n, _, b, a in raw if n == "chunk" and a.get("index") == 0]
    if not ends:
        return None
    end = ends[0]
    spans = [Span(n, max(a, t0), min(b, end), args)
             for n, a, b, args in raw if a < end and b > t0]
    return SetupView(t0, end, spans)


def union_s(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def names_s(v: SetupView, names: Tuple[str, ...]) -> Optional[float]:
    """Seconds of set-up under any span named in `names` (a union), or
    None when set-up holds none of them."""
    iv = [(s.start, s.end) for s in v.spans if s.name in names]
    return union_s(iv) if iv else None


def breakdown(v: SetupView) -> Dict[str, str]:
    """{label: seconds} of set-up by span name, `fun_name` and cache
    outcome (the TOP largest, then the count and summed seconds of the
    rest), with the traced interval and the part no span covers."""
    groups: Dict[str, List[Interval]] = {}
    for s in v.spans:
        key = s.name
        if s.name.startswith("jax."):
            key += ":" + str(s.args.get("fun_name"))
            if "cache" in s.args:
                key += ":" + s.args["cache"]
        elif s.name == "chunk.device":
            key += ":" + str(s.args.get("index"))
        groups.setdefault(key, []).append((s.start, s.end))
    covered = union_s((s.start, s.end) for s in v.spans)
    out = {"traced_s": f"{v.end - v.start:.4f}",
           "uncovered_s": f"{v.end - v.start - covered:.4f}"}
    ranked = sorted(((union_s(iv), k) for k, iv in groups.items()),
                    reverse=True)
    for t, key in ranked[:TOP]:
        out[key] = f"{t:.4f}"
    if len(ranked) > TOP:
        out["rest_n"] = str(len(ranked) - TOP)
        out["rest_sum_s"] = f"{sum(t for t, _ in ranked[TOP:]):.4f}"
    return out


_SEEN: Dict[str, object] = {}


def view(log=None) -> Optional[SetupView]:
    """The set-up view of the program's last tracer, printing its
    breakdown line the first time; None when there is none."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    last = getattr(trace, "last_tracer", None)
    tracer = last() if last is not None else None
    if tracer is None:
        return None
    if _SEEN.get("tracer") is not tracer:
        v = from_events(tracer.events, tracer.unix_ns_at_ts0)
        _SEEN.update(tracer=tracer, view=v)
        if v is not None:
            print("setup_spans " + " ".join(
                f"{k}={x}" for k, x in breakdown(v).items()),
                file=log or sys.stderr, flush=True)
    return _SEEN["view"]
