"""The `setup.*` readers: set-up's spans as the program's tracer records
them, on synthetic span lists and on a whole run at CPU size; and a
run without tracing makes no tracer and registers no listener."""
import io
import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import setup_spans, spec
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer, tracing

ROOT = Path(__file__).resolve().parents[2]
READERS = ("setup.trace_lower_s", "setup.compile_load_s",
           "setup.first_chunk_device_s")


def _read(name):
    mod = spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                           "test_" + name.replace(".", "_"))
    return mod.read(None)


def _installed(spans):
    """A tracer holding `spans` ((name, start, end, args), seconds from
    the tracer's creation), left as the program's last tracer."""
    with tracing(Tracer()) as t:
        pass
    t0 = t.unix_ns_at_ts0 / 1e9
    for name, a, b, args in spans:
        t.record(name, t0 + a, t0 + b, **args)
    return t


SET_UP = [
    ("chunk", 1.0, 5.0, {"index": 0}),
    ("compile", 1.0, 2.2, {"index": 0}),
    # an outer trace holding an inner jit's trace, then its lowering
    ("jax.trace", 1.0, 1.5, {"fun_name": "chunk"}),
    ("jax.trace", 1.1, 1.2, {"fun_name": "inner"}),
    ("jax.lower", 1.5, 1.7, {"fun_name": "jit_chunk"}),
    ("jax.compile", 1.7, 2.2, {"fun_name": "jit_chunk", "cache": "hit"}),
    ("chunk.device", 2.2, 4.0, {"index": 0}),
    ("eval", 4.0, 5.0, {"index": 0}),
    ("jax.trace", 4.0, 4.25, {"fun_name": "evaluate"}),
    ("jax.compile", 4.3, 4.5, {"fun_name": "jit_evaluate",
                               "cache": "miss"}),
    # outside set-up: before the tracer was made, and in chunk 1
    ("jax.trace", -1.0, 0.5, {"fun_name": "early"}),
    ("chunk", 5.0, 7.0, {"index": 1}),
    ("jax.compile", 5.5, 6.5, {"fun_name": "jit_late", "cache": "off"}),
    ("chunk.device", 5.0, 7.0, {"index": 1}),
]


def test_readers_union_nested_spans_and_clip_to_set_up():
    _installed(SET_UP)
    got = {n: _read(n) for n in READERS}
    # (0, 0.5] of the early trace + [1.0, 1.7] + [4.0, 4.25]
    assert got["setup.trace_lower_s"] == pytest.approx(0.5 + 0.7 + 0.25)
    assert got["setup.compile_load_s"] == pytest.approx(0.5 + 0.2)
    assert got["setup.first_chunk_device_s"] == pytest.approx(1.8)


def test_breakdown_names_functions_cache_and_the_uncovered_part():
    t = _installed(SET_UP)
    v = setup_spans.from_events(t.events, t.unix_ns_at_ts0)
    assert v.end - v.start == pytest.approx(5.0)
    b = {k: float(x) for k, x in setup_spans.breakdown(v).items()}
    assert b["traced_s"] == pytest.approx(5.0)
    assert b["jax.compile:jit_chunk:hit"] == pytest.approx(0.5)
    assert b["jax.compile:jit_evaluate:miss"] == pytest.approx(0.2)
    assert b["jax.trace:early"] == pytest.approx(0.5)
    assert b["chunk.device:0"] == pytest.approx(1.8)
    assert "jax.compile:jit_late:off" not in b
    # (0.5, 1.0) is the only part of set-up no span covers
    assert b["uncovered_s"] == pytest.approx(0.5)
    log = io.StringIO()
    setup_spans._SEEN.clear()
    setup_spans.view(log=log)
    setup_spans.view(log=log)
    (line,) = log.getvalue().splitlines()
    assert line.startswith("setup_spans traced_s=5.0000 uncovered_s=0.5000")


@pytest.mark.parametrize("missing", ["chunk", "jax.", "chunk.device"])
def test_readers_give_none_when_their_spans_are_missing(missing):
    _installed([s for s in SET_UP if not s[0].startswith(missing)])
    got = {n: _read(n) for n in READERS}
    if missing == "chunk":     # no chunk 0: no set-up to clip to
        assert set(got.values()) == {None}
    elif missing == "jax.":
        assert got["setup.trace_lower_s"] is None
        assert got["setup.compile_load_s"] is None
        assert got["setup.first_chunk_device_s"] == pytest.approx(1.8)
    else:
        assert got["setup.first_chunk_device_s"] is None
        assert got["setup.compile_load_s"] == pytest.approx(0.7)


def test_readers_give_none_without_the_programs_tracer(monkeypatch):
    _installed(SET_UP)
    monkeypatch.setattr(obs_trace, "_LAST", None)
    assert {_read(n) for n in READERS} == {None}
    # a program whose obs layer has no `last_tracer` at all
    monkeypatch.delattr(obs_trace, "last_tracer")
    assert {_read(n) for n in READERS} == {None}


def test_the_readers_clip_to_one_warm_chunk():
    """Set-up ends with chunk 0's span only while every cell warms one
    chunk."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert spec.load_cell(w["name"], ROOT).traffic["warm_chunks"] == 1


def test_readers_read_a_whole_run(tiny_cells):
    """At CPU size, under a tracer: every set-up reader reads a positive
    value that fits in the run's set-up. (Their sum need not: a jit
    traced inside another compiles eager constants, so a compile can
    nest in a trace.)"""
    from bench import harness
    setup_spans._SEEN.clear()
    with tracing(Tracer()):
        out = harness.run(tiny_cells["cnn_mnist.s3500_k20"], 2147483777,
                          0.2, False, time.time(), require_tpu=False,
                          log=io.StringIO())
    got = [_read(n) for n in READERS]
    assert all(v is not None and v > 0 for v in got), got
    assert max(got) <= out["metrics"]["setup_s"]["value"]


PROBE = """
import io, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from jax._src import monitoring
from repro.obs import trace
made = []
init = trace.Tracer.__init__
def counting(self, *a, **kw):
    made.append(1)
    init(self, *a, **kw)
trace.Tracer.__init__ = counting
from bench import harness, spec
sys.path.insert(0, {tests!r})
from conftest import shrink
cell = shrink(spec.load_cell("cnn_mnist.s3500_k20"))
out = harness.run(cell, 2147483791, 0.2, False, time.time(),
                  require_tpu=False, log=io.StringIO())
ours = [f for f in monitoring.get_event_listeners()
        + monitoring.get_event_time_span_listeners()
        if getattr(f, "__module__", "") == "repro.obs.trace"]
print(out["correct"], len(made), len(ours), trace._LISTENING)
"""


def test_untraced_run_makes_no_tracer_and_registers_no_listener():
    code = PROBE.format(root=str(ROOT), src=str(ROOT / "src"),
                        tests=str(ROOT / "tests" / "bench"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split()[-4:] == ["True", "0", "0", "False"]
