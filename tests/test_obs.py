"""Observability-layer tests (`repro.obs`): span tracer nesting and
Chrome trace-event round-trip, zero-overhead no-op mode, the `repro`
logger severity routing, Gini / chunk-sample / report units for the
fleet-health monitors, and end-to-end flat-battery alarm behavior —
the alarm must trip on a drain-heavy scenario and stay silent on
overnight-charging."""
import dataclasses
import io
import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import FLConfig, METHODS
from repro.core.metrics import TelemetryCfg
from repro.core.policy import PolicyCfg
from repro.launch import engine as eng
from repro.launch.fl_run import build_task
from repro.models.fl_models import make_fl_model
from repro.obs.health import (HealthCfg, HealthReport, chunk_sample,
                              finalize_report, format_health_table, gini,
                              with_health_specs)
from repro.obs.log import configure_logging, get_logger
from repro.obs.trace import (NullTracer, Tracer, _NULL_SPAN,
                             format_span_table, get_tracer, last_tracer,
                             set_tracer, span, tracing)
from repro.sim.devices import build_fleet
from repro.sim.dynamics import get_scenario

N, K = 10, 4


# ------------------------------------------------------------- tracer

def test_span_nesting_containment():
    """Nested spans record 'X' events whose [ts, ts+dur] intervals nest —
    the containment Perfetto reconstructs the stack from."""
    t = Tracer()
    with t.span("outer", 0):
        with t.span("inner", 0):
            time.sleep(0.002)
    evs = {e["name"]: e for e in t.events}
    assert set(evs) == {"outer", "inner"}
    o, i = evs["outer"], evs["inner"]
    assert o["ph"] == i["ph"] == "X"
    assert o["tid"] == i["tid"] == threading.get_ident()
    assert i["ts"] >= o["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert i["dur"] >= 2000.0  # slept 2 ms, recorded in µs


def test_span_args_and_index_serialized():
    t = Tracer()
    with t.span("chunk", 3, rounds=5, start=15):
        pass
    (ev,) = t.events
    assert ev["args"] == {"index": 3, "rounds": 5, "start": 15}


def test_chrome_json_round_trip(tmp_path):
    """write() emits Perfetto-loadable Chrome trace-event JSON."""
    t = Tracer()
    with t.span("a", 0):
        with t.span("b"):
            pass
    path = tmp_path / "out.trace.json"
    t.write(str(path))
    d = json.loads(path.read_text())
    assert d["displayTimeUnit"] == "ms"
    assert d["otherData"]["unix_ns_at_ts0"] == t.unix_ns_at_ts0
    evs = d["traceEvents"]
    assert {e["name"] for e in evs} == {"a", "b"}
    assert all("ts" in e and "pid" in e and "tid" in e for e in evs)
    assert len({e["id"] for e in evs}) == 2


def test_summary_aggregates_per_name():
    t = Tracer()
    for _ in range(3):
        with t.span("work"):
            time.sleep(0.001)
    s = t.summary()["work"]
    assert s["count"] == 3
    assert s["total_s"] >= 0.003
    assert s["max_s"] <= s["total_s"]
    assert s["mean_s"] == pytest.approx(s["total_s"] / 3)
    table = format_span_table(t.summary())
    assert table.splitlines()[0].startswith("span")
    assert "work" in table
    assert format_span_table({}) == "(no spans recorded)"


def test_tracing_context_installs_and_restores():
    prev = get_tracer()
    t = Tracer()
    with tracing(t) as active:
        assert active is t and get_tracer() is t
        with span("via_module", 1):
            pass
    assert get_tracer() is prev
    assert [e["name"] for e in t.events] == ["via_module"]


def test_null_tracer_is_shared_singleton():
    """The no-op tracer allocates nothing per span: every call returns
    the one shared do-nothing context manager."""
    nt = NullTracer()
    assert nt.span("a") is nt.span("b") is _NULL_SPAN
    assert not nt.enabled and Tracer().enabled
    assert nt.events == [] and nt.summary() == {}
    assert nt.record("x", 0.0, 1.0) is None  # no-op, no error


def test_record_lands_where_a_span_over_the_same_interval_does():
    """`record()` maps `time.time()` seconds onto the span clock through
    the creation anchor: within 1 ms of a context-manager span."""
    t = Tracer()
    with t.span("cm"):
        a = time.time()
        time.sleep(0.01)
        b = time.time()
    i = t.record("rec", a, b, parent=7, note="x")
    evs = {e["name"]: e for e in t.events}
    cm, rec = evs["cm"], evs["rec"]
    assert rec["id"] == i and rec["parent"] == 7
    assert rec["args"] == {"note": "x"}
    assert abs(rec["ts"] - cm["ts"]) < 1000.0
    assert abs(rec["ts"] + rec["dur"] - cm["ts"] - cm["dur"]) < 1000.0


def _tiny_jit_once(n):
    def tiny_fn(x):
        return x * n + 1.0
    return jax.jit(tiny_fn)(jax.numpy.ones((3,))).block_until_ready()


def test_jax_compile_phases_recorded_as_spans():
    """A fresh jit under an installed tracer yields JAX's trace, lower and
    compile phases as spans named for its function; the compile names
    its persistent-cache outcome."""
    with tracing(Tracer()) as t:
        _tiny_jit_once(3.0)
    evs = [e for e in t.events if e["name"].startswith("jax.")]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e["args"])
    assert {"jax.trace", "jax.lower", "jax.compile"} <= set(by)
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert any("tiny_fn" in str(a["fun_name"]) for a in by[name])
    assert all(a["cache"] in ("hit", "miss", "off")
               for a in by["jax.compile"])
    assert "compile" not in {e["name"] for e in t.events}


def test_null_tracer_records_no_compile_phases():
    """With the default NullTracer installed the listener forwards
    nothing, to no tracer made before or installed after."""
    made = Tracer()
    prev = set_tracer(NullTracer())
    try:
        _tiny_jit_once(5.0)
    finally:
        set_tracer(prev)
    assert made.events == []
    with tracing(Tracer()) as later:
        pass
    assert later.events == []


def test_last_tracer_outlives_its_region():
    with tracing(Tracer()) as t:
        pass
    with tracing(NullTracer()):
        pass
    assert last_tracer() is t


def test_xla_chunk_span_is_a_profiler_step_on_the_anchor_clock(tmp_path):
    """With `xla=True` the `chunk` span is a profiler step on the host
    plane, named `chunk` as before, and the tracer's anchor puts its
    start within 1 ms of the capture's own clock."""
    from jax.profiler import ProfileData
    t = Tracer(xla=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("chunk", 4):
            with t.span("dispatch", 4):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    start_ns = None
    host = []
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_ns = stats["profile_start_time"]
        if plane.name.startswith("/host"):
            host += [e for ln in plane.lines for e in ln.events]
    chunk = [e for e in host if e.name == "chunk"]
    assert len(chunk) == 1 and dict(chunk[0].stats)["step_num"] == 4
    assert [e.name for e in host if e.name == "dispatch"] == ["dispatch"]
    ev = next(e for e in t.events if e["name"] == "chunk")
    mine = t.unix_ns_at_ts0 + 1e3 * ev["ts"]
    assert abs(start_ns + chunk[0].start_ns - mine) < 1e6


def test_noop_span_overhead_is_negligible():
    """With the default NullTracer installed, the module-level span()
    the engine hot loops call must stay in the tens-of-nanoseconds
    regime — budget 5 µs/call to stay robust on loaded CI runners."""
    prev = set_tracer(NullTracer())
    try:
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with span("chunk", 0):
                pass
        per_call = (time.perf_counter() - t0) / n
    finally:
        set_tracer(prev)
    assert per_call < 5e-6


# ------------------------------------------------------------- logging

def test_logger_severity_routing():
    buf = io.StringIO()
    configure_logging(stream=buf)
    log = get_logger("obs_test")
    assert log.name == "repro.obs_test"
    log.info("plain chatter")
    log.warning("alarm fired")
    log.debug("hidden detail")
    out = buf.getvalue()
    assert "plain chatter\n" in out          # INFO prints bare
    assert "WARNING: alarm fired" in out     # WARNING keeps its prefix
    assert "hidden detail" not in out        # DEBUG hidden at default

    quiet = io.StringIO()
    configure_logging(quiet=True, stream=quiet)
    log.info("suppressed")
    log.warning("still visible")
    assert "suppressed" not in quiet.getvalue()
    assert "WARNING: still visible" in quiet.getvalue()

    verbose = io.StringIO()
    configure_logging(verbosity=1, stream=verbose)
    log.debug("now shown")
    assert "now shown" in verbose.getvalue()
    # idempotent: repeated configuration never stacks handlers
    assert len(logging.getLogger("repro").handlers) == 1
    configure_logging()  # restore defaults for other tests


# ------------------------------------------------------------- health units

def test_gini_bounds_and_ordering():
    assert gini([]) == 0.0
    assert gini([0, 0, 0]) == 0.0
    assert gini([5, 5, 5, 5]) == pytest.approx(0.0)
    # all selections on one device of n: Gini = (n-1)/n
    assert gini([0] * 9 + [90]) == pytest.approx(0.9)
    spread, skewed = gini([3, 4, 5, 4]), gini([0, 1, 2, 13])
    assert 0.0 <= spread < skewed < 1.0


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_chunk_sample_flat_and_near_counts():
    # reserve 10 J everywhere; near margin 0.5 -> near band (10, 15]
    state = _Obj(residual_energy=np.array([5.0, 10.0, 12.0, 20.0, 14.0]),
                 dropped=np.array([True, True, False, False, False]))
    fleet = _Obj(e0_reserve=np.full(5, 10.0))
    cfg = HealthCfg(max_flat_frac=0.5, max_near_frac=0.5)
    sample, warns = chunk_sample(cfg, state, fleet, round_idx=7)
    assert sample["round"] == 7
    assert sample["flat_battery"] == 2 and sample["flat_frac"] == 0.4
    assert sample["near_depletion"] == 2 and sample["near_frac"] == 0.4
    assert sample["n_dropped"] == 2
    assert warns == []  # both at 40%, thresholds at 50%

    tight = HealthCfg(max_flat_frac=0.1, max_near_frac=0.1)
    _, warns = chunk_sample(tight, state, fleet, round_idx=7)
    assert len(warns) == 2
    assert "flat-battery alarm" in warns[0]
    assert "near-depletion watermark" in warns[1]

    off = HealthCfg(max_flat_frac=None, max_near_frac=None)
    _, warns = chunk_sample(off, state, fleet, round_idx=7)
    assert warns == []


def test_finalize_report_prefers_streaming_quantiles():
    state = _Obj(residual_energy=np.linspace(1.0, 100.0, 50),
                 u=np.arange(50, dtype=np.float64),
                 n_selected=np.full(50, 3.0))
    fleet = _Obj(e0_reserve=np.full(50, 1.0))
    cfg = HealthCfg()
    samples = [{"round": 9, "flat_battery": 0, "flat_frac": 0.0,
                "near_depletion": 1, "near_frac": 0.02, "n_dropped": 0}]
    tel = {"tel/staleness/p50": np.float32(4.0),
           "tel/staleness/p95": np.float32(9.5),
           "tel/residual_energy/p50": np.float32(42.0),
           "tel/residual_energy/p95": np.float32(97.0)}
    rep = finalize_report(cfg, samples, [], state=state, fleet=fleet,
                          telemetry=tel, rounds_run=10)
    assert rep.ok
    assert rep.metrics["staleness_p95"] == pytest.approx(9.5)
    assert rep.metrics["residual_energy_p50"] == pytest.approx(42.0)
    assert rep.metrics["flat_battery"] == 0
    assert rep.metrics["sel_gini"] == pytest.approx(0.0)
    # dense fallback: exact end-state percentiles when no tel keys
    rep2 = finalize_report(cfg, samples, [], state=state, fleet=fleet,
                           telemetry=None, rounds_run=10)
    assert rep2.metrics["staleness_p95"] == pytest.approx(
        np.percentile(state.u, 95))
    # staleness-tail threshold turns the report into an alarm
    strict = dataclasses.replace(cfg, max_staleness_p95=5.0)
    rep3 = finalize_report(strict, samples, [], state=state, fleet=fleet,
                           telemetry=tel, rounds_run=10)
    assert not rep3.ok and "staleness P95" in rep3.warnings[0]
    # carried chunk warnings alone flip ok
    rep4 = finalize_report(cfg, samples, ["health[r=3]: boom"],
                           state=state, fleet=fleet, telemetry=tel,
                           rounds_run=10)
    assert not rep4.ok


def test_finalize_report_gini_alarm_and_table():
    state = _Obj(residual_energy=np.full(10, 50.0),
                 u=np.zeros(10),
                 n_selected=np.array([0.0] * 9 + [90.0]))
    fleet = _Obj(e0_reserve=np.full(10, 1.0))
    rep = finalize_report(HealthCfg(max_gini=0.85), [], [], state=state,
                          fleet=fleet, rounds_run=4)
    assert not rep.ok
    assert "Gini" in rep.warnings[0]
    table = format_health_table(rep)
    assert table.startswith("fleet health: ALARM")
    assert "sel_gini" in table and "! health[final]" in table
    d = rep.to_json()
    assert d["ok"] is False and d["metrics"]["sel_gini"] > 0.85


def test_quantile_specs_share_state_and_dedupe():
    cfg = HealthCfg(quantile_bins=32)
    specs = cfg.quantile_specs(rounds=20, energy_hi=1e5)
    assert len(specs) == 4
    by_metric = {}
    for s in specs:
        by_metric.setdefault(s.metric, set()).add(s.state_key)
    # p50/p95 of one metric share one histogram accumulator
    assert all(len(v) == 1 for v in by_metric.values())

    tcfg = TelemetryCfg(mode="streaming", specs=specs[:1])
    fleet = _Obj(init_energy=np.array([1e4, 1e5]))
    merged = with_health_specs(tcfg, cfg, rounds=20, fleet=fleet)
    assert len(merged.specs) == 4  # already-declared p50 not duplicated
    assert with_health_specs(merged, cfg, 20, fleet) is merged


# ------------------------------------------- engine alarm (end-to-end)

@pytest.fixture(scope="module")
def setup():
    model = make_fl_model("cnn@mnist", small=True)
    fleet = build_fleet(N, seed=0, init_energy_mean=0.3)
    cx, cy, _ = build_task("cnn@mnist", N, 0.8, per_client=8, n_test=16)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4, lr=0.05,
                   uplink_bits=16e6, policy=PolicyCfg(H0=2, H_max=6))
    return model, fleet, cx, cy, cfg


def _health_run(setup, scenario, hcfg, telemetry="dense", rounds=4):
    model, fleet, cx, cy, cfg = setup
    return eng.run_rounds(model, fleet, cx, cy, cfg, METHODS["rewafl"],
                          rounds=rounds, key=jax.random.PRNGKey(7),
                          params=model.init(jax.random.PRNGKey(0)),
                          ecfg=eng.EngineCfg(
                              chunk_size=2, health=hcfg,
                              telemetry=TelemetryCfg(mode=telemetry)),
                          scenario=scenario,
                          env_key=jax.random.PRNGKey(3))


# Background drain far beyond any battery's round budget, no chargers:
# the whole fleet hits the depletion floor within a round or two.
DRAIN_HEAVY = dataclasses.replace(
    get_scenario("congested-urban"), name="test-drain-heavy",
    minutes_per_round=30.0, idle_drain_w=500.0,
    plug_on_day=0.0, plug_on_night=0.0, frac_charging0=0.0)


def test_flat_battery_alarm_trips_on_drain_heavy_scenario(setup):
    res = _health_run(setup, DRAIN_HEAVY, HealthCfg())
    rep = res.health
    assert isinstance(rep, HealthReport)
    assert not rep.ok
    assert any("flat-battery alarm" in w for w in rep.warnings)
    assert rep.metrics["flat_frac"] > HealthCfg().max_flat_frac
    # one sample per chunk boundary (4 rounds / chunk 2)
    assert [s["round"] for s in rep.samples] == [1, 3]


def test_flat_battery_alarm_silent_on_overnight_charging(setup):
    """Arouj-style overnight regime: chargers outpace the drain, nobody
    goes flat — the alarm must not fire."""
    res = _health_run(setup, get_scenario("overnight-charging"),
                      HealthCfg(max_near_frac=None, max_gini=None))
    rep = res.health
    assert rep.metrics["flat_battery"] == 0
    assert not any("flat-battery" in w for w in rep.warnings)
    assert rep.ok


def test_health_streaming_quantiles_on_static_paper(setup):
    """health + streaming telemetry: the report's staleness / energy
    quantiles come from the auto-injected campaign-wide reducers."""
    res = _health_run(setup, get_scenario("static-paper"),
                      HealthCfg(max_near_frac=None),
                      telemetry="streaming", rounds=4)
    rep = res.health
    for k in ("staleness_p50", "staleness_p95", "residual_energy_p50",
              "residual_energy_p95", "sel_gini", "flat_frac"):
        assert k in rep.metrics, k
    assert "tel/staleness/p95" in res.telemetry
    # staleness is bounded by the campaign length
    assert 0.0 <= rep.metrics["staleness_p95"] <= 4.0
    assert rep.metrics["flat_battery"] == 0  # feasibility guards reserve


def test_health_none_skips_monitoring(setup):
    res = _health_run(setup, get_scenario("static-paper"), None)
    assert res.health is None


def test_engine_run_emits_phase_spans(setup):
    """A traced engine run records the per-phase spans engine_bench
    aggregates; numbers must match the untraced run bitwise."""
    base = _health_run(setup, get_scenario("static-paper"), None)
    with tracing(Tracer()) as t:
        traced = _health_run(setup, get_scenario("static-paper"), None)
    evs = t.events
    names = {e["name"] for e in evs}
    # no eval_fn in this run, so no "eval" span
    assert {"chunk", "transfer"} <= names
    assert "compile" in names or "dispatch" in names
    np.testing.assert_array_equal(base.history["global_loss"],
                                  traced.history["global_loss"])
    for k in base.history:
        np.testing.assert_array_equal(base.history[k], traced.history[k])
    # one chunk.device per chunk, under its chunk, ending no earlier than
    # the chunk's dispatch (it starts when the dispatch returns)
    chunks = {e["args"]["index"]: e for e in evs if e["name"] == "chunk"}
    calls = {e["args"]["index"]: e for e in evs
             if e["name"] in ("compile", "dispatch")}
    dev = [e for e in evs if e["name"] == "chunk.device"]
    assert sorted(e["args"]["index"] for e in dev) == sorted(chunks) \
        == [0, 1]
    for e in dev:
        i = e["args"]["index"]
        assert e["parent"] == chunks[i]["id"]
        assert e["ts"] + e["dur"] >= calls[i]["ts"] + calls[i]["dur"]
