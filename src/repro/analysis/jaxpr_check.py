"""Jaxpr-level carry-contract checker for the scan engine hot path.

The AST linter (`analysis/lint.py`) reasons about source text; this
module reasons about the *traced program*. It builds every registered
scenario's round body (sync and async, dense and streaming telemetry) at
a tiny harness scale, traces the chunk closure with `jax.make_jaxpr`,
and asserts the invariants the `jit(scan)` engine depends on:

  carry-stability   the scan carry (params, state[, astate], env) must
                    come back with identical pytree structure, shapes,
                    and dtypes — `lax.scan` enforces this with an opaque
                    TypeError at trace time; we check it per-leaf with a
                    readable diff *before* scan ever sees it.
  no-f64            zero float64/complex128 avals anywhere in the traced
                    program (weak-type promotion leaks double the carry
                    and silently upcast the REWAFL utility/energy math).
  no-host-callback  zero `pure_callback`/`io_callback`/`debug_callback`
                    primitives — a host callback inside the chunk stalls
                    the device every round; obs tracing is host-side by
                    design (spans wrap the chunk, never live inside it).
  prim-budget       recursive primitive count per cell, recorded to a
                    BENCH-style JSON and gated in CI via
                    `check_regression --spec 'jaxpr_*:n_prims:lower:...'`
                    so hot-path op-count growth fails CI like a
                    throughput drop.

Tracing is abstract — no kernel runs, no real data loads — so the full
32-cell matrix (7 scenarios x {sync,async} x {dense,streaming}, plus
static-paper x {sync,async} x {dense,streaming} under the forced-pallas
fused-selection lowering) traces in ~10 s on CPU, cheap enough for the
CI static-analysis job. The chaos
scenarios (`lossy-uplink`, `flaky-fleet`) trace the fault-injection +
robust-screen gates (and, in their async cells, the slot-TTL
expire/retry path), so chaos-path op-count growth gates in CI exactly
like the clean hot path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

# primitives that imply a host round-trip inside the traced program
# (`jax.debug.print` traces to its own `debug_print` primitive)
FORBIDDEN_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print")

F64_DTYPES = (jnp.float64, jnp.complex128)


@dataclasses.dataclass(frozen=True)
class ContractFinding:
    cell: str          # e.g. "sync_dense_static-paper"
    check: str         # carry-stability | no-f64 | no-host-callback | trace
    message: str

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"[{self.check}] {self.cell}: {self.message}"


@dataclasses.dataclass(frozen=True)
class CellReport:
    cell: str
    n_prims: int
    n_eqns_top: int
    findings: Tuple[ContractFinding, ...]


# ----------------------------------------------------------- jaxpr walking


def iter_eqns(jaxpr):
    """Yield every eqn in `jaxpr`, recursing into sub-jaxprs carried in
    eqn params (scan `jaxpr`, cond `branches`, pjit `jaxpr`, ...)."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for j in vs:
                if isinstance(j, jcore.ClosedJaxpr):
                    yield from iter_eqns(j.jaxpr)
                elif isinstance(j, jcore.Jaxpr):
                    yield from iter_eqns(j)


def count_prims(jaxpr) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def forbidden_prims(jaxpr, forbidden: Sequence[str] = FORBIDDEN_PRIMS
                    ) -> List[str]:
    hits = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name in forbidden:
            hits.append(e.primitive.name)
    return hits


def f64_avals(jaxpr) -> List[str]:
    """Dtype-offending avals (vars and literals) in the whole program."""
    hits = []
    for e in iter_eqns(jaxpr):
        for v in list(e.invars) + list(e.outvars):
            aval = getattr(v, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and any(dtype == d for d in F64_DTYPES):
                hits.append(f"{e.primitive.name}: {aval.str_short()}")
    return hits


# ------------------------------------------------------ carry comparison


def _leaf_sig(x) -> str:
    return f"{jnp.shape(x)}:{jnp.result_type(x)}"


def diff_carry(tree_in, tree_out, label: str) -> List[str]:
    """Human-readable structure/shape/dtype differences between the
    carry fed into a scan body and the carry it returns."""
    msgs: List[str] = []
    td_in = jax.tree.structure(tree_in)
    td_out = jax.tree.structure(tree_out)
    if td_in != td_out:
        return [f"{label}: pytree structure changed "
                f"{td_in} -> {td_out}"]
    paths_in = jax.tree_util.tree_flatten_with_path(tree_in)[0]
    leaves_out = jax.tree.leaves(tree_out)
    for (path, a), b in zip(paths_in, leaves_out):
        sa, sb = _leaf_sig(a), _leaf_sig(b)
        if sa != sb:
            p = jax.tree_util.keystr(path)
            msgs.append(f"{label}{p}: {sa} -> {sb}")
    return msgs


def check_carry_contract(body_fn, args, carry_slice: slice,
                         cell: str) -> List[ContractFinding]:
    """eval_shape `body_fn(*args)` and compare the carry portion of the
    output against the carry portion of the input. `carry_slice` selects
    the carry args from `args`; the body is expected to return the
    updated carry as its leading outputs (the engine convention:
    (params, state[, astate], env, metrics))."""
    out = jax.eval_shape(body_fn, *args)
    carry_in = tuple(args[carry_slice])
    carry_out = tuple(out[:len(carry_in)])
    names = ("params", "state", "astate", "env") if len(carry_in) == 4 \
        else ("params", "state", "env")
    msgs = []
    for label, ci, co in zip(names, carry_in, carry_out):
        msgs.extend(diff_carry(ci, co, label))
    return [ContractFinding(cell, "carry-stability", m) for m in msgs]


# -------------------------------------------------------- harness (tiny)


@dataclasses.dataclass(frozen=True)
class HarnessCfg:
    """Tiny trace-only scale: jaxpr structure (primitive mix, carry
    contract, dtype discipline) is shape-polymorphic in S, so the
    smallest fleet that exercises every code path suffices."""
    n_devices: int = 8
    n_select: int = 2
    per_device: int = 8
    chunk_len: int = 2
    buffer_m: int = 2


def build_cell(scenario_name: Optional[str], aggregation: str,
               telemetry: str, kernel_backend: str = "auto",
               hc: HarnessCfg = HarnessCfg()):
    """Construct (chunk_fn, args, carry_slice, body_fn, body_args) for
    one matrix cell. Imports are deferred so `repro.analysis` stays
    importable without triggering engine/model imports (the AST linter
    must run even where jax is too old to trace)."""
    from repro.core.async_agg import AsyncCfg
    from repro.core.metrics import TelemetryCfg
    from repro.core.methods import METHODS, method_params
    from repro.core.policy import PolicyCfg
    from repro.core.round import (
        FLConfig,
        make_async_round_body_mp,
        make_round_body_mp,
    )
    from repro.core.state import init_async_state, init_fleet_state
    from repro.launch.engine import _chunk_body_mp, _telemetry_carry
    from repro.models.fl_models import make_cnn
    from repro.sim.devices import build_fleet
    from repro.sim.dynamics import init_env_state
    from repro.sim.dynamics.scenarios import get_scenario

    S, K, n = hc.n_devices, hc.n_select, hc.per_device
    model = make_cnn((8, 8, 1), 4, c1=2, c2=2, d_fc=8)
    fleet = build_fleet(S)
    cfg = FLConfig(n_select=K, batch_size=4, probe_size=4,
                   policy=PolicyCfg(H0=2, H_max=4),
                   kernel_backend=kernel_backend)
    cx = jnp.zeros((S, n, 8, 8, 1))
    cy = jnp.zeros((S, n), jnp.int32)
    params = model.init(jax.random.PRNGKey(0))
    state = init_fleet_state(fleet)
    scenario = get_scenario(scenario_name) if scenario_name else None
    env = init_env_state(fleet, scenario, jax.random.PRNGKey(1))
    # chaos scenarios thread FaultParams through MethodParams (the
    # compile-once grid path) — trace them here too so the fault gates'
    # carry leaves are contract-checked like every other cell
    fcfg = scenario.faults if scenario is not None else None
    mp = method_params(METHODS["rewafl"], fault_cfg=fcfg)
    key = jax.random.PRNGKey(2)
    r0 = jnp.int32(0)

    tcfg = TelemetryCfg(mode="streaming") if telemetry == "streaming" \
        else None

    if aggregation == "async":
        # faulted cells also trace the slot-TTL expire/retry path (the
        # async half of core.resilience) so its counters are budgeted
        ttl = 300.0 if (fcfg is not None and fcfg.enabled) else None
        acfg = AsyncCfg(buffer_m=hc.buffer_m, ttl=ttl)
        body = make_async_round_body_mp(model, cfg, scenario, acfg)
        astate = init_async_state(params, S, acfg.slots(K))
        body_args = (mp, params, state, astate, env, fleet, cx, cy,
                     key, r0)
        carry_slice = slice(1, 5)   # params, state, astate, env
        chunk = _chunk_body_mp(body, hc.chunk_len, True, tcfg,
                               async_mode=True)
    else:
        body = make_round_body_mp(model, cfg, scenario)
        body_args = (mp, params, state, env, fleet, cx, cy, key, r0)
        carry_slice = slice(1, 4)   # params, state, env
        chunk = _chunk_body_mp(body, hc.chunk_len, True, tcfg)

    args = list(body_args)
    if tcfg is not None:
        tel = _telemetry_carry(tcfg, body, tuple(body_args))
        args = args + [tel]
    return chunk, tuple(args), carry_slice, body, body_args


def cell_name(scenario: Optional[str], aggregation: str,
              telemetry: str, kernel_backend: str = "auto") -> str:
    base = f"{aggregation}_{telemetry}_{scenario or 'none'}"
    # the default ("auto") resolves to the XLA reference on the pinned
    # CPU CI runner, so only a forced backend earns a suffix — keeping
    # the historical cell names (and their baselines) stable
    if kernel_backend in ("auto", "xla"):
        return base
    return f"{base}_{kernel_backend}"


def check_cell(scenario: Optional[str], aggregation: str, telemetry: str,
               kernel_backend: str = "auto",
               hc: HarnessCfg = HarnessCfg()) -> CellReport:
    """Trace one matrix cell and run every contract check against it."""
    cell = cell_name(scenario, aggregation, telemetry, kernel_backend)
    findings: List[ContractFinding] = []
    try:
        chunk, args, carry_slice, body, body_args = build_cell(
            scenario, aggregation, telemetry, kernel_backend, hc)
    except Exception as e:  # construction failed — report, don't crash
        return CellReport(cell, -1, -1, (ContractFinding(
            cell, "trace", f"harness construction failed: {e!r}"),))

    # carry contract at the round-body level (readable per-leaf diff)
    try:
        findings.extend(check_carry_contract(
            body, body_args, carry_slice, cell))
    except TypeError as e:
        findings.append(ContractFinding(
            cell, "carry-stability", f"eval_shape raised: {e}"))

    # full chunk trace: scan actually enforces the carry contract here,
    # so a TypeError from make_jaxpr is itself a contract finding
    try:
        jx = jax.make_jaxpr(chunk)(*args)
    except TypeError as e:
        findings.append(ContractFinding(
            cell, "carry-stability",
            f"lax.scan rejected the chunk carry: {e}"))
        return CellReport(cell, -1, -1, tuple(findings))

    for p in forbidden_prims(jx.jaxpr):
        findings.append(ContractFinding(
            cell, "no-host-callback",
            f"host callback primitive `{p}` inside the traced chunk — "
            f"obs spans wrap the chunk on the host; nothing may call "
            f"back mid-scan"))
    for h in f64_avals(jx.jaxpr):
        findings.append(ContractFinding(
            cell, "no-f64",
            f"float64 aval in traced program ({h}) — the carry "
            f"contract is f32/i32"))

    return CellReport(cell, count_prims(jx.jaxpr), len(jx.jaxpr.eqns),
                      tuple(findings))


def default_matrix() -> List[Tuple]:
    from repro.sim.dynamics.scenarios import SCENARIOS
    cells: List[Tuple] = []
    for name in sorted(SCENARIOS):
        for agg in ("sync", "async"):
            for tel in ("dense", "streaming"):
                cells.append((name, agg, tel))
    # fused kernel_backend cells: the forced-pallas lowering swaps the
    # rank-space argsort selection for the fused top_k+scatter emission,
    # so its prim mix gets its own budget rows. One scenario suffices —
    # the selection lowering is scenario-independent.
    for agg in ("sync", "async"):
        for tel in ("dense", "streaming"):
            cells.append(("static-paper", agg, tel, "pallas"))
    return cells


def check_contracts(cells: Optional[Sequence[Tuple]] = None,
                    hc: HarnessCfg = HarnessCfg(),
                    progress=None) -> List[CellReport]:
    if cells is None:
        cells = default_matrix()
    reports = []
    for cell in cells:
        if progress is not None:
            progress(cell_name(*cell))
        reports.append(check_cell(*cell, hc=hc))
    return reports


def prim_budget_results(reports: Sequence[CellReport]) -> Dict:
    """BENCH-style payload for `check_regression --spec` gating: one
    `jaxpr_<cell>` row per traced cell with its recursive prim count."""
    results = {f"jaxpr_{r.cell}": {"n_prims": r.n_prims}
               for r in reports if r.n_prims >= 0}
    return {"results": results, "jax_version": jax.__version__,
            "numpy_version": np.__version__}
