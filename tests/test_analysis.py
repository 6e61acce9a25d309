"""Static-analysis subsystem tests (ISSUE 8).

Three layers:
  1. AST rules — one fixture snippet per rule that trips exactly that
     rule, plus a clean twin that must not.
  2. jaxpr contracts — an injected carry-dtype mutation and an injected
     io_callback must each be caught; the real static-paper cell must
     be clean.
  3. CLI — exit codes and the JSON report shape.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.lint import (
    RULES,
    LintConfig,
    baseline_suppressed,
    lint_source,
    make_baseline,
)

# every rule-fixture lints under a path inside the traced-module set so
# the host-sync rules are active
TRACED_PATH = "src/repro/core/fixture.py"
LAUNCH_PATH = "src/repro/launch/fixture.py"
HOST_PATH = "src/repro/obs/fixture.py"  # not traced, prints forbidden


def findings(src, path=TRACED_PATH, **kw):
    return lint_source(textwrap.dedent(src), path, **kw)


def rules_of(fs):
    return sorted({f.rule for f in fs})


# ------------------------------------------------------------- AST rules


BAD_GOOD = {
    "host-item": (
        "def f(x):\n    return x.mean().item()\n",
        "def f(x):\n    return x.mean()\n",
    ),
    "host-asarray": (
        "import numpy as np\n\ndef f(x):\n    return np.asarray(x)\n",
        "import jax.numpy as jnp\n\ndef f(x):\n    return jnp.asarray(x)\n",
    ),
    "host-cast": (
        "import jax.numpy as jnp\n\ndef f(x):\n"
        "    return float(jnp.sum(x))\n",
        # trace-time constants (plain python, no jnp call inside) are fine
        "def f(cfg, model):\n"
        "    return float(cfg.uplink_bits or model.param_bits)\n",
    ),
    "host-branch": (
        "import jax.numpy as jnp\n\ndef f(x):\n"
        "    if jnp.any(x > 0):\n        return x\n    return -x\n",
        # dtype queries are host values — branching on them is trace-time
        # dispatch, not a traced branch
        "import jax.numpy as jnp\n\ndef f(x, dtype):\n"
        "    if jnp.issubdtype(dtype, jnp.inexact):\n        return x\n"
        "    return -x\n",
    ),
    "bare-print": (
        "def f(x):\n    print('round', x)\n    return x\n",
        "from repro.obs.log import get_logger\n\n\ndef f(x):\n"
        "    get_logger(__name__).info('round %s', x)\n    return x\n",
    ),
    "jit-static-args": (
        "import jax\n\ndef run(params, cfg):\n    return params\n\n"
        "step = jax.jit(run)\n",
        "import jax\n\ndef run(params, cfg):\n    return params\n\n"
        "step = jax.jit(run, static_argnames=('cfg',))\n",
    ),
    "f64-literal": (
        "import jax.numpy as jnp\n\ndef f(x):\n"
        "    return x.astype(jnp.float64)\n",
        "import jax.numpy as jnp\n\ndef f(x):\n"
        "    return x.astype(jnp.float32)\n",
    ),
    "pytree-order": (
        "class Carry:\n"
        "    a: int\n"
        "    b: int\n"
        "    def tree_flatten(self):\n"
        "        return (self.b, self.a), None\n",
        "class Carry:\n"
        "    a: int\n"
        "    b: int\n"
        "    def tree_flatten(self):\n"
        "        return (self.a, self.b), None\n",
    ),
}


@pytest.mark.parametrize("rule", sorted(BAD_GOOD))
def test_rule_trips_on_bad_and_only_that_rule(rule):
    bad, _ = BAD_GOOD[rule]
    path = HOST_PATH if rule == "bare-print" else TRACED_PATH
    fs = findings(bad, path)
    assert rules_of(fs) == [rule], \
        f"{rule}: expected exactly [{rule}], got {rules_of(fs)}"


@pytest.mark.parametrize("rule", sorted(BAD_GOOD))
def test_rule_passes_on_clean_twin(rule):
    _, good = BAD_GOOD[rule]
    path = HOST_PATH if rule == "bare-print" else TRACED_PATH
    fs = findings(good, path)
    assert rule not in rules_of(fs), \
        f"{rule}: clean twin tripped: {[str(f) for f in fs]}"


def test_registry_covers_every_fixture():
    assert set(BAD_GOOD) == set(RULES)


def test_host_rules_scoped_to_traced_modules():
    """np.asarray in host-side orchestration (launch/) is legitimate."""
    bad, _ = BAD_GOOD["host-asarray"]
    assert findings(bad, LAUNCH_PATH) == []


def test_f64_dtype_string_and_kwarg():
    fs = findings(
        "import jax.numpy as jnp\n\n"
        "def f(s):\n    return jnp.zeros(s, dtype='float64')\n")
    assert rules_of(fs) == ["f64-literal"]
    fs = findings(
        "import numpy as np\n\ndef f(s):\n    return np.zeros(s)\n")
    assert "f64-literal" not in rules_of(fs)


def test_jit_static_args_decorator_and_partial():
    fs = findings(
        "import jax\n\n@jax.jit\ndef step(params, cfg):\n"
        "    return params\n")
    assert rules_of(fs) == ["jit-static-args"]
    fs = findings(
        "import jax\nfrom functools import partial\n\n"
        "@partial(jax.jit, static_argnames=('cfg',))\n"
        "def step(params, cfg):\n    return params\n")
    assert fs == []


def test_inline_noqa_suppresses():
    bad = ("def f(x):\n"
           "    return x.mean().item()  # noqa: host-item\n")
    assert findings(bad) == []
    # a noqa for a different rule does not suppress
    bad2 = ("def f(x):\n"
            "    return x.mean().item()  # noqa: bare-print\n")
    assert rules_of(findings(bad2)) == ["host-item"]


def test_baseline_suppression_survives_line_drift():
    bad = "def f(x):\n    return x.mean().item()\n"
    fs = findings(bad)
    entries = make_baseline(fs)["entries"]
    # same content moved two lines down still matches
    moved = "\n\n" + bad
    for f in findings(moved):
        assert baseline_suppressed(f, entries)


def test_custom_config_scoping():
    cfg = LintConfig(traced_prefixes=("mypkg/hot/",))
    bad, _ = BAD_GOOD["host-item"]
    assert lint_source(bad, "mypkg/hot/x.py", cfg) != []
    assert lint_source(bad, "mypkg/cold/x.py", cfg) == []


# -------------------------------------------------------- jaxpr layer


jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis.jaxpr_check import (  # noqa: E402
    check_carry_contract,
    check_cell,
    diff_carry,
    f64_avals,
    forbidden_prims,
    iter_eqns,
)


def test_static_paper_cell_is_clean():
    rep = check_cell("static-paper", "sync", "dense")
    assert rep.findings == (), [str(f) for f in rep.findings]
    assert rep.n_prims > 0


def test_injected_carry_dtype_mutation_caught():
    """A body that changes one carry leaf's dtype (e.g. a bf16
    compaction applied on output but not input) must produce a
    carry-stability finding."""
    def body(params, state):
        # state comes back a different dtype — scan would reject this
        return params, state.astype(jnp.bfloat16), jnp.float32(0.0)

    args = (jnp.zeros((3,), jnp.float32), jnp.zeros((2,), jnp.float32))
    fs = check_carry_contract(body, args, slice(0, 2), "injected")
    assert len(fs) == 1
    assert fs[0].check == "carry-stability"
    assert "float32" in fs[0].message and "bfloat16" in fs[0].message


def test_injected_structure_change_caught():
    def body(params, state):
        return (params, params), state, jnp.float32(0.0)

    args = (jnp.zeros((3,)), jnp.zeros((2,)))
    fs = check_carry_contract(body, args, slice(0, 2), "injected")
    assert fs and "structure" in fs[0].message


def test_injected_io_callback_caught():
    from jax.experimental import io_callback

    def chunk(x):
        io_callback(lambda v: None, None, x)
        return x * 2.0

    jx = jax.make_jaxpr(chunk)(jnp.ones((4,)))
    assert forbidden_prims(jx.jaxpr) == ["io_callback"]


def test_debug_print_caught_inside_scan():
    """Callback prims must be found recursively inside scan bodies,
    where they would fire every round."""
    def chunk(x):
        def step(c, _):
            jax.debug.print("c={c}", c=c)
            return c + 1.0, c
        y, ys = jax.lax.scan(step, x, None, length=3)
        return y

    jx = jax.make_jaxpr(chunk)(jnp.float32(0.0))
    assert "debug_print" in forbidden_prims(jx.jaxpr)


def test_f64_aval_scan():
    def f(x):
        return x.astype("float64") * 2.0

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(f)(jnp.ones((2,), jnp.float32))
    assert f64_avals(jx.jaxpr)
    jx32 = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((2,), jnp.float32))
    assert f64_avals(jx32.jaxpr) == []


def test_iter_eqns_recurses_into_cond_branches():
    def f(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jnp.exp(v),
                            lambda v: jnp.log1p(v), x)

    jx = jax.make_jaxpr(f)(jnp.ones((2,)))
    prims = {e.primitive.name for e in iter_eqns(jx.jaxpr)}
    assert "exp" in prims and "log1p" in prims


def test_diff_carry_reports_shape_change():
    a = {"w": jnp.zeros((3, 2))}
    b = {"w": jnp.zeros((2, 3))}
    msgs = diff_carry(a, b, "params")
    assert len(msgs) == 1 and "(3, 2)" in msgs[0] and "(2, 3)" in msgs[0]


# --------------------------------------------------------------- CLI


SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def run_cli(*args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd, env=env)


def test_cli_clean_file_exits_zero(tmp_path):
    p = tmp_path / "src" / "repro" / "core"
    p.mkdir(parents=True)
    (p / "clean.py").write_text("def f(x):\n    return x\n")
    r = run_cli(str(p))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_cli_violation_exits_nonzero_and_json_reports(tmp_path):
    p = tmp_path / "src" / "repro" / "core"
    p.mkdir(parents=True)
    (p / "bad.py").write_text(
        "def f(x):\n    return x.mean().item()\n")
    r = run_cli(str(p), "--format", "json")
    assert r.returncode == 1
    rep = json.loads(r.stdout)
    assert len(rep["findings"]) == 1
    f = rep["findings"][0]
    assert f["rule"] == "host-item" and f["line"] == 2


def test_cli_baseline_suppresses_to_zero(tmp_path):
    p = tmp_path / "src" / "repro" / "core"
    p.mkdir(parents=True)
    (p / "bad.py").write_text(
        "def f(x):\n    return x.mean().item()\n")
    bl = tmp_path / "baseline.json"
    r = run_cli(str(p), "--write-baseline", str(bl))
    assert r.returncode == 0
    r = run_cli(str(p), "--baseline", str(bl))
    assert r.returncode == 0, r.stdout


def test_cli_unknown_rule_is_usage_error(tmp_path):
    r = run_cli(str(tmp_path), "--rules", "no-such-rule")
    assert r.returncode == 2


@pytest.mark.slow
def test_cli_contracts_single_cell():
    """End-to-end: one real traced cell through the CLI, JSON shape with
    the prim-budget payload check_regression consumes."""
    r = run_cli("--contracts", "--cells", "sync_dense_static-paper*",
                "--format", "json")
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["contracts"] == []
    budget = rep["prim_budget"]["results"]
    assert list(budget) == ["jaxpr_sync_dense_static-paper"]
    assert budget["jaxpr_sync_dense_static-paper"]["n_prims"] > 0
    assert rep["prim_budget"]["jax_version"]
