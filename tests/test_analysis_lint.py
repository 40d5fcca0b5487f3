"""Per-rule bad/good fixtures for the serving-invariant lint pass.

Each rule gets (at least) one fixture tree that must trip it with a
``path:line`` diagnostic and one that must stay clean; plus the
suppression syntax, the CLI exit-code contract, and the meta-check that
the repo's own ``src/`` tree lints clean (satellite: zero suppressions
in serving/).
"""
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis.lint import main, run_paths

REPO = Path(__file__).resolve().parent.parent


def _write(root: Path, rel: str, text: str) -> Path:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return p


def _diags(root, rule=None):
    from repro.analysis.rules import ALL_RULES

    rules = None
    if rule is not None:
        rules = [r for r in ALL_RULES if r.name == rule]
        assert rules, f"unknown rule {rule}"
    return run_paths([root], rules)


# ----------------------------------------------------------------- RULE-CLOCK
def test_clock_flags_bare_calls_in_serving(tmp_path):
    _write(tmp_path, "serving/gateway.py", (
        "import time\n"
        "def wait():\n"
        "    t0 = time.monotonic()\n"
        "    return t0\n"))
    out = _diags(tmp_path, "clock")
    assert len(out) == 1
    assert out[0].line == 3 and out[0].rule == "clock"
    assert "serving/gateway.py" in out[0].path


def test_clock_allows_injection_references(tmp_path):
    # references (injection-point defaults) are the sanctioned idiom —
    # only *calls* are flagged
    _write(tmp_path, "serving/gateway.py", (
        "import time\n"
        "def make(clock=time.perf_counter):\n"
        "    return clock()\n"))
    assert _diags(tmp_path, "clock") == []


def test_clock_ignores_out_of_scope_files(tmp_path):
    _write(tmp_path, "training/loop.py", (
        "import time\n"
        "t = time.time()\n"))
    assert _diags(tmp_path, "clock") == []


def test_clock_suppression_comment(tmp_path):
    _write(tmp_path, "serving/gateway.py", (
        "import time\n"
        "t0 = time.monotonic()  # lint: allow-clock\n"
        "# lint: allow-clock\n"
        "t1 = time.monotonic()\n"
        "t2 = time.monotonic()\n"))
    out = _diags(tmp_path, "clock")
    assert [d.line for d in out] == [5]       # only the unsuppressed one


# ------------------------------------------------------------------- RULE-OBS
_OBS_BAD = (
    "class G:\n"
    "    def step(self):\n"
    "        self.tracer.begin('step', 1)\n")

_OBS_GOOD = (
    "class G:\n"
    "    def step(self):\n"
    "        if self.obs:\n"
    "            self.tracer.begin('step', 1)\n"
    "    def emit(self):\n"
    "        if not self.obs:\n"
    "            return\n"
    "        self.h.observe(0.5)\n"
    "        self.audit.record('flip', v=2)\n"
    "    def reg(self):\n"
    "        if self.audit is not None:\n"
    "            self.audit.record('grant', t='free')\n")


def test_obs_flags_unguarded_record_sites(tmp_path):
    _write(tmp_path, "serving/fleet.py", _OBS_BAD)
    out = _diags(tmp_path, "obs")
    assert len(out) == 1 and out[0].line == 3


def test_obs_accepts_guard_styles(tmp_path):
    # enclosing if, early-out, and the optional-audit idiom all count
    _write(tmp_path, "serving/fleet.py", _OBS_GOOD)
    assert _diags(tmp_path, "obs") == []


def test_obs_flags_unguarded_program_span(tmp_path):
    # a span enters a profiler annotation and writes the tape: the same
    # guard applies, and the shared-null-context ternary satisfies it
    _write(tmp_path, "serving/gateway.py", (
        "class G:\n"
        "    def step(self):\n"
        "        with self.tracer.span('gw:step'):\n"
        "            pass\n"
        "        with (self.tracer.span('gw:emit') if self.obs\n"
        "              else NULL_SPAN):\n"
        "            pass\n"))
    out = _diags(tmp_path, "obs")
    assert [d.line for d in out] == [3]
    assert "tracer.span" in out[0].message


def test_obs_exempts_instrument_implementations(tmp_path):
    _write(tmp_path, "serving/telemetry.py", _OBS_BAD)
    _write(tmp_path, "serving/tracing.py", _OBS_BAD)
    assert _diags(tmp_path, "obs") == []


# ------------------------------------------------------------ RULE-GUARDED-BY
def test_guarded_by_lock_discipline(tmp_path):
    _write(tmp_path, "serving/transport.py", (
        "class T:\n"
        "    def __init__(self):\n"
        "        self._counts = {}  # guarded-by: _lock\n"
        "    def good(self, op, n):\n"
        "        with self._lock:\n"
        "            self._counts[op] = 1\n"
        "            self._counts = {}\n"
        "    def bad(self):\n"
        "        self._counts = {}\n"))
    out = _diags(tmp_path, "guarded-by")
    assert [d.line for d in out] == [9]
    assert "_lock" in out[0].message


def test_guarded_by_owner_discipline(tmp_path):
    _write(tmp_path, "serving/updates.py", (
        "class S:\n"
        "    def __init__(self):\n"
        "        self._pos = (0, 0)  # guarded-by: owner(__init__, begin)\n"
        "    def begin(self):\n"
        "        self._pos = (1, 0)\n"
        "    def rogue(self):\n"
        "        self._pos = (9, 9)\n"))
    out = _diags(tmp_path, "guarded-by")
    assert [d.line for d in out] == [7]
    assert "rogue" in out[0].message


def test_guarded_by_tuple_assignment_target(tmp_path):
    # ``old, self._cursor = self._cursor, None`` is still a write
    _write(tmp_path, "serving/updates.py", (
        "class S:\n"
        "    def __init__(self):\n"
        "        self._cursor = None  # guarded-by: owner(__init__)\n"
        "    def swap(self):\n"
        "        old, self._cursor = self._cursor, None\n"
        "        return old\n"))
    out = _diags(tmp_path, "guarded-by")
    assert [d.line for d in out] == [5]


# -------------------------------------------------------------- RULE-HOT-PATH
def test_hot_path_flags_per_iteration_sync(tmp_path):
    _write(tmp_path, "serving/scheduler.py", (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def step(xs):\n"
        "    acc = []\n"
        "    for x in xs:\n"
        "        acc.append(float(jnp.sum(x)))\n"
        "    outs = np.asarray(jnp.stack(acc))\n"   # boundary: legal
        "    return outs\n"))
    out = _diags(tmp_path, "hot-path")
    assert [d.line for d in out] == [6]


def test_hot_path_flags_explicit_fences(tmp_path):
    _write(tmp_path, "serving/engine.py", (
        "import jax\n"
        "def step(y):\n"
        "    y.block_until_ready()\n"
        "    return jax.device_get(y)\n"))
    out = _diags(tmp_path, "hot-path")
    assert [d.line for d in out] == [3, 4]


def test_hot_path_ignores_host_staging_and_benchmarks(tmp_path):
    _write(tmp_path, "serving/gateway.py", (
        "import jax.numpy as jnp\n"
        "def stage(rows):\n"
        "    for r in rows:\n"
        "        x = jnp.asarray(r)\n"      # host->device: not a sync
        "    return x\n"))
    _write(tmp_path, "bench/decode.py", (
        "def bench(y):\n"
        "    y.block_until_ready()\n"))     # benchmarks are out of scope
    assert _diags(tmp_path, "hot-path") == []


# ---------------------------------------------------------------- RULE-KERNEL
_KERNEL_GOOD = (
    "import jax\n"
    "from jax.experimental import pallas as pl\n"
    "def addone(x, interpret=False):\n"
    "    return pl.pallas_call(lambda r, o: None, out_shape=x,\n"
    "                          interpret=interpret)(x)\n")


def test_kernel_requires_interpret_and_oracle(tmp_path):
    _write(tmp_path, "kernels/bad.py", (
        "from jax.experimental import pallas as pl\n"
        "def mystery(x):\n"
        "    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)\n"))
    out = _diags(tmp_path, "kernel")
    msgs = "\n".join(d.message for d in out)
    assert "interpret" in msgs
    assert "ref.py" in msgs


def test_kernel_clean_with_oracle_pair(tmp_path):
    _write(tmp_path, "kernels/addone.py", _KERNEL_GOOD)
    _write(tmp_path, "kernels/ref.py", "def addone(x):\n    return x + 1\n")
    assert _diags(tmp_path, "kernel") == []


def test_kernel_donate_requires_alias(tmp_path):
    _write(tmp_path, "kernels/donated.py", (
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "def _call(x):\n"
        "    return pl.pallas_call(lambda r, o: None, out_shape=x,\n"
        "                          interpret=False)(x)\n"
        "@jax.jit(donate_argnums=(0,))\n"
        "def donated(x):\n"
        "    return _call(x)\n"))
    _write(tmp_path, "kernels/ref.py", "def donated(x):\n    return x\n")
    out = _diags(tmp_path, "kernel")
    assert len(out) == 1 and "donate_argnums" in out[0].message


def test_kernel_alias_keys_must_index_operands(tmp_path):
    _write(tmp_path, "kernels/aliased.py", (
        "from jax.experimental import pallas as pl\n"
        "def scatter(a, b):\n"
        "    return pl.pallas_call(lambda r, o: None, out_shape=a,\n"
        "                          input_output_aliases={5: 0},\n"
        "                          interpret=False)(a, b)\n"))
    _write(tmp_path, "kernels/ref.py", "def scatter(a, b):\n    return a\n")
    out = _diags(tmp_path, "kernel")
    assert len(out) == 1 and "exceeds" in out[0].message


# --------------------------------------------------------------- RULE-METRICS
_METRICS_DOC = (
    "# Observability\n"
    "| series | type |\n"
    "|---|---|\n"
    "| `serving_requests_{admitted,rejected}_total` | counter |\n"
    "| `serving_phantom_total` | counter |\n")

_METRICS_SRC = (
    "class M:\n"
    "    def reg(self, t):\n"
    "        t.counter('serving_requests_admitted_total')\n"
    "        t.counter('serving_requests_rejected_total')\n"
    "        t.counter('serving_undocumented_total')\n")


def test_metrics_cross_checks_code_and_docs(tmp_path):
    _write(tmp_path, "docs/OBSERVABILITY.md", _METRICS_DOC)
    _write(tmp_path, "serving/fleet.py", _METRICS_SRC)
    out = _diags(tmp_path, "metrics")
    msgs = {d.message.split("`")[1]: d for d in out}
    assert set(msgs) == {"serving_undocumented_total",
                         "serving_phantom_total"}
    assert "serving/fleet.py" in msgs["serving_undocumented_total"].path
    assert msgs["serving_phantom_total"].path.endswith("OBSERVABILITY.md")


def test_metrics_flags_duplicate_declared_keys(tmp_path):
    _write(tmp_path, "serving/telemetry.py", (
        "GATEWAY_METRICS_KEYS = (\n"
        "    'admitted', 'rejected', 'admitted',\n"
        ")\n"))
    out = _diags(tmp_path, "metrics")
    assert len(out) == 1 and "duplicate" in out[0].message


def test_metrics_export_table_keys_must_be_declared(tmp_path):
    _write(tmp_path, "serving/telemetry.py",
           "GATEWAY_METRICS_KEYS = ('admitted',)\n")
    _write(tmp_path, "serving/fleet.py", (
        "TABLE = [\n"
        "    ('admitted', 'serving_requests_admitted_total', 'ok'),\n"
        "    ('ghost', 'serving_ghosts_total', 'not declared'),\n"
        "]\n"))
    out = _diags(tmp_path, "metrics")
    assert len(out) == 1 and "ghost" in out[0].message


# ------------------------------------------------------------------ CLI / API
def test_cli_exit_codes(tmp_path, capsys):
    bad = _write(tmp_path, "serving/gateway.py",
                 "import time\nt = time.monotonic()\n")
    good = _write(tmp_path, "serving/clean.py", "x = 1\n")
    assert main([str(good)]) == 0
    assert main([str(bad)]) == 1
    rendered = capsys.readouterr().out
    assert "RULE-CLOCK" in rendered and ":2:" in rendered
    with pytest.raises(SystemExit) as exc:
        main([str(bad), "--rule", "no-such-rule"])
    assert exc.value.code == 2
    with pytest.raises(SystemExit):
        main([str(tmp_path / "no-such-tree")])


def test_cli_rule_filter(tmp_path):
    bad = _write(tmp_path, "serving/gateway.py",
                 "import time\nt = time.monotonic()\n")
    assert main([str(bad), "--rule", "obs"]) == 0      # clock finding masked
    assert main([str(bad), "--rule", "clock"]) == 1


def test_module_entrypoint_runs():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--help"],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0
    assert "docs/ANALYSIS.md" in out.stdout


# -------------------------------------------------------------- the real tree
def test_repo_serving_tree_is_clean():
    """The merged tree lints clean — and with zero suppressions under
    serving/ (the satellite contract)."""
    diags = run_paths([REPO / "src"])
    assert diags == [], "\n".join(d.render() for d in diags)
    for p in (REPO / "src" / "repro" / "serving").rglob("*.py"):
        assert "lint: allow-" not in p.read_text(), \
            f"suppression found in serving/: {p}"
