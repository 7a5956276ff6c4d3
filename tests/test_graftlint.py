"""graftlint Layer A: the AST rule engine, the CLI ratchet, and the two
satellite behaviors it guards (accounted serving fetches, injectable
clocks).

The rule-engine tests exercise ``lint_source`` directly (loaded standalone
via importlib, exactly like the tier-1 dry-run lane — these tests double as
proof the module stays stdlib-only). The CLI tests run
``scripts/graftlint.py`` as a subprocess against tmp trees, pinning the
exit conventions: 0 clean, 2 malformed baseline, 3 regression — including
the acceptance case of a new ``.item()`` injected into a guarded file.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRAFTLINT = os.path.join(REPO_ROOT, "scripts", "graftlint.py")
PERF_GATE = os.path.join(REPO_ROOT, "scripts", "perf_gate.py")
LINT_BASELINE = os.path.join(REPO_ROOT, "onchip_results",
                             "lint_baseline.json")


def _load_astlint():
    path = os.path.join(REPO_ROOT, "deepspeed_tpu", "analysis", "astlint.py")
    spec = importlib.util.spec_from_file_location("_astlint_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lint = _load_astlint()


def _rules(src):
    return [f["rule"] for f in lint.lint_source(textwrap.dedent(src))]


def _run(argv, **kw):
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, cwd=REPO_ROOT, **kw)


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def test_item_call_flagged():
    assert "GL001" in _rules("""
        def hot(x):
            return x.item()
    """)


def test_float_over_jax_expr_flagged_plain_float_not():
    src_bad = """
        import jax.numpy as jnp
        def f(x):
            return float(jnp.mean(x))
    """
    src_ok = """
        def f(x):
            return float(x)
    """
    assert "GL002" in _rules(src_bad)
    assert "GL002" not in _rules(src_ok)


def test_device_get_flagged_outside_but_not_inside_host_fetch():
    flagged = _rules("""
        import jax
        def grab(x):
            return jax.device_get(x)
    """)
    assert "GL003" in flagged
    # the accounted path is exempt by construction — the false-positive
    # fixture from the issue: a legitimate device_get inside _host_fetch
    exempt = _rules("""
        import jax
        import numpy as np
        class Engine:
            def _host_fetch(self, value, what):
                self._host_sync_count += 1
                return jax.device_get(value)
            def host_fetch(self, value, what):
                return np.asarray(value)
    """)
    assert "GL003" not in exempt
    assert "GL004" not in exempt


def test_asarray_flagged_with_import_alias_resolution():
    assert "GL004" in _rules("""
        import numpy as np
        def f(x):
            return np.asarray(x)
    """)
    # from-import spelling resolves too
    assert "GL004" in _rules("""
        from numpy import asarray
        def f(x):
            return asarray(x)
    """)


def test_jit_in_loop_flagged():
    assert "GL101" in _rules("""
        import jax
        def tune(fns, x):
            out = []
            for fn in fns:
                out.append(jax.jit(fn)(x))
            return out
    """)


def test_missing_donate_on_step_jit_flagged_eval_exempt():
    flagged = _rules("""
        import jax
        def micro_step(state, batch):
            return state
        f = jax.jit(micro_step)
    """)
    assert "GL102" in flagged
    ok = _rules("""
        import jax
        def micro_step(state, batch):
            return state
        f = jax.jit(micro_step, donate_argnums=(0,))
    """)
    assert "GL102" not in ok
    # eval steps must NOT donate (they read shared state)
    assert "GL102" not in _rules("""
        import jax
        def eval_step(state, batch):
            return state
        f = jax.jit(eval_step)
    """)


def test_wallclock_reachable_from_traced_code_flagged():
    flagged = _rules("""
        import jax
        import time
        def stamp():
            return time.perf_counter()
        def micro_step(state):
            t = stamp()
            return state, t
        f = jax.jit(micro_step, donate_argnums=(0,))
    """)
    assert "GL103" in flagged
    # the same clock call NOT reachable from any traced root is fine
    assert "GL103" not in _rules("""
        import time
        def stamp():
            return time.perf_counter()
    """)


def test_jit_on_fresh_lambda_flagged():
    assert "GL104" in _rules("""
        import jax
        def f(x):
            return jax.jit(lambda y: y * 2)(x)
    """)


def test_clock_alias_bypass_flagged():
    flagged = _rules("""
        import time
        _now = time.perf_counter
        def f():
            return time.perf_counter()
    """)
    assert "GL105" in flagged
    # no alias in the module -> no GL105 (GL103 governs traced reads)
    assert "GL105" not in _rules("""
        import time
        def f():
            return time.perf_counter()
    """)


def test_unlocked_global_write_flagged_locked_ok():
    flagged = _rules("""
        _CACHE = None
        def setup(v):
            global _CACHE
            _CACHE = v
    """)
    assert "GL201" in flagged
    assert "GL201" not in _rules("""
        import threading
        _LOCK = threading.Lock()
        _CACHE = None
        def setup(v):
            global _CACHE
            with _LOCK:
                _CACHE = v
    """)


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------

def test_pragma_suppresses_on_line():
    src = """
        import jax
        def grab(x):
            return jax.device_get(x)  # graftlint: allow[GL003] cold path, runs once at checkpoint save
    """
    assert _rules(src) == []


def test_pragma_on_def_line_covers_whole_function():
    src = """
        import jax
        def grab(x):  # graftlint: allow[GL003] whole function is the swap tier
            a = jax.device_get(x)
            b = jax.device_get(a)
            return b
    """
    assert _rules(src) == []


def test_pragma_without_reason_is_gl000_and_does_not_suppress():
    src = """
        import jax
        def grab(x):
            return jax.device_get(x)  # graftlint: allow[GL003]
    """
    rules = _rules(src)
    assert "GL000" in rules  # the bare pragma is itself a finding
    assert "GL003" in rules  # and it suppressed nothing


def test_pragma_unknown_rule_is_gl000():
    src = """
        def f():
            pass  # graftlint: allow[GL999] no such rule
    """
    assert "GL000" in _rules(src)


def test_pragma_only_suppresses_named_rule():
    src = """
        import jax
        import numpy as np
        def f(x):
            return np.asarray(jax.device_get(x))  # graftlint: allow[GL003] fetch is audited upstream
    """
    rules = _rules(src)
    assert "GL003" not in rules
    assert "GL004" in rules


def test_syntax_error_reports_not_raises():
    fs = lint.lint_source("def f(:\n    pass\n")
    assert [f["rule"] for f in fs] == ["GL000"]


# ---------------------------------------------------------------------------
# baseline ratchet (library level)
# ---------------------------------------------------------------------------

def _mk_tree(tmp_path, body):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(textwrap.dedent(body))
    return pkg


def test_ratchet_allows_equal_refuses_growth(tmp_path):
    pkg = _mk_tree(tmp_path, """
        import jax
        def grab(x):
            return jax.device_get(x)
    """)
    findings = lint.lint_paths([str(pkg)], relative_to=str(tmp_path))
    base = lint.make_baseline(findings)
    assert lint.check_baseline(findings, base)["ok"]
    # one MORE device_get in the same file is a regression
    _mk_tree(tmp_path, """
        import jax
        def grab(x):
            return jax.device_get(x)
        def grab2(x):
            return jax.device_get(x)
    """)
    worse = lint.lint_paths([str(pkg)], relative_to=str(tmp_path))
    verdict = lint.check_baseline(worse, base)
    assert not verdict["ok"]
    assert any("GL003" in r for r in verdict["regressions"])


def test_ratchet_reports_improvement_on_shrink(tmp_path):
    pkg = _mk_tree(tmp_path, """
        import jax
        def grab(x):
            return jax.device_get(x)
    """)
    base = lint.make_baseline(
        lint.lint_paths([str(pkg)], relative_to=str(tmp_path)))
    _mk_tree(tmp_path, "def grab(x):\n    return x\n")
    verdict = lint.check_baseline(
        lint.lint_paths([str(pkg)], relative_to=str(tmp_path)), base)
    assert verdict["ok"]
    assert any("tighten" in i for i in verdict["improvements"])


def test_ratchet_refuses_new_file_even_if_total_flat(tmp_path):
    """Per-file ratchet: moving a finding to a new file is still a
    regression for that file — counts are not fungible across files."""
    pkg = _mk_tree(tmp_path, """
        import jax
        def grab(x):
            return jax.device_get(x)
    """)
    base = lint.make_baseline(
        lint.lint_paths([str(pkg)], relative_to=str(tmp_path)))
    (pkg / "mod.py").write_text("def grab(x):\n    return x\n")
    (pkg / "other.py").write_text(
        "import jax\ndef g(x):\n    return jax.device_get(x)\n")
    verdict = lint.check_baseline(
        lint.lint_paths([str(pkg)], relative_to=str(tmp_path)), base)
    assert not verdict["ok"]
    assert any("pkg/other.py" in r for r in verdict["regressions"])


# ---------------------------------------------------------------------------
# CLI exit conventions + the repo's own gate
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_injected_item_exits_3(tmp_path):
    """The acceptance case: freeze a baseline over a guarded tree, inject a
    new ``.item()`` into a guarded file, and the gate exits 3 naming
    GL001."""
    pkg = tmp_path / "guarded"
    pkg.mkdir()
    mod = pkg / "engine.py"
    mod.write_text("def step(state):\n    return state\n")
    bl = tmp_path / "baseline.json"
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline", str(bl),
              "--write-baseline"])
    assert r.returncode == 0, r.stderr
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline", str(bl)])
    assert r.returncode == 0, r.stdout + r.stderr
    # the injection
    mod.write_text("def step(state):\n    loss = state.loss.item()\n"
                   "    return state, loss\n")
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline", str(bl)])
    assert r.returncode == 3, r.stdout + r.stderr
    assert "GL001" in r.stdout


@pytest.mark.slow
def test_cli_malformed_baseline_exits_2(tmp_path):
    pkg = tmp_path / "guarded"
    pkg.mkdir()
    (pkg / "m.py").write_text("x = 1\n")
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline", str(bad)])
    assert r.returncode == 2
    # valid JSON, wrong shape
    bad.write_text(json.dumps({"tool": "something_else"}))
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline", str(bad)])
    assert r.returncode == 2
    assert "malformed" in r.stderr
    # missing file
    r = _run([GRAFTLINT, "--scan-root", str(pkg), "--baseline",
              str(tmp_path / "absent.json")])
    assert r.returncode == 2


@pytest.mark.slow
def test_repo_gate_is_clean_and_baseline_checked_in():
    """Acceptance: graftlint over the repo reports 0 unbaselined findings
    with the checked-in baseline."""
    assert os.path.exists(LINT_BASELINE)
    r = _run([GRAFTLINT, "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["ok"] and not doc["regressions"]


@pytest.mark.slow
def test_perf_gate_dry_run_includes_lint():
    r = _run([PERF_GATE, "--baseline",
              os.path.join(REPO_ROOT, "BASELINE.json"), "--dry-run"])
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["inputs_ok"]
    assert doc["lint"]["findings"] == sum(doc["lint"]["counts"].values())


# ---------------------------------------------------------------------------
# satellite: accounted serving fetch + injectable clocks
# ---------------------------------------------------------------------------

def test_telemetry_span_uses_injectable_clock(monkeypatch):
    from deepspeed_tpu.telemetry import core

    t = [100.0]

    def fake_now():
        t[0] += 1.5
        return t[0]

    monkeypatch.setattr(core, "_now", fake_now)
    tm = core.Telemetry()
    tm.enabled = True
    sp = tm.span("pinned")
    dt = sp.end()
    assert dt == pytest.approx(1.5)  # exactly one tick between begin/end
    assert tm.span_stats["pinned"] == [1, pytest.approx(1.5)]


def test_telemetry_run_id_uses_wall_alias(monkeypatch):
    from deepspeed_tpu.telemetry import core
    monkeypatch.delenv("DS_TPU_HARNESS_RUN_ID", raising=False)
    monkeypatch.setattr(core, "_now_wall", lambda: 1234567890.9)
    tm = core.Telemetry()
    assert tm.run_id.endswith("-1234567890")


def test_autotuning_budget_pinned_by_fake_clock(monkeypatch):
    """With the module clock pinned, the second experiment is skipped the
    deterministic moment the fake clock crosses tuning_budget_s — no
    sleeps, no wall-clock flake."""
    from deepspeed_tpu.autotuning import scheduler as sched_mod

    t = [0.0]
    monkeypatch.setattr(sched_mod, "_now", lambda: t[0])
    monkeypatch.setattr(sched_mod.time, "sleep", lambda s: None)
    rm = sched_mod.ResourceManager(hosts=1, tuning_budget_s=10.0)
    rm.schedule_experiments([{"name": "a"}, {"name": "b"}])

    def run_fn(exp, res):
        t[0] += 11.0  # the first experiment burns the whole budget
        return {"metric": 1.0}

    done = rm.run(run_fn)
    assert done["a"]["result"] == {"metric": 1.0}
    assert "budget" in done["b"]["error"]


def test_serving_decode_round_is_one_accounted_fetch():
    """One scheduler decode round = exactly one host_fetch (the sampled-ids
    fetch), counted on engine.host_sync_count and attributed to the
    host_sync telemetry counter — the audit the GL003/GL004 rules funnel
    serving code toward."""
    jax = pytest.importorskip("jax")
    import numpy as np
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu import telemetry

    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32,
                          "max_context": 64, "num_kv_blocks": 16},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine, token_budget=16, device_sampling=True)
    sched.submit(1, np.array([2, 3, 4, 5], np.int32), max_new_tokens=3)

    tm = telemetry.get_telemetry()
    tm.configure(enabled=True)
    try:
        sched.step()  # prefill round (also one fetch)
        before = engine.host_sync_count
        sched.step()  # one decode round
        assert engine.host_sync_count == before + 1
        key = ("what", "scheduler/sampled_ids")
        per = tm.counters.get("host_sync", {})
        assert any(key in tags for tags in per)
    finally:
        tm.configure(enabled=False)
        tm.reset()
