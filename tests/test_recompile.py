"""The recompilation auditor (DESIGN.md §9.3): the counter sees every real
XLA compile and nothing on cache hits, the budget checker fails on synthetic
retraces, and the audit JSON round-trips through the env-var hook."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import recompile

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_counter_sees_compiles_not_cache_hits():
    @jax.jit
    def poly(x):
        return x * x + 3.0 * x

    # inputs built OUTSIDE the scope: eager array creation compiles tiny
    # programs of its own (broadcast_in_dim etc.) which the counter —
    # correctly — would also see
    a4, b4 = jnp.ones((4,), jnp.float32), jnp.zeros((4,), jnp.float32)
    a9, b9 = jnp.ones((9,), jnp.float32), jnp.full((9,), 2.0, jnp.float32)
    with recompile.count_compilations() as log:
        poly(a4)                                # compile 1
        poly(b4)                                # cache hit: same shape/dtype
        poly(a9)                                # compile 2: new shape
        poly(b9)                                # cache hit again
    assert log.total == 2, log.counts
    assert any("poly" in name for name in log.counts), log.counts


def test_counter_catches_per_call_closure_retraces():
    """The bug class the budget exists for: wrapping a fresh closure in
    jax.jit per call compiles every time despite identical math."""
    x = jnp.ones((4,), jnp.float32)
    with recompile.count_compilations() as log:
        for _ in range(3):
            fn = jax.jit(lambda x: x + 1.0)     # fresh closure: cache miss
            fn(x)
    assert log.total == 3, log.counts


def test_counting_scope_detaches_cleanly():
    # the scope must restore the flag to whatever it found — it may be ON
    # when the whole pytest session runs under REPRO_RECOMPILE_AUDIT
    prev_flag = jax.config.jax_log_compiles
    x3, x5 = jnp.ones((3,), jnp.float32), jnp.ones((5,), jnp.float32)
    with recompile.count_compilations() as log:
        jax.jit(lambda x: x * 2.0)(x3)
    before = log.total
    assert before >= 1
    # outside the scope nothing is recorded anymore
    jax.jit(lambda x: x * 4.0)(x5)
    assert log.total == before
    assert jax.config.jax_log_compiles == prev_flag


def test_counting_inside_obs_trace_span_is_complete():
    """Counting scopes nest inside obs.trace spans without losing compiles:
    the tracer's jax.profiler annotation must not perturb the logging hook
    the counter rides on (observability layered over the audit — both see
    the same program launches)."""
    from repro import obs

    x4, x7 = jnp.ones((4,), jnp.float32), jnp.ones((7,), jnp.float32)
    with recompile.count_compilations() as outer:
        with obs.trace("test.outer-span", case="nested"):
            jax.jit(lambda x: x - 1.0)(x4)          # compile 1
            with recompile.count_compilations() as inner:
                with obs.trace("test.inner-span"):
                    jax.jit(lambda x: x / 2.0)(x7)  # compile 2
            jax.jit(lambda x: x * 3.0)(x4)          # compile 3 (outer only)
    assert inner.total == 1, inner.counts
    # nothing dropped: the outer scope saw every compile, incl. the inner
    # span's; nothing double-counted: exactly 3
    assert outer.total == 3, outer.counts


def test_absorb_counts_during_active_scope_neither_drops_nor_doubles(
        monkeypatch):
    """The forked-bench-worker path (absorb_counts) used simultaneously with
    a local counting scope: worker counts fold into the INSTALLED process
    audit exactly once, and the local scope keeps seeing only its own
    in-process compiles."""
    installed = recompile.CompilationLog()
    monkeypatch.setattr(recompile, "_installed", installed)
    x6 = jnp.ones((6,), jnp.float32)
    with recompile.count_compilations() as local:
        jax.jit(lambda x: x + 5.0)(x6)              # in-process compile
        # a forked worker reports back mid-scope (absorb_counts' protocol)
        recompile.absorb_counts({"worker_sweep": 4})
        recompile.absorb_counts({"worker_sweep": 1, "worker_predict": 2})
    # absorbed counts land on the installed audit log, accumulated not
    # overwritten, and never leak into the local scope's counts
    assert installed.counts == {"worker_sweep": 5, "worker_predict": 2}
    assert "worker_sweep" not in local.counts
    assert local.total == 1, local.counts


# ------------------------------------------------------------------ budget


def test_check_budget_passes_within_ceiling():
    budget = {"tier1_suite": {"max_compiles": 10}}
    assert recompile.check_budget("tier1_suite", 10, budget) == []
    assert recompile.check_budget("tier1_suite", 3, budget) == []


def test_check_budget_fails_on_synthetic_retrace():
    log = recompile.CompilationLog()
    for _ in range(12):
        log.record("leaky_program")             # synthetic retrace storm
    budget = {"tier1_suite": {"max_compiles": 10}}
    violations = recompile.check_budget("tier1_suite", log.total, budget)
    assert len(violations) == 1
    assert "exceed the budget" in violations[0]


def test_check_budget_fails_on_missing_entry():
    violations = recompile.check_budget("new_process", 1, {})
    assert len(violations) == 1 and "no budget" in violations[0]


def test_checked_in_budget_covers_the_audited_entries():
    budget = recompile.load_budget(
        os.path.join(REPO, "tools", "recompile_budget.json"))
    # the two processes CI audits must have declared ceilings
    assert "tier1_suite" in budget
    assert "bench_faults" in budget
    for entry, spec in budget.items():
        assert int(spec["max_compiles"]) > 0, entry


def test_load_budget_rejects_missing_entries_key(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"tier1_suite": {"max_compiles": 5}}))
    with pytest.raises(ValueError, match="entries"):
        recompile.load_budget(str(p))


def test_absorb_counts_merges_into_installed_log(monkeypatch):
    """Forked bench workers report counts over stdout; absorb_counts folds
    them into the parent's audit — and is a no-op when auditing is off."""
    recompile.absorb_counts({"sweep": 5})       # off: must not raise
    log = recompile.CompilationLog()
    log.record("sweep")
    monkeypatch.setattr(recompile, "_installed", log)
    recompile.absorb_counts({"sweep": 2, "run_fn": 1})
    assert log.counts == {"sweep": 3, "run_fn": 1}
    assert log.total == 4


# ------------------------------------------------------------- audit files


def test_write_audit_roundtrip(tmp_path):
    log = recompile.CompilationLog()
    log.record("sweep")
    log.record("sweep")
    log.record("run_fn")
    path = tmp_path / "audit.json"
    recompile.write_audit(str(path), "tier1_suite", log)
    data = json.loads(path.read_text())
    assert data == {"entry": "tier1_suite", "total": 3,
                    "counts": {"run_fn": 1, "sweep": 2}}


def test_install_from_env_disabled_without_var(monkeypatch):
    monkeypatch.delenv("REPRO_RECOMPILE_AUDIT", raising=False)
    assert recompile.install_from_env("tier1_suite") is None


def test_install_from_env_writes_at_exit(tmp_path):
    """End-to-end through a real interpreter: the atexit hook writes the
    audit, and the check CLI passes/fails it against a budget."""
    audit = tmp_path / "audit.json"
    budget = tmp_path / "budget.json"
    script = ("import jax, jax.numpy as jnp\n"
              "from repro.analysis import recompile\n"
              "recompile.install_from_env('probe')\n"
              "jax.jit(lambda x: x + 1.0)(jnp.ones((3,), jnp.float32))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_RECOMPILE_AUDIT"] = str(audit)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(audit.read_text())
    assert data["entry"] == "probe" and data["total"] >= 1

    tool = os.path.join(REPO, "tools", "recompile_audit.py")
    budget.write_text(json.dumps(
        {"entries": {"probe": {"max_compiles": data["total"]}}}))
    ok = subprocess.run([sys.executable, tool, "check", str(audit),
                         "--budget", str(budget)], env=env,
                        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "within budget" in ok.stdout
    budget.write_text(json.dumps(
        {"entries": {"probe": {"max_compiles": data["total"] - 1}}}))
    bad = subprocess.run([sys.executable, tool, "check", str(audit),
                         "--budget", str(budget)], env=env,
                        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1
    assert "BUDGET VIOLATION" in bad.stderr
