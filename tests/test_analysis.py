"""Static-analysis subsystem tests (DESIGN.md §11).

Acceptance coverage for the analysis PR:
  * an injected weak_type flip (the PR 4 solved-trim bug class) is caught
    by the retrace sanitizer with an error NAMING the flipped argument,
  * an injected extra-dot regression fails the census budget check with
    the offending budget line (and regeneration instructions) in the
    message,
  * the checked-in ANALYSIS_BUDGETS.json statically asserts the ADC-less
    claim (pallas frontend: 1 dot, 0 convs) and the live jaxpr census
    still matches it,
  * each AST rule fires on a minimal synthetic source and stays quiet on
    the compliant variant; inline + budget-file waivers work; the repo
    itself lints clean.
"""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import astlint, census, tracecheck

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS = os.path.join(ROOT, census.BUDGETS_BASENAME)


# --- tracecheck: the retrace sanitizer --------------------------------------

class TestTracecheck:
    def test_weak_type_flip_is_caught_and_named(self):
        """The PR 4 repro: a solved trim passed back as a Python scalar
        flips weak_type and silently retraces — the sanitizer must name
        the argument and the flip."""
        @jax.jit
        def step(params, trim):
            return params["w"] * trim

        params = {"w": jnp.ones((4,))}
        with tracecheck.capture() as rec:
            step(params, jnp.asarray(1.0, jnp.float32))   # strong f32[]
            step(params, 1.0)                             # weak f32[] !
        with pytest.raises(tracecheck.RetraceError) as ei:
            tracecheck.assert_jit_cache(step, 1, recorder=rec, what="step")
        msg = str(ei.value)
        assert "trim" in msg                      # the offending argument
        assert "weak_type" in msg                 # what changed about it
        assert "False -> True" in msg

    def test_no_retrace_raises_at_the_offending_call(self):
        @jax.jit
        def f(x):
            return x + 1

        with pytest.raises(tracecheck.RetraceError) as ei:
            with tracecheck.no_retrace():
                f(jnp.zeros((3,)))
                f(jnp.zeros((4,)))                # shape change
        assert "shape" in str(ei.value)
        assert "x" in str(ei.value)

    def test_no_retrace_allowlist(self):
        @jax.jit
        def f(x):
            return x * 2

        with tracecheck.no_retrace(allow=[f]):
            f(jnp.zeros((3,)))
            f(jnp.zeros((4,)))                    # allowed to retrace

    def test_clean_stream_passes(self):
        @jax.jit
        def f(x):
            return x - 1

        with tracecheck.capture() as rec:
            for i in range(4):
                f(jnp.full((3,), float(i)))
        tracecheck.assert_jit_cache(f, 1, recorder=rec)
        assert rec.explain_retraces(f) is None

    def test_assert_without_recorder_still_reports_count(self):
        @jax.jit
        def f(x):
            return x

        f(jnp.zeros((2,)))
        f(jnp.zeros((3,)))
        with pytest.raises(tracecheck.RetraceError, match="is 2"):
            tracecheck.assert_jit_cache(f, 1)

    def test_patch_restores_on_exit(self):
        from jax._src.interpreters import partial_eval as _pe
        before = _pe.trace_to_jaxpr
        with tracecheck.capture():
            with tracecheck.capture():        # nested: one shared patch
                pass
            assert _pe.trace_to_jaxpr is not before
        assert _pe.trace_to_jaxpr is before


# --- census: budgets and the injected-regression path -----------------------

def _toy_entry(fn, *args):
    return {"jaxpr": census.jaxpr_census(fn, *args),
            "hlo": census.hlo_census(fn, *args)[0]}


class TestCensus:
    def test_jaxpr_census_counts(self):
        def f(x, key):
            y = x @ x                              # one dot
            z = jax.random.uniform(key, x.shape)   # rng
            return jnp.take(y + z, jnp.arange(2), axis=0)   # gather

        c = census.jaxpr_census(jax.jit(f), jnp.ones((4, 4)),
                                jax.random.PRNGKey(0))
        assert c["dot_general"] == 1
        assert c["conv"] == 0
        assert c["rng"] >= 1
        assert c["gather"] >= 1
        assert c["f64_convert"] == 0

    def test_injected_extra_dot_fails_budget_with_diff(self):
        """Acceptance: force a second dot into a budgeted step — the check
        must fail, quote the drifted budget line, and carry the
        --update-budgets instructions."""
        x = jnp.ones((8, 8))
        one_dot = jax.jit(lambda a: a @ a)
        two_dot = jax.jit(lambda a: (a @ a) @ a)
        budgets = {"census": {"toy.step": _toy_entry(one_dot, x)},
                   "waivers": {"census": [], "ast": []}}
        ok = census.check({"toy.step": _toy_entry(one_dot, x)}, budgets)
        assert ok == []
        fails = census.check({"toy.step": _toy_entry(two_dot, x)}, budgets)
        assert fails, "extra dot must fail the budget check"
        joined = "\n".join(fails)
        assert "toy.step.hlo.dot_count: budget 1, current 2" in joined
        assert "--update-budgets" in joined

    def test_budget_drift_fails_in_both_directions(self):
        """An improvement is ALSO a failure: the stale budget must be
        regenerated so the next regression is caught at the new level."""
        budgets = {"census": {"e": {"hlo": {"dot_count": 2}}},
                   "waivers": {"census": []}}
        fails = census.budget_failures({"e": {"hlo": {"dot_count": 1}}},
                                       budgets)
        assert any("budget 2, current 1" in f for f in fails)

    def test_census_waiver_skips_field(self):
        budgets = {"census": {"e": {"hlo": {"dot_count": 2}}},
                   "waivers": {"census": [{"entry": "e",
                                           "field": "hlo.dot_count",
                                           "reason": "toy"}]}}
        assert census.budget_failures({"e": {"hlo": {"dot_count": 1}}},
                                      budgets) == []

    def test_unbudgeted_entry_is_a_failure(self):
        budgets = {"census": {}, "waivers": {"census": []}}
        fails = census.budget_failures({"new.entry": {"hlo": {}}}, budgets)
        assert any("no budget" in f for f in fails)

    def test_checked_in_budget_asserts_adc_less_pallas(self):
        """The repo budget file statically pins the paper's ADC-less
        claim: the pallas frontend step is ONE dot, ZERO convs."""
        with open(BUDGETS) as f:
            doc = json.load(f)
        hlo = doc["census"]["frontend.pallas"]["hlo"]
        assert hlo["dot_count"] == 1
        assert hlo["conv_count"] == 0
        jx = doc["census"]["frontend.pallas"]["jaxpr"]
        assert jx["dot_general"] == 1
        assert jx["conv"] == 0
        assert jx["f64_convert"] == 0

    def test_live_frontend_jaxpr_census_matches_budget(self):
        """Trace (no compile — cheap) the four frontend backends and hold
        them to the checked-in jaxpr budgets."""
        results = census.collect(["frontend"], hlo=False)
        doc = census.load_budgets(BUDGETS)
        for entry, r in results.items():
            assert r["jaxpr"] == doc["census"][entry]["jaxpr"], entry

    def test_structural_rules_fire_on_conv_in_pallas(self):
        bad = {"frontend.pallas": {"hlo": {"dot_count": 1, "conv_count": 2,
                                           "matmul_flops": 1.0}}}
        fails = census.structural_failures(bad)
        assert any("frontend.pallas.hlo.conv_count" in f for f in fails)


# --- astlint: rule catalog on synthetic sources -----------------------------

def _lint(source: str, protected=None, rel="src/repro/x.py"):
    lint = astlint._FileLint("x.py", rel, textwrap.dedent(source),
                             protected or {})
    return lint.run()


def _rules(vs):
    return [v.rule for v in vs]


class TestAstRules:
    def test_vmap_outside_jit_flagged(self):
        vs = _lint("import jax\ny = jax.vmap(f)(x)\n")
        assert _rules(vs) == ["vmap-needs-jit"]

    def test_vmap_under_jit_call_ok(self):
        assert _lint("import jax\ng = jax.jit(jax.vmap(f))\n") == []

    def test_vmap_in_jitted_function_ok(self):
        src = """
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            return jax.vmap(inner)(x)
        """
        assert _lint(src) == []

    def test_wallclock_single_clock_rule(self):
        assert _rules(_lint("import time\nt = time.time()\n")) == \
            ["no-wallclock"]
        # single-clock rule: perf_counter is banned everywhere ...
        assert _rules(_lint("import time\nt = time.perf_counter()\n")) == \
            ["no-wallclock"]
        # ... except inside repro.obs.clock itself, the one sanctioned site
        assert _lint("import time\nt = time.perf_counter()\n",
                     rel="src/repro/obs/clock.py") == []

    def test_host_rng_flagged(self):
        assert _rules(_lint("import numpy as np\nx = np.random.rand(3)\n")) \
            == ["no-host-rng"]
        assert _rules(_lint("import jax\nk = jax.random.PRNGKey(0)\n")) == \
            ["no-host-rng"]
        # a seed threaded from the caller is the sanctioned pattern
        assert _lint("import jax\nk = jax.random.PRNGKey(seed)\n") == []

    def test_frozen_config_rule(self):
        bad = """
        import dataclasses

        @dataclasses.dataclass
        class FooConfig:
            a: int = 1
        """
        assert _rules(_lint(bad)) == ["frozen-config"]
        good = bad.replace("@dataclasses.dataclass",
                           "@dataclasses.dataclass(frozen=True)")
        assert _lint(good) == []

    def test_physics_constant_fork_flagged_outside_core(self):
        protected = {0.9717: "core/mtj.py"}
        vs = _lint("P_READ = 0.9717\n", protected=protected)
        assert _rules(vs) == ["physics-constants"]
        assert "core/mtj.py" in vs[0].message
        # the same literal inside core/ is the definition, not a fork
        assert _lint("P_READ = 0.9717\n", protected=protected,
                     rel="src/repro/core/mtj.py") == []

    def test_inline_waiver_suppresses(self):
        src = ("import time\n"
               "t = time.time()  # analysis: waive=no-wallclock\n")
        assert _lint(src) == []

    def test_budget_waiver_matches_rule_and_path(self):
        vs = [astlint.Violation("no-wallclock", "src/repro/x.py", 2, "m")]
        rem, waived = astlint.apply_waivers(
            vs, [{"rule": "no-wallclock", "path": "src/repro/x.py",
                  "reason": "toy"}])
        assert rem == [] and len(waived) == 1

    def test_waiver_without_reason_rejected(self):
        with pytest.raises(ValueError, match="reason"):
            astlint.apply_waivers([], [{"rule": "r", "path": "p"}])

    def test_sig_digits_filter(self):
        assert astlint._sig_digits(0.9717) == 4
        assert astlint._sig_digits(0.062) == 2
        assert astlint._sig_digits(1400.0) == 2
        assert astlint._sig_digits(0.9) == 1          # generic: unprotected
        assert astlint._sig_digits(3.0) == 1


class TestImportGraph:
    def test_orphan_detected_in_synthetic_repo(self, tmp_path):
        pkg = tmp_path / "src" / "repro"
        pkg.mkdir(parents=True)
        (pkg / "used.py").write_text("X = 1\n")
        (pkg / "dead.py").write_text("Y = 2\n")
        tdir = tmp_path / "tests"
        tdir.mkdir()
        (tdir / "test_used.py").write_text("from repro import used\n")
        vs = astlint.orphan_modules(str(tmp_path))
        assert [v.path for v in vs] == [os.path.join("src", "repro",
                                                     "dead.py")]
        assert vs[0].rule == "orphan-module"

    def test_repo_has_no_orphans(self):
        assert astlint.orphan_modules(ROOT) == []


class TestRepoIsClean:
    def test_repo_lints_clean_with_checked_in_waivers(self):
        doc = census.load_budgets(BUDGETS)
        remaining, waived = astlint.run(
            ROOT, doc.get("waivers", {}).get("ast", []))
        assert remaining == [], "\n".join(str(v) for v in remaining)
        # the waiver list is not a dead config: it actively covers findings
        assert waived
