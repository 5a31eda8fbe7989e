"""splint — the project-native static-analysis pass (tools/splint).

Tier-1 wiring: the analyzer runs over splatt_tpu/ and the build fails
on any non-baselined finding, so the dispatch/resilience/recompilation
invariants (docs/static-analysis.md) are machine-checked on every test
run, not re-litigated in review.  Per-rule fixtures under
tests/splint_fixtures/ pin each rule's detection with one known-bad
and one known-good example.
"""

import ast
import json
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "splint_fixtures"

sys.path.insert(0, str(REPO))  # `tools` is importable from the root

from tools.splint import (Config, load_baseline, load_config, run,  # noqa: E402
                          update_baseline)
from tools.splint.config import _parse_table  # noqa: E402


def _cfg(**overrides) -> Config:
    cfg = load_config(REPO)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _rule_findings(report, rule: str, relpath: str):
    return [f for f in report.findings
            if f.rule == rule and f.path == relpath]


# -- the tier-1 gate --------------------------------------------------------

def test_package_has_zero_nonbaselined_findings():
    """The acceptance invariant: splint over splatt_tpu/ is clean
    modulo the justified baseline."""
    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    report = run(_cfg(), baseline=baseline)
    msg = "\n".join(f"{f.path}:{f.line}: {f.rule} {f.message}"
                    for f in report.new)
    assert report.ok, f"new splint findings:\n{msg}"


def test_zero_budget_rules_are_clean():
    """The [tool.splint] zero-rules budgets: these rules are fixed in
    code — never grandfathered, never pragma'd away wholesale.  Covers
    the PR 2 burn-down commitment (SPL001/SPL002) and the dataflow
    rules (SPL008-SPL012), whose real findings — the phased sweep's
    donated-M re-read, the inline cache opens, the undocumented
    env_platform_error event — were fixed, not baselined."""
    cfg = _cfg()
    assert {"SPL001", "SPL002", "SPL008", "SPL011"} <= set(cfg.zero_rules)
    report = run(cfg, baseline={})
    by_rule = {}
    for f in report.findings:
        by_rule.setdefault(f.rule, []).append(f)
    for rule in cfg.zero_rules:
        hits = ["{0.path}:{0.line}: {0.message}".format(f)
                for f in by_rule.get(rule, [])]
        assert not hits, f"{rule} must stay at zero findings:\n" \
                         + "\n".join(hits)


def test_baseline_never_contains_zero_budget_rules():
    """Baseline honesty for the zero-rules: the grandfathering ledger
    may not quietly absorb a rule whose budget is hard zero."""
    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    zero = set(_cfg().zero_rules)
    offending = [k for k in baseline if k.split(":")[0] in zero]
    assert not offending, offending


def test_baseline_entries_are_justified():
    """The v5 burn-down emptied the baseline — every rule is a
    zero-rule now.  Any entry that ever reappears must carry a
    human-written reason and a live count."""
    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    assert baseline == {}, \
        "the baseline was burned down to empty; do not grandfather " \
        "new findings — fix them or add a reasoned inline pragma"
    for key, entry in baseline.items():
        reason = entry.get("reason", "")
        assert reason and not reason.startswith("UNJUSTIFIED"), \
            f"baseline entry {key} lacks a human-written reason"
        assert entry["count"] > 0, f"stale baseline entry {key}"


def test_baseline_has_no_stale_or_overcounted_entries():
    """Every baseline entry matches reality: no stale groups (0
    findings) and no padded counts (fewer findings than baselined) —
    the ledger may only record what the code actually contains."""
    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    report = run(_cfg(), baseline=baseline)
    assert not report.stale, f"stale baseline entries: {report.stale}"
    assert not report.shrunk, \
        f"baseline counts exceed current findings: {report.shrunk}"


# -- per-rule fixtures ------------------------------------------------------

RULE_IDS = ["SPL000", "SPL001", "SPL002", "SPL003", "SPL004", "SPL005",
            "SPL006", "SPL007", "SPL008", "SPL009", "SPL010", "SPL011",
            "SPL012", "SPL013", "SPL014", "SPL015", "SPL016", "SPL017",
            "SPL018", "SPL019", "SPL020", "SPL021", "SPL022",
            "SPL023", "SPL024", "SPL025", "SPL026", "SPL027",
            "SPL028", "SPL029"]


@pytest.mark.parametrize("rule", RULE_IDS)
def test_rule_flags_bad_fixture(rule):
    rel = f"tests/splint_fixtures/{rule.lower()}_bad.py"
    report = run(_cfg(paths=[rel]), baseline={})
    assert _rule_findings(report, rule, rel), \
        f"{rule} found nothing in its known-bad fixture"


@pytest.mark.parametrize("rule", RULE_IDS)
def test_rule_passes_good_fixture(rule):
    rel = f"tests/splint_fixtures/{rule.lower()}_good.py"
    report = run(_cfg(paths=[rel]), baseline={})
    hits = _rule_findings(report, rule, rel)
    assert not hits, f"{rule} false positives: " + "\n".join(
        f"{f.path}:{f.line} {f.message}" for f in hits)


def test_good_fixtures_are_fully_clean():
    """The good fixtures are clean under EVERY rule, not only their
    own (cross-rule noise in an exemplar would teach the wrong idiom)."""
    rels = [f"tests/splint_fixtures/{r.lower()}_good.py"
            for r in RULE_IDS]
    report = run(_cfg(paths=rels), baseline={})
    hits = [f for f in report.findings if f.path in rels]
    assert not hits, "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in hits)


def test_hot_function_config_extends_spl003():
    rel = "tests/splint_fixtures/spl003_bad.py"
    plain = run(_cfg(paths=[rel]), baseline={})
    assert not any(f.line == 24 for f in
                   _rule_findings(plain, "SPL003", rel))
    hot = run(_cfg(paths=[rel],
                   hot_functions=[f"{rel}::hot_sweep"]), baseline={})
    assert any("hot path" in f.message for f in
               _rule_findings(hot, "SPL003", rel))


# -- pragma / baseline workflow --------------------------------------------

def test_reasonless_pragma_is_spl000_and_still_suppresses():
    rel = "tests/splint_fixtures/spl000_bad.py"
    report = run(_cfg(paths=[rel]), baseline={})
    assert _rule_findings(report, "SPL000", rel)
    assert not _rule_findings(report, "SPL005", rel)
    assert report.suppressed == 1


def test_baseline_workflow_roundtrip(tmp_path):
    """update-baseline grandfathers today's findings; a new violation
    fails; burning one down is detected as shrinkage."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    mod = pkg / "m.py"
    mod.write_text("import jax.numpy as jnp\n"
                   "A = jnp.zeros(2, jnp.float32)\n"
                   "B = jnp.zeros(2, jnp.float64)\n")
    cfg = Config(root=tmp_path, paths=["pkg"])
    bl_path = tmp_path / "baseline.json"

    first = run(cfg, baseline={})
    assert len(first.findings) == 2 and not first.ok
    entries = update_baseline(bl_path, first)
    assert entries["SPL005:pkg/m.py"]["count"] == 2
    assert "UNJUSTIFIED" in entries["SPL005:pkg/m.py"]["reason"]

    clean = run(cfg, baseline=load_baseline(bl_path))
    assert clean.ok and len(clean.findings) == 2

    mod.write_text(mod.read_text()
                   + "C = jnp.zeros(2, jnp.bfloat16)\n")
    over = run(cfg, baseline=load_baseline(bl_path))
    assert not over.ok and len(over.new) == 3  # whole group surfaces

    mod.write_text("import jax.numpy as jnp\n"
                   "A = jnp.zeros(2, jnp.float32)\n")
    shrunk = run(cfg, baseline=load_baseline(bl_path))
    assert shrunk.ok and shrunk.shrunk["SPL005:pkg/m.py"] == (1, 2)
    # reasons survive a baseline rewrite
    entries["SPL005:pkg/m.py"]["reason"] = "fixture justification"
    bl_path.write_text(json.dumps({"version": 1, "entries": entries}))
    rewritten = update_baseline(bl_path, shrunk)
    assert rewritten["SPL005:pkg/m.py"] == {
        "count": 1, "reason": "fixture justification"}


def test_spl013_declaration_drift(tmp_path):
    """Both span-drift directions, on a mini-project: an undeclared
    opened name fires at the call site, a declared-but-never-opened
    name fires at the registry, and a declared ``x.*`` family matches
    f-string opens."""
    (tmp_path / "pkg").mkdir()
    trace_mod = tmp_path / "pkg" / "trace.py"
    trace_mod.write_text(
        "SPANS = {'used.span': 'doc', 'fam.*': 'doc', "
        "'dead.span': 'doc'}\n"
        "def span(name, **attrs): ...\n"
        "def begin(name, **attrs): ...\n")
    (tmp_path / "pkg" / "prod.py").write_text(
        "from pkg import trace\n"
        "def f(k):\n"
        "    with trace.span('used.span'):\n"
        "        pass\n"
        "    trace.begin(f'fam.{k}')\n"
        "    with trace.span('rogue.span'):\n"
        "        pass\n")
    cfg = Config(root=tmp_path, paths=["pkg"],
                 trace_module="pkg/trace.py")
    msgs = [f.message for f in run(cfg, baseline={}).findings
            if f.rule == "SPL013"]
    assert any("rogue.span" in m and "not declared" in m for m in msgs)
    assert any("dead.span" in m and "never opened" in m for m in msgs)
    assert not any("used.span" in m or "fam." in m for m in msgs)
    # opening the dead span and declaring the rogue one clears the drift
    trace_mod.write_text(
        "SPANS = {'used.span': 'doc', 'fam.*': 'doc', "
        "'dead.span': 'doc', 'rogue.span': 'doc'}\n"
        "def span(name, **attrs): ...\n"
        "def begin(name, **attrs): ...\n")
    (tmp_path / "pkg" / "prod.py").write_text(
        "from pkg import trace\n"
        "def f(k):\n"
        "    with trace.span('used.span'):\n"
        "        pass\n"
        "    trace.begin(f'fam.{k}')\n"
        "    with trace.span('rogue.span'):\n"
        "        pass\n"
        "    with trace.span('dead.span'):\n"
        "        pass\n")
    assert not [f for f in run(cfg, baseline={}).findings
                if f.rule == "SPL013"]


def test_spl013_span_registry_matches_runtime():
    """The SPANS registry is importable, documented, and every name the
    summarizer special-cases (roots, iteration spans, the guard family)
    is declared — the static check and the runtime summary read the
    same surface."""
    from splatt_tpu.trace import METRICS, SPANS

    assert {"cpd.als", "cpd.iter", "dist.als", "dist.step",
            "cpd.guard.health_pack", "cpd.guard.snapshot",
            "cpd.guard.rollback", "serve.job", "trace.export",
            "timer.*"} <= set(SPANS)
    for name, doc in SPANS.items():
        assert isinstance(doc, str) and len(doc) > 10, name
    for name, (typ, doc) in METRICS.items():
        assert typ in ("counter", "gauge", "histogram"), name
        assert isinstance(doc, str) and len(doc) > 10, name


def _spl029_project(tmp_path, docs: str = None):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "trace.py").write_text(
        "METRICS = {'splatt_used_total': ('counter', 'doc'),\n"
        "           'splatt_dead_total': ('counter', 'doc'),\n"
        "           'splatt_depth': ('gauge', 'doc')}\n"
        "def metric_inc(name, value=1.0, **labels): ...\n"
        "def metric_set(name, value, **labels): ...\n"
        "def metric_observe(name, value, **labels): ...\n")
    (tmp_path / "pkg" / "prod.py").write_text(
        "from pkg import trace\n"
        "def f():\n"
        "    trace.metric_inc('splatt_used_total')\n"
        "    trace.metric_set('splatt_depth', 1.0)\n"
        "    trace.metric_inc('splatt_rogue_total')\n"
        "    trace.metric_inc('splatt_depth')\n")
    kw = {}
    if docs is not None:
        (tmp_path / "docs").mkdir(exist_ok=True)
        (tmp_path / "docs" / "obs.md").write_text(docs)
        kw["metrics_doc"] = "docs/obs.md"
    return Config(root=tmp_path, paths=["pkg"],
                  trace_module="pkg/trace.py", **kw)


def test_spl029_metric_drift(tmp_path):
    """Both registry directions plus the type check, on a
    mini-project: an undeclared recorded name fires at the call site,
    a declared-but-never-recorded name fires at the registry, and a
    counter recorded through the gauge verb (a runtime raise) is a
    finding before anything runs."""
    cfg = _spl029_project(tmp_path)
    msgs = [f.message for f in run(cfg, baseline={}).findings
            if f.rule == "SPL029"]
    assert any("splatt_rogue_total" in m and "not declared" in m
               for m in msgs)
    assert any("splatt_dead_total" in m and "never recorded" in m
               for m in msgs)
    assert any("splatt_depth" in m and "declared as a gauge" in m
               and "metric_inc" in m for m in msgs)
    assert not any("splatt_used_total" in m for m in msgs)


def test_spl029_docs_table_both_directions(tmp_path):
    """The docs legs: a declared metric missing from the configured
    metrics doc fires at the registry, and a doc-table metric the
    registry never declares is a dead promise."""
    docs = ("# metrics\n"
            "| metric | type |\n|---|---|\n"
            "| `splatt_used_total` | counter |\n"
            "| `splatt_ghost_total{x=y}` | counter |\n"
            "| `splatt_depth` | gauge |\n")
    cfg = _spl029_project(tmp_path, docs=docs)
    msgs = [f.message for f in run(cfg, baseline={}).findings
            if f.rule == "SPL029"]
    assert any("splatt_dead_total" in m and "no row" in m
               for m in msgs)
    assert any("splatt_ghost_total" in m and "never declares" in m
               for m in msgs)
    # documented + declared names are clean on the docs legs
    assert not any("splatt_used_total" in m and "row" in m
                   for m in msgs)
    # completing the table and dropping the ghost clears the docs legs
    (tmp_path / "docs" / "obs.md").write_text(
        docs.replace("| `splatt_ghost_total{x=y}` | counter |\n", "")
        + "| `splatt_dead_total` | counter |\n")
    msgs2 = [f.message for f in run(cfg, baseline={}).findings
             if f.rule == "SPL029"]
    assert not any("row" in m or "never declares" in m for m in msgs2)


def test_spl029_registry_matches_runtime_and_docs():
    """The real registry is importable and the real docs table is in
    sync (the full-tree zero gate enforces this too; this pins the
    wiring: metrics-doc configured, every metric typed + documented)."""
    cfg = _cfg()
    assert cfg.metrics_doc == "docs/observability.md"
    from splatt_tpu.trace import METRICS

    text = (REPO / "docs" / "observability.md").read_text()
    for name in METRICS:
        assert name in text, f"{name} missing from the docs table"


def test_spl006_declaration_drift(tmp_path):
    """Both drift directions: a declared-but-never-called site and a
    declared-but-untested site are findings at the registry."""
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "prod.py").write_text(
        "from pkg import faults\n"
        "faults.maybe_fail('used_site')\n")
    faults_mod = tmp_path / "pkg" / "faults.py"
    faults_mod.write_text(
        "SITES = {'used_site': 'doc', 'dead_site': 'doc'}\n"
        "def maybe_fail(site): ...\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_x.py").write_text(
        "from pkg import faults\n"
        "def test_x():\n    faults.maybe_fail('other')\n")
    cfg = Config(root=tmp_path, paths=["pkg"],
                 faults_module="pkg/faults.py", tests_path="tests")
    report = run(cfg, baseline={})
    msgs = [f.message for f in report.findings if f.rule == "SPL006"]
    assert any("dead_site" in m and "no production call" in m
               for m in msgs)
    assert any("used_site" in m and "not exercised" in m for m in msgs)
    # exercising + calling both sites clears the drift
    (tdir / "test_x.py").write_text(
        "from pkg import faults\n"
        "def test_x():\n"
        "    faults.maybe_fail('used_site')\n"
        "    faults.maybe_fail('dead_site')\n")
    (tmp_path / "pkg" / "prod.py").write_text(
        "from pkg import faults\n"
        "faults.maybe_fail('used_site')\n"
        "faults.maybe_fail('dead_site')\n")
    assert not [f for f in run(cfg, baseline={}).findings
                if f.rule == "SPL006"]


# -- dataflow engine (CFG / def-use / jit-boundary map) ---------------------

from tools.splint.core import (FileCtx, FunctionCFG,  # noqa: E402
                               def_use_chains, jit_boundary)


def _cfg_of(src: str) -> FunctionCFG:
    fn = ast.parse(textwrap.dedent(src).strip()).body[0]
    return FunctionCFG(fn)


def _use_defs_lines(cfg: FunctionCFG, name: str, kind=None):
    """{use line: sorted def lines} for every use of `name`."""
    chains = def_use_chains(cfg)
    out = {}
    for node in cfg.nodes:
        if kind is not None and node.kind != kind:
            continue
        if any(n == name for n, _ in node.uses):
            defs = chains.get((node.idx, name), set())
            out[node.line] = sorted(cfg.nodes[d].line for d in defs)
    return out


def test_cfg_branch_defs_merge_at_join():
    cfg = _cfg_of("""
        def f(c):
            if c:
                x = 1
            else:
                x = 2
            return x
    """)
    assert _use_defs_lines(cfg, "x") == {6: [3, 5]}


def test_cfg_loop_carried_defs_reach_header_and_exit():
    cfg = _cfg_of("""
        def f(xs):
            total = 0
            for x in xs:
                total = total + x
            return total
    """)
    uses = _use_defs_lines(cfg, "total")
    assert uses[4] == [2, 4]   # in-loop use: initial AND loop-carried
    assert uses[5] == [2, 4]   # after the loop: both reach the return


def test_cfg_except_handler_sees_mid_try_defs():
    """Exception edges carry defs WITHOUT the kill: the raise may have
    happened before or after the rebind, so both defs reach."""
    cfg = _cfg_of("""
        def f(boom):
            x = 1
            try:
                x = 2
                boom()
            except ValueError:
                y = x
            return x
    """)
    uses = _use_defs_lines(cfg, "x")
    assert uses[7] == [2, 4]   # the handler sees pre- and mid-try defs
    assert uses[8] == [2, 4]


def test_cfg_tuple_unpacking_defines_and_kills():
    cfg = _cfg_of("""
        def f(pair):
            a, b = pair
            b, a = a, b
            return a + b
    """)
    uses_a = _use_defs_lines(cfg, "a")
    assert uses_a[3] == [2]    # swap reads the unpacked def
    assert uses_a[4] == [3]    # return reads ONLY the re-bind (killed)
    # function parameters are definitions at the entry node
    chains = def_use_chains(cfg)
    pair_use = next(k for k in chains if k[1] == "pair")
    assert chains[pair_use] == {cfg.entry.idx}


def test_cfg_while_break_paths():
    cfg = _cfg_of("""
        def f(xs):
            y = 0
            while True:
                y = xs.pop()
                if not xs:
                    break
            return y
    """)
    assert _use_defs_lines(cfg, "y")[7] == [2, 4]


def _ctx_of(src: str) -> FileCtx:
    src = textwrap.dedent(src).strip() + "\n"
    return FileCtx(Path("mem.py"), "mem.py", src, ast.parse(src))


def test_jit_boundary_factory_chain_and_conditional_union():
    """The interprocedural map follows a factory chain and unions
    conditional donate specs — the build_sweep/_make_sweep shape."""
    ctx = _ctx_of("""
        import jax

        def _make(donate):
            def sweep(factors, grams, first):
                return factors
            return jax.jit(sweep, static_argnames=("first",),
                           donate_argnums=(0, 1) if donate else ())

        def _make_other():
            def sweep(factors, grams, first):
                return factors
            return sweep

        def build(phased, donate):
            return (_make_other if phased else _make)(donate)
    """)
    jb = jit_boundary(ctx)
    assert jb.factories["_make"].donate_argnums == {0, 1}
    assert jb.factories["_make"].static_argnames == {"first"}
    assert jb.factories["build"].donate_argnums == {0, 1}
    assert "_make_other" not in jb.factories


def test_jit_boundary_wrapped_and_traced():
    ctx = _ctx_of("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("mode",))
        def decorated(x, mode):
            return x

        def plain(a, b):
            return a + b

        wrapped = jax.jit(plain, donate_argnums=(0,))
    """)
    jb = jit_boundary(ctx)
    assert jb.wrapped["decorated"].static_argnames == {"mode"}
    assert jb.wrapped["wrapped"].donate_argnums == {0}
    traced_names = {fn.name for fn, _ in jb.traced}
    assert traced_names == {"decorated", "plain"}


# -- analyzer coverage: class methods, direct calls, loop headers -----------

from tools.splint.core import Project  # noqa: E402
from tools.splint.rules import (CacheLockDiscipline,  # noqa: E402
                                RecompileTrigger, RunReportEventDrift,
                                UseAfterDonate)


def _rule_hits(rule, src: str):
    ctx = _ctx_of(src)
    project = Project(_cfg())
    project.files.append(ctx)
    return rule.check(ctx, project) + rule.finalize(project)


_DONATING_FACTORY = """
    import jax

    def make_step(reg):
        def step(state, grad):
            return state - reg * grad
        return jax.jit(step, donate_argnums=(0,))
"""


def test_spl008_covers_class_methods():
    hits = _rule_hits(UseAfterDonate(), _DONATING_FACTORY + """
    class Driver:
        def run(self, state, grad, reg):
            step = make_step(reg)
            new = step(state, grad)
            return state + new
""")
    assert hits and "state" in hits[0].message


def test_spl008_covers_unbound_factory_invocation():
    """A donating factory invoked without ever binding the wrapper —
    make_step(reg)(state, grad) — still donates its argnums."""
    hits = _rule_hits(UseAfterDonate(), _DONATING_FACTORY + """
    def run(state, grad, reg):
        new = make_step(reg)(state, grad)
        return state + new
""")
    assert hits and "state" in hits[0].message


def test_spl010_loop_header_is_not_in_the_loop():
    """A jit call in a for-statement's ITERABLE evaluates once per
    loop entry — flagging it would hard-fail the zero-budget gate on
    correct code.  The body (and a while test) re-run per iteration."""
    clean = _rule_hits(RecompileTrigger(), """
    import jax

    def f(g, xs):
        out = []
        for step in (jax.jit(g), jax.jit(g)):
            out.append(step(xs))
        return out
""")
    assert not clean
    dirty = _rule_hits(RecompileTrigger(), """
    import jax

    def f(g, xs):
        n = 0
        while jax.jit(g)(xs) > 0:
            n += 1
        return n
""")
    assert any("inside a loop" in h.message for h in dirty)


def test_spl010_covers_class_methods():
    hits = _rule_hits(RecompileTrigger(), """
    import jax

    class Driver:
        def run(self, x):
            f = jax.jit(lambda a, cfg: a, static_argnums=(1,))
            return f(x, [1, 2, 3])
""")
    assert any("unhashable" in h.message for h in hits)


def test_spl011_covers_class_methods():
    hits = _rule_hits(CacheLockDiscipline(), """
    import json
    import pathlib

    def cache_path():
        return pathlib.Path("/tmp/c.json")

    class Store:
        def flush(self, data):
            with open(cache_path(), "w") as f:
                json.dump(data, f)
""")
    assert any("bypasses the locked" in h.message for h in hits)


def test_spl012_covers_aliased_report():
    """rr = run_report(); rr.add(...) is the same emission surface."""
    hits = _rule_hits(RunReportEventDrift(), """
    from splatt_tpu import resilience

    def emit(err):
        rr = resilience.run_report()
        rr.add("spl012_alias_undeclared_event", error=str(err))
""")
    assert any("spl012_alias_undeclared_event" in h.message
               for h in hits)


# -- the concurrency family (SPL014-SPL018, tools/splint/locks.py) ----------

from tools.splint.locks import (FileLocks,  # noqa: E402
                                iter_scope_functions, lock_walk)
from tools.splint.rules import (BlockingCallUnderLock,  # noqa: E402
                                ContextvarLeak, LockOrderCycle,
                                SharedStateWithoutLock)


def _lock_walk_of(src: str):
    ctx = _ctx_of(src)
    fl = FileLocks(ctx)
    fns = list(iter_scope_functions(ctx.tree))
    fn, cls = fns[-1]
    return ctx, lock_walk(ctx, fn, cls, fl)


def test_lock_walk_with_nesting_and_restore():
    src = """
        import threading

        _A = threading.Lock()
        _B = threading.Lock()

        def f(x):
            before = 1
            with _A:
                inside_a = 2
                with _B:
                    inside_ab = 3
                after_b = 4
            after_a = 5
    """
    ctx, walk = _lock_walk_of(src)
    held_by_line = {}
    fn = [s for s in ast.walk(ctx.tree)
          if isinstance(s, ast.FunctionDef)][0]
    for stmt in ast.walk(fn):
        if isinstance(stmt, ast.stmt) and id(stmt) in walk.held_at:
            held_by_line[stmt.lineno] = {
                h.split("::")[-1] for h in walk.held_at[id(stmt)]}
    assert held_by_line[7] == set()          # before
    assert held_by_line[9] == {"_A"}         # inside_a
    assert held_by_line[11] == {"_A", "_B"}  # inside_ab
    assert held_by_line[12] == {"_A"}        # after_b: _B restored
    assert held_by_line[13] == set()         # after_a: both restored
    # acquisition sites record the held-before sets (SPL015's edges)
    acq = {(lid.split("::")[-1], tuple(sorted(
        h.split("::")[-1] for h in held)))
        for lid, _line, held in walk.acquisitions}
    assert acq == {("_A", ()), ("_B", ("_A",))}


def test_lock_walk_acquire_release_pairs_and_closures():
    src = """
        import threading

        _A = threading.Lock()

        def f(xs):
            _A.acquire()
            xs.append(1)
            _A.release()
            xs.append(2)
            def closure():
                xs.append(3)  # runs later: NOT under _A
    """
    ctx, walk = _lock_walk_of(src)
    fn = [s for s in ast.walk(ctx.tree)
          if isinstance(s, ast.FunctionDef) and s.name == "f"][0]
    held = {s.lineno: walk.held_at[id(s)] for s in fn.body
            if id(s) in walk.held_at}
    assert not held[6]                      # before acquire
    assert any(held[7])                     # between the pair
    assert not held[9]                      # after release


def test_spl015_cross_function_cycle_and_self_loop():
    hits = _rule_hits(LockOrderCycle(), """
    import threading

    _A = threading.Lock()
    _B = threading.Lock()

    def ab():
        with _A:
            with _B:
                pass

    def ba():
        with _B:
            with _A:
                pass
""")
    assert any("cycle" in h.message and "_A" in h.message
               and "_B" in h.message for h in hits)
    # self-loop: re-acquiring a non-reentrant lock under itself
    hits = _rule_hits(LockOrderCycle(), """
    import threading

    _A = threading.Lock()

    def helper():
        with _A:
            pass

    def outer():
        with _A:
            helper()
""")
    assert any("cycle" in h.message for h in hits)


def test_spl015_interprocedural_edge_through_method_call():
    """An edge discovered through a call under a held lock: outer holds
    Server's lock while calling a helper that takes the metrics lock —
    plus the reverse nesting elsewhere closes the cycle."""
    hits = _rule_hits(LockOrderCycle(), """
    import threading

    _MET = threading.Lock()

    def record():
        with _MET:
            pass

    class Server:
        def __init__(self):
            self._lock = threading.Lock()

        def poll(self):
            with self._lock:
                record()

        def backwards(self):
            with _MET:
                with self._lock:
                    pass
""")
    assert any("cycle" in h.message for h in hits)


def test_spl017_flags_transitive_blocking_and_exempts_str_join():
    cfg = _cfg(hot_lock_paths=["mem.py::submit"])
    src = """
    import os
    import threading

    class Journal:
        def append(self, rec):
            with open("/tmp/j", "ab") as f:
                f.write(rec)
                os.fsync(f.fileno())

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.journal = Journal()

        def submit(self, jid, parts):
            with self._lock:
                label = ", ".join(parts)   # str.join: NOT blocking
                self.journal.append(label.encode())
            return jid
"""
    ctx = _ctx_of(src)
    project = Project(cfg)
    project.files.append(ctx)
    rule = BlockingCallUnderLock()
    hits = rule.check(ctx, project) + rule.finalize(project)
    assert len(hits) == 1, [h.message for h in hits]
    assert "via Journal.append" in hits[0].message
    assert "fsync" in hits[0].message or "flock" in hits[0].message


def test_spl018_enter_exit_pairs_are_exempt():
    hits = _rule_hits(ContextvarLeak(), """
    import contextvars

    _STACK = contextvars.ContextVar("stack", default=())

    class Handle:
        def __enter__(self):
            _STACK.set(_STACK.get() + (self,))
            return self

        def __exit__(self, *exc):
            _STACK.set(tuple(s for s in _STACK.get() if s is not self))
            return False
""")
    assert not hits


def test_spl014_flags_mutators_outside_bare_expressions():
    """A mutator call is a write wherever it appears — assigned
    (`jid = self._queue.pop(0)`), in a test position, in a return —
    not only as a bare expression statement (review-found gap)."""
    cfg = _cfg(shared_state=["mem.py::self._queue=self._lock"])
    src = """
    import threading

    class S:
        def __init__(self):
            self._lock = threading.Lock()
            self._queue = []

        def bad_pick(self):
            jid = self._queue.pop(0)
            return jid

        def bad_test(self):
            if self._queue.pop(0):
                return True

        def good_pick(self):
            with self._lock:
                return self._queue.pop(0)
"""
    ctx = _ctx_of(src)
    project = Project(cfg)
    project.files.append(ctx)
    hits = SharedStateWithoutLock().check(ctx, project)
    assert sorted(f.line for f in hits) == [9, 13]


def test_spl014_alias_imprecision_is_documented_not_flagged():
    """Mutation through an alias is the documented blind spot — the
    SPLATT_LOCKCHECK runtime sanitizer covers it dynamically."""
    cfg = _cfg(shared_state=["mem.py::self._jobs=self._lock"])
    src = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self._jobs = {}

        def touch(self, jid):
            j = self._jobs[jid]
            j["state"] = "started"   # alias write: not seen
"""
    ctx = _ctx_of(src)
    project = Project(cfg)
    project.files.append(ctx)
    rule = SharedStateWithoutLock()
    assert not rule.check(ctx, project)


def _copy_serve_tree(tmp_path, mutate):
    """A tmp mini-tree holding the REAL serve.py (+ its durable-write
    helper, preserving the package layout the call summaries resolve
    against), with `mutate(src) -> src` applied to serve.py."""
    pkg = tmp_path / "splatt_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "serve.py").write_text(
        mutate((REPO / "splatt_tpu" / "serve.py").read_text()))
    (pkg / "utils" / "durable.py").write_text(
        (REPO / "splatt_tpu" / "utils" / "durable.py").read_text())
    cfg = _cfg()
    cfg.root = tmp_path
    cfg.paths = ["splatt_tpu"]
    return cfg


def test_spl017_fires_when_submit_journals_under_the_lock(tmp_path):
    """Re-introducing the PR 11 submit bug — the durable accept append
    moved INSIDE the server lock — must trip SPL017 through the
    interprocedural summary (the fsync is two calls down, in the
    shared durable-write helper).  The unmutated file is clean (also
    covered by the tree gate)."""
    anchor = ("self._jobs[jid] = "
              "self._new_job_locked(spec, ACCEPTING)")

    def mutate(src):
        assert anchor in src, "serve.py submit anchor drifted"
        return src.replace(
            anchor,
            anchor + "\n                self.journal.append("
                     "self._rec(ACCEPTED, jid, spec=spec))")

    cfg = _copy_serve_tree(tmp_path, mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL017"]
    assert hits and any("Journal.append" in f.message for f in hits)


def test_spl014_fires_when_replay_drops_the_lock(tmp_path):
    """Deleting _replay's server-lock region (the pre-PR-12 shape)
    must trip SPL014 on the queue/job-table mutations — proof the
    shared-state map guards the real file, not a fixture."""
    def mutate(src):
        anchor = ("        resumed: List[tuple] = []\n"
                  "        with self._lock:")
        assert anchor in src, "serve.py _replay anchor drifted"
        return src.replace(
            anchor, "        resumed: List[tuple] = []\n"
                    "        if True:")

    cfg = _copy_serve_tree(tmp_path, mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL014"]
    assert hits and any("_queue" in f.message or "_jobs" in f.message
                        for f in hits)


def test_spl020_fires_when_backstop_fence_reverted(tmp_path):
    """Reverting the PR 17 fix — _backstop_fail's lease fence before
    its terminal FAILED append — must trip SPL020: the append is then
    reachable without a dominating renew, the exact zombie-commit
    shape the fence exists to kill."""
    anchor = "        if not self._renew_fence(jid):"

    def mutate(src):
        assert anchor in src, "serve.py _backstop_fail anchor drifted"
        return src.replace(anchor, "        if jid is None:", 1)

    cfg = _copy_serve_tree(tmp_path, mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL020"]
    assert hits and any("_backstop_fail" in f.message for f in hits)


def test_spl022_fires_when_replay_gate_reverted(tmp_path):
    """Reverting the PR 17 forward-compat gate — _apply_rec_locked's
    KNOWN_KINDS membership check — must trip SPL022's never-consulted
    leg: a declared vocabulary replay no longer reads is exactly the
    drift the rule polices."""
    anchor = "if kind not in KNOWN_KINDS:"

    def mutate(src):
        assert anchor in src, "serve.py replay-gate anchor drifted"
        return src.replace(anchor, "if not isinstance(kind, str):", 1)

    cfg = _copy_serve_tree(tmp_path, mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL022"]
    assert hits and any("KNOWN_KINDS" in f.message for f in hits)


def test_spl019_fires_when_publish_dir_fsync_reverted(tmp_path):
    """Reverting the PR 17 durability fix — publish_bytes' post-rename
    directory fsync — must trip SPL019 on the helper itself: without
    the barrier the rename can be lost on power failure after the
    caller was acknowledged (the crash-point checker's rename-lost
    states show the resulting data loss dynamically)."""
    pkg = tmp_path / "splatt_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "serve.py").write_text(
        (REPO / "splatt_tpu" / "serve.py").read_text())
    src = (REPO / "splatt_tpu" / "utils" / "durable.py").read_text()
    anchor = ("        os.replace(tmp, path)\n"
              "        if fsync:\n"
              "            _fsync_dir(path)")
    assert anchor in src, "durable.py publish_bytes anchor drifted"
    (pkg / "utils" / "durable.py").write_text(
        src.replace(anchor, "        os.replace(tmp, path)", 1))
    cfg = _cfg()
    cfg.root = tmp_path
    cfg.paths = ["splatt_tpu"]
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL019"
            and f.path.endswith("durable.py")]
    assert hits and any("publish_bytes" in f.message for f in hits)


def test_shared_state_config_is_well_formed():
    """Every [tool.splint] shared-state / hot-lock-paths entry parses
    and points at a real file (a typo'd map silently unguards)."""
    from tools.splint.rules import _parse_shared_state

    cfg = _cfg()
    by_file = _parse_shared_state(cfg.shared_state)
    assert "splatt_tpu/serve.py" in by_file
    assert ("self._jobs", "self._lock") in by_file["splatt_tpu/serve.py"]
    for rel in by_file:
        assert (REPO / rel).is_file(), rel
    for entry in cfg.hot_lock_paths:
        rel, name = entry.split("::")
        assert (REPO / rel).is_file(), rel
    with pytest.raises(ValueError):
        _parse_shared_state(["no-separator"])


# -- the SPL008 guard: cpd.py's re-materialization is load-bearing ----------

def test_spl008_fires_when_cpd_rematerialization_deleted(tmp_path):
    """Deleting the engine-rescue re-materialization lines from cpd.py
    must make SPL008 fire — proof the analyzer actually guards the
    donated-sweep contract rather than pattern-matching today's file."""
    src = (REPO / "splatt_tpu" / "cpd.py").read_text()
    targets = ["factors = [jnp.asarray(u) for u in snap[0]]",
               "grams = [jnp.asarray(g) for g in snap[1]]"]
    mutated = src
    for t in targets:
        assert t in mutated, f"cpd.py no longer contains {t!r}"
        mutated = mutated.replace(t, "pass")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "cpd.py").write_text(mutated)
    report = run(Config(root=tmp_path, paths=["pkg"]), baseline={})
    hits = [f for f in report.findings if f.rule == "SPL008"]
    assert hits, "SPL008 must fire once the re-materialization is gone"
    assert any("factors" in f.message or "grams" in f.message
               for f in hits)
    # the unmutated file is clean (also covered by the tree gate)
    (pkg / "cpd.py").write_text(src)
    report = run(Config(root=tmp_path, paths=["pkg"]), baseline={})
    assert not [f for f in report.findings if f.rule == "SPL008"]


# -- entry points stay in lockstep ------------------------------------------

def test_cli_json_matches_pytest_wiring():
    """`python -m tools.splint --json` (the CLI/CI entry) agrees with
    the in-process run the tests use."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    report = run(_cfg(), baseline=baseline)
    assert len(payload["findings"]) == len(report.findings)


def test_cli_focus_analyzes_full_tree():
    """Positional paths focus the report only: no false SPL006 drift
    from a partial view, and a focused --update-baseline still rewrites
    from the full tree instead of destroying unanalyzed files' entries."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "splatt_tpu/ops"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no production call" not in proc.stdout
    assert "focused on splatt_tpu/ops" in proc.stdout


def test_cli_focused_update_baseline_keeps_all_groups(tmp_path):
    bl = tmp_path / "bl.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "splatt_tpu/ops",
         "--baseline", str(bl), "--update-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    repo_groups = set(load_baseline(
        REPO / "tools" / "splint" / "baseline.json"))
    assert set(load_baseline(bl)) == repo_groups


def test_cli_json_lockstep_for_dataflow_rules():
    """CLI --json findings for the SPL008-SPL012 family agree exactly
    (rule, path, line) with the in-process run pytest gates on."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--json", "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    payload = json.loads(proc.stdout)
    new_rules = {"SPL008", "SPL009", "SPL010", "SPL011", "SPL012"}
    cli = sorted((f["rule"], f["path"], f["line"])
                 for f in payload["findings"] if f["rule"] in new_rules)
    report = run(_cfg(), baseline={})
    mine = sorted((f.rule, f.path, f.line)
                  for f in report.findings if f.rule in new_rules)
    assert cli == mine


def test_cli_json_lockstep_for_concurrency_rules(tmp_path):
    """CLI --json ≡ in-process for the SPL014-SPL018 family, on a
    mini-project holding the bad fixtures (the production tree is
    clean for them by the zero-budget gate, so lockstep there would
    compare empty sets).  Same pyproject, same analyzer, same
    findings — the CI entry point cannot drift from the pytest gate."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    for n in ("014", "015", "016", "017", "018"):
        name = f"spl{n}_bad.py"
        (pkg / name).write_text((FIXTURES / name).read_text())
    (tmp_path / "pyproject.toml").write_text(
        '[tool.splint]\n'
        'paths = ["pkg"]\n'
        'shared-state = ["pkg/spl014_bad.py::self._jobs=self._lock",\n'
        '               "pkg/spl014_bad.py::_TABLE=_TABLE_LOCK"]\n'
        'durable-write-helpers = ["publish_bytes"]\n'
        'hot-lock-paths = ["pkg/spl017_bad.py::submit_hot"]\n')
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--root", str(tmp_path),
         "--json", "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    payload = json.loads(proc.stdout)
    fam = {"SPL014", "SPL015", "SPL016", "SPL017", "SPL018"}
    cli = sorted((f["rule"], f["path"], f["line"])
                 for f in payload["findings"] if f["rule"] in fam)
    report = run(load_config(tmp_path), baseline={})
    mine = sorted((f.rule, f.path, f.line)
                  for f in report.findings if f.rule in fam)
    assert cli and cli == mine
    assert {r for r, _, _ in cli} == fam  # every rule fires somewhere


def test_cli_sarif_structure(tmp_path):
    """`--sarif` writes a SARIF 2.1.0 log whose results agree with the
    --json findings — the CI code-scanning upload cannot drift from
    the gate.  Checked on a mini-project where SPL024 actually fires
    (the production tree is clean, so its results array is empty)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "spl024_bad.py").write_text(
        (FIXTURES / "spl024_bad.py").read_text())
    (tmp_path / "pyproject.toml").write_text(
        '[tool.splint]\n'
        'paths = ["pkg"]\n'
        'numerics-modules = ["pkg/spl024_bad.py"]\n')
    sarif_path = tmp_path / "out.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--root", str(tmp_path),
         "--sarif", str(sarif_path), "--json", "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    sarif = json.loads(sarif_path.read_text())
    assert sarif["version"] == "2.1.0"
    driver = sarif["runs"][0]["tool"]["driver"]
    assert driver["name"] == "splint"
    by_id = {r["id"]: r for r in driver["rules"]}
    assert "SPL024" in by_id
    assert len(by_id["SPL024"]["shortDescription"]["text"]) > 10
    results = sarif["runs"][0]["results"]
    got = sorted(
        (r["ruleId"],
         r["locations"][0]["physicalLocation"]["artifactLocation"]["uri"],
         r["locations"][0]["physicalLocation"]["region"]["startLine"])
        for r in results)
    want = sorted((f["rule"], f["path"], f["line"])
                  for f in payload["findings"])
    assert got and got == want
    assert all(r["ruleId"] in by_id for r in results)
    # new findings carry no suppression; none are baselined here
    assert not any("suppressions" in r for r in results)
    # the clean production tree writes an empty results array
    clean_path = tmp_path / "clean.sarif"
    clean = subprocess.run(
        [sys.executable, "-m", "tools.splint",
         "--sarif", str(clean_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert json.loads(clean_path.read_text())["runs"][0]["results"] == []


def test_cli_list_rules_covers_new_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rid in ("SPL008", "SPL009", "SPL010", "SPL011", "SPL012",
                "SPL014", "SPL015", "SPL016", "SPL017", "SPL018",
                "SPL024", "SPL025", "SPL026", "SPL027", "SPL028",
                "SPL029"):
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith(rid)), "")
        assert line and len(line.split(None, 1)[1]) > 10, \
            f"--list-rules lacks a one-line summary for {rid}"


def test_cli_explain_prints_doc_and_fixtures():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--explain", "SPL008"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SPL008" in proc.stdout
    assert "donate" in proc.stdout          # the rule doc
    assert "known-bad fixture" in proc.stdout
    assert "known-good fixture" in proc.stdout
    assert "spl008_bad.py" in proc.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "tools.splint", "--explain", "SPL999"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert bad.returncode == 2
    assert "unknown rule" in bad.stderr


def test_full_tree_run_stays_fast():
    """The splint pass rides in tier-1 on every pytest run: a full-tree
    analysis (all rules, the dataflow passes, AND the v4 durability
    rules) plus one full crash-point enumeration must stay well under
    12 s or the gate starts costing more than it protects.  The
    crash-state count is bounded here too: the checker's cost is
    linear in enumerated states, so an accidental combinatorial
    blow-up (a new init x op product) fails this gate before it
    swamps CI."""
    from tools.splint.crashpoint import run_crash_check

    baseline = load_baseline(REPO / "tools" / "splint" / "baseline.json")
    t0 = time.perf_counter()
    run(_cfg(), baseline=baseline)
    crash = run_crash_check()
    elapsed = time.perf_counter() - t0
    assert crash.states <= 64, (
        f"crash-point enumeration grew to {crash.states} states — "
        f"bound it or move the new protocol to the slow tier")
    assert elapsed < 12.0, (
        f"full-tree splint + crash-point run took {elapsed:.1f}s")


def test_env_docs_render():
    from tools.splint.__main__ import _env_docs

    table = _env_docs(_cfg())
    assert "SPLATT_ENGINE_FALLBACK" in table
    assert "SPLATT_PROBE_CACHE_TTL_S" in table
    assert "| variable |" in table


def test_pyproject_table_parser():
    text = ('[tool.other]\nx = 1\n[tool.splint]\npaths = ["a",\n'
            '  "b"]\nbaseline = "bl.json"\n[tool.after]\ny = 2\n')
    table = _parse_table(text, "tool.splint")
    assert table == {"paths": ["a", "b"], "baseline": "bl.json"}


def test_config_matches_pyproject():
    cfg = load_config(REPO)
    assert cfg.paths == ["splatt_tpu"]
    assert cfg.resolve(cfg.baseline).exists()
    assert "_cache_io_error" in cfg.resilience_routers
    assert cfg.resilience_module == "splatt_tpu/resilience.py"
    assert cfg.trace_module == "splatt_tpu/trace.py"
    assert "SPL013" in cfg.zero_rules
    assert set(cfg.cache_path_functions) == {"_cache_path", "cache_path"}
    assert "_json_cache_update" in cfg.cache_io_helpers
    assert "_json_cache_load" in cfg.cache_io_helpers
    # the concurrency family (SPL014-SPL018) is zero-budget and its
    # three config keys are populated
    assert {"SPL014", "SPL015", "SPL016", "SPL017", "SPL018"} \
        <= set(cfg.zero_rules)
    assert any(e.startswith("splatt_tpu/serve.py::self._jobs=")
               for e in cfg.shared_state)
    assert any(e.startswith("splatt_tpu/tune.py::_MEM=")
               for e in cfg.shared_state)
    assert {"publish_bytes", "publish_json", "publish_file",
            "append_line"} <= set(cfg.durable_write_helpers)
    assert "splatt_tpu/serve.py::submit" in cfg.hot_lock_paths
    # the v5 numerics/tiling family (SPL024-SPL028) is zero-budget and
    # its config surface is populated
    assert {"SPL024", "SPL025", "SPL026", "SPL027", "SPL028"} \
        <= set(cfg.zero_rules)
    assert "splatt_tpu/ops/linalg.py" in cfg.numerics_modules
    assert "acc_dtype" in cfg.acc_dtype_helpers
    assert "splatt_tpu/cpd.py::_zz_inner" in cfg.hot_stream_functions
    assert any(e.startswith("splatt_tpu/cpd.py::_zz_inner::U_last=")
               for e in cfg.hot_stream_param_dtypes)
    assert "splatt_tpu/ops/pallas_kernels.py" in cfg.pallas_modules
    assert "tile_packing" in cfg.tile_pack_helpers
    assert int(cfg.vmem_budget_mib) > 0
    gate_map = dict(e.split("=") for e in cfg.vmem_gate_map)
    assert gate_map["fused_mttkrp_t"] == "fused_t_vmem_ok"
    assert "_tuned_plan_for" in cfg.plan_match_functions
    # SPL005 joined the zero-rules in the v5 burn-down
    assert "SPL005" in cfg.zero_rules


# -- the v5 guards: the numerics/tiling fixes are load-bearing --------------
#
# Each test re-introduces one production bug the v5 pass fixed (or a
# regression the rules exist to catch) into a tmp copy of the REAL
# package tree and asserts the matching rule fires.  The unmutated
# tree is clean (the tree gate above), so these prove the rules guard
# the real files, not just the fixtures.

def _copy_package_tree(tmp_path, rel, mutate):
    """A tmp copy of the full splatt_tpu package (+ the docs the
    registry rules read) with `mutate(src) -> src` applied to `rel`."""
    shutil.copytree(REPO / "splatt_tpu", tmp_path / "splatt_tpu")
    (tmp_path / "docs").mkdir()
    shutil.copy(REPO / "docs" / "observability.md", tmp_path / "docs")
    target = tmp_path / rel
    target.write_text(mutate(target.read_text()))
    cfg = _cfg()
    cfg.root = tmp_path
    cfg.paths = ["splatt_tpu"]
    return cfg


def test_spl024_fires_when_gram_pin_reverted(tmp_path):
    """Dropping gram's preferred_element_type pin — the exact shape
    the reference port had before the v5 fix — must trip SPL024: a
    bf16 factor would then accumulate its Gram matrix at bf16 and feed
    the error straight into the normal equations."""
    anchor = ("    return jnp.matmul(U.T, U, "
              "preferred_element_type=acc_dtype(U.dtype),\n"
              "                      precision=mxu_precision(U.dtype))")

    def mutate(src):
        assert anchor in src, "linalg.py gram anchor drifted"
        return src.replace(anchor, "    return jnp.matmul(U.T, U)")

    cfg = _copy_package_tree(tmp_path, "splatt_tpu/ops/linalg.py", mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL024" and f.path.endswith("linalg.py")]
    assert hits and any("matmul" in f.message for f in hits)


def test_spl025_fires_when_rank_pad_reverted(tmp_path):
    """Reverting a kernel's rank padding to the dtype-blind
    ``ceil_to(R, 8)`` (the pre-v5 shape: correct for f32, half the
    sublane tile for bf16) must trip SPL025 on the block position the
    padded value certifies."""
    anchor = ("    R8 = _rank_pad(R, dtype)\n"
              "    others = [k for k in range(layout.nmodes) "
              "if k != mode]\n"
              "    grid = (nb,)\n")

    def mutate(src):
        assert anchor in src, "pallas_kernels.py rank-pad anchor drifted"
        return src.replace(
            anchor,
            "    R8 = ceil_to(R, 8)\n"
            "    others = [k for k in range(layout.nmodes) "
            "if k != mode]\n"
            "    grid = (nb,)\n", 1)

    cfg = _copy_package_tree(
        tmp_path, "splatt_tpu/ops/pallas_kernels.py", mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL025"]
    assert hits and any("R8" in f.message for f in hits)


def test_spl026_fires_when_gate_consult_dropped(tmp_path):
    """Short-circuiting the fused_t dispatch gate — the kernel runs
    whether or not its block plan fits VMEM — must trip SPL026's
    registry leg: the declared gate is never consulted."""
    anchor = ('    if gather and live("fused_t") and '
              "fused_t_vmem_ok(factors, mode,")

    def mutate(src):
        assert anchor in src, "mttkrp.py fused_t gate anchor drifted"
        return src.replace(
            anchor,
            '    if gather and live("fused_t") and '
            "(lambda *a: True)(factors, mode,", 1)

    cfg = _copy_package_tree(tmp_path, "splatt_tpu/ops/mttkrp.py", mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL026"]
    assert hits and any("fused_t_vmem_ok" in f.message
                        and "consulted" in f.message for f in hits)


def test_spl027_fires_when_match_comparison_dropped(tmp_path):
    """Deleting one strict-match comparison from _tuned_plan_for (a
    plan measured for another nnz block would then steer this
    dispatch) must trip SPL027's dispatch leg."""
    anchor = "            or plan.nnz_block != layout.block\n"

    def mutate(src):
        assert anchor in src, "mttkrp.py plan-match anchor drifted"
        return src.replace(anchor, "", 1)

    cfg = _copy_package_tree(tmp_path, "splatt_tpu/ops/mttkrp.py", mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL027"]
    assert hits and any("nnz_block" in f.message for f in hits)


def test_spl028_fires_when_zz_inner_product_reverted(tmp_path):
    """Reverting _zz_inner's pinned einsum to the elementwise
    ``M * U_last`` product must trip SPL028 under the declared storage
    contract (M wide, U_last narrow): the product materializes a wide
    (dim, R) intermediate ahead of the reduce — the doubled hot-loop
    bytes the rule exists to catch."""
    anchor = ('    inner = jnp.einsum("dr,dr,r->", M, U_last, lam,\n'
              "                       preferred_element_type=acc)")

    def mutate(src):
        assert anchor in src, "cpd.py _zz_inner anchor drifted"
        return src.replace(
            anchor,
            "    inner = jnp.sum(M * U_last * lam[None, :], dtype=acc)")

    cfg = _copy_package_tree(tmp_path, "splatt_tpu/cpd.py", mutate)
    hits = [f for f in run(cfg, baseline={}).findings
            if f.rule == "SPL028" and f.path.endswith("cpd.py")]
    assert hits


def test_run_report_registry_matches_runtime():
    """The RUN_REPORT_EVENTS registry is importable and every kind the
    RunReport summary formatter special-cases is declared — the static
    SPL012 check and the runtime reporting read the same surface."""
    from splatt_tpu.resilience import RUN_REPORT_EVENTS

    assert set(RUN_REPORT_EVENTS) >= {
        "transient_retry", "engine_demotion", "checkpoint_recovery",
        "probe_downgrade", "probe_cache_io_error", "tune_cache_io_error",
        "tuned_plan", "tuner_negative", "tuner_degraded", "block_clamp"}
    for kind, doc in RUN_REPORT_EVENTS.items():
        assert isinstance(doc, str) and len(doc) > 10, kind
