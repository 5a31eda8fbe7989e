"""Guarded ALS: numerical-health sentinel, rollback, deadline watchdog.

The contract under test (ISSUE 5 / docs/guarded-als.md): non-finite
sweep outputs are DETECTED at the existing fit-fetch sync, rolled back
to the last-good snapshot (bump regularization / re-randomize the
offending factor), retried within SPLATT_HEALTH_RETRIES, and degraded
to checkpoint-and-abort when the budget is exhausted — in the
single-device AND distributed drivers; a blown host-side deadline
classifies TIMEOUT and demotes per-shape exactly like OOM; and the
chaos schedules that drive all of this are seeded, declarative, and
round-trip through their grammar.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu import resilience, tune
from splatt_tpu.config import Options, Verbosity
from splatt_tpu.cpd import cpd_als, load_checkpoint
from splatt_tpu.resilience import (DeadlineExceeded, FailureClass,
                                   NumericalHealthError, classify_failure)
from splatt_tpu.utils import faults
from tests import gen


@pytest.fixture(autouse=True)
def _clean_guard_state(monkeypatch):
    """Demotions, the run report, armed faults, the deadline override
    and the plan-cache override are process-global; every test starts
    clean and leaves nothing armed."""
    resilience.reset_demotions()
    resilience.run_report().clear()
    resilience.set_fallback(None)
    resilience.set_deadline(None)
    faults.reset()
    tune.set_cache_path(None)
    yield
    resilience.reset_demotions()
    resilience.run_report().clear()
    resilience.set_fallback(None)
    resilience.set_deadline(None)
    faults.reset()
    tune.set_cache_path(None)


def _opts(**kw):
    kw.setdefault("random_seed", 31)
    kw.setdefault("verbosity", Verbosity.NONE)
    return Options(**kw)


# -- schedule grammar -------------------------------------------------------

@pytest.mark.parametrize("spec_str", [
    "site_a:http500",
    "site_a:http500:3",
    "site_a:oom:*",
    "engine.unfused_pallas:nan:iter=3",
    "probe_compile:internal:p=0.25:seed=7",
    "tuner.measure:slow:delay=2.5",
    "site_b:runtime:after=1.5",
    "site_c:mosaic:iter=2:p=0.5:seed=9:after=0.25:4",
])
def test_schedule_spec_round_trip(spec_str):
    """parse(format(parse(s))) preserves every schedule field."""
    site, spec = faults.parse_spec(spec_str)
    site2, spec2 = faults.parse_spec(faults.format_spec(site, spec))
    assert site2 == site
    for f in ("kind", "times", "iter_at", "p", "seed", "after", "delay"):
        assert getattr(spec2, f) == getattr(spec, f), f


def test_schedule_round_trip_and_kind_default():
    sched = faults.parse_schedule(
        "a:http500:2, engine.x:iter=3, b:slow:delay=0.5:*")
    # omitted kind defaults to runtime (the issue's `site:iter=k` form)
    assert sched["engine.x"].kind == "runtime"
    assert sched["engine.x"].iter_at == 3
    assert sched["b"].times == faults.ALWAYS
    back = faults.parse_schedule(faults.format_schedule(sched))
    assert back.keys() == sched.keys()
    for site in sched:
        assert back[site].kind == sched[site].kind
        assert back[site].times == sched[site].times


@pytest.mark.parametrize("bad", [
    "nosite",                      # no kind/modifier at all
    "s:unknownkind",               # unknown kind
    "s:runtime:iter=0",            # iter is 1-based
    "s:runtime:p=1.5",             # p out of range
    "s:runtime:frobnicate=1",      # unknown modifier
    "s:runtime:two",               # unparseable modifier
])
def test_schedule_malformed_specs_raise(bad):
    with pytest.raises((ValueError, TypeError)):
        faults.parse_spec(bad)


def test_schedule_env_malformed_entries_ignored(monkeypatch, capsys):
    """The env loader keeps its warn-and-ignore contract for the new
    grammar: a typo must not kill the run at a random hook site."""
    monkeypatch.setenv("SPLATT_FAULTS",
                       "s:runtime:iter=zero,ok:mosaic:iter=1")
    faults.reset()
    faults.maybe_fail("s")                       # malformed: ignored
    with pytest.raises(RuntimeError, match="Mosaic"):
        faults.maybe_fail("ok")                  # valid entry armed
    assert "iter=zero" in capsys.readouterr().err


def test_schedule_iter_fires_on_exact_call():
    fired_at = []
    with faults.inject("it_site", "runtime", iter_at=3):
        for call in range(1, 6):
            try:
                faults.maybe_fail("it_site")
            except RuntimeError:
                fired_at.append(call)
    assert fired_at == [3]


def test_schedule_p_seed_deterministic():
    def pattern():
        hits = []
        with faults.inject("p_site", "runtime", times=faults.ALWAYS,
                           p=0.3, seed=42):
            for call in range(30):
                try:
                    faults.maybe_fail("p_site")
                except RuntimeError:
                    hits.append(call)
        return hits

    a, b = pattern(), pattern()
    assert a == b                      # seeded: replayable
    assert 0 < len(a) < 30             # actually probabilistic


def test_schedule_after_gate():
    with faults.inject("af_site", "runtime", after=0.15):
        faults.maybe_fail("af_site")   # too early: no-op
        time.sleep(0.2)
        with pytest.raises(RuntimeError, match="injected"):
            faults.maybe_fail("af_site")


def test_poison_and_kind_filtering():
    """maybe_fail must not claim (and waste) a poison-armed spec at the
    same site, and poison must not claim a raising spec."""
    with faults.inject("mix", "nan", times=1):
        faults.maybe_fail("mix")                 # not claimed
        assert np.isnan(faults.poison("mix", 2.0))
        assert faults.poison("mix", 2.0) == 2.0  # exhausted
    with faults.inject("mix", "http500", times=1):
        assert faults.poison("mix", 2.0) == 2.0  # not claimed
        with pytest.raises(RuntimeError, match="HTTP code 500"):
            faults.maybe_fail("mix")
    # inf variant poisons too
    with faults.inject("mix", "inf", times=1):
        assert np.isinf(faults.poison("mix", 2.0))


def test_slow_kind_sleeps_not_raises():
    t0 = time.monotonic()
    with faults.inject("sl", "slow", delay=0.3):
        faults.maybe_fail("sl")        # sleeps, returns
        assert faults.fired("sl") == 1
    assert time.monotonic() - t0 >= 0.25


# -- taxonomy: NUMERICAL / TIMEOUT ------------------------------------------

def test_classify_new_classes_and_precedence():
    assert classify_failure(DeadlineExceeded(
        "splatt deadline blown at x after 1s")) is FailureClass.TIMEOUT
    # the watchdog marker outranks the transient 'timed out' markers a
    # blown-deadline message might echo
    assert classify_failure(
        "splatt deadline blown at probe after 240s "
        "(timed out)") is FailureClass.TIMEOUT
    assert classify_failure(NumericalHealthError(
        "non-finite sweep outputs")) is FailureClass.NUMERICAL
    assert classify_failure(
        "non-finite factors at iteration 3") is FailureClass.NUMERICAL
    # RPC-level deadline strings stay transient
    assert classify_failure(
        "DEADLINE_EXCEEDED: compile RPC") is FailureClass.TRANSIENT


def test_timeout_demotes_per_shape_like_oom():
    resilience.demote_engine(
        "unfused_pallas", DeadlineExceeded("splatt deadline blown at "
                                           "engine.unfused_pallas after 2s"),
        shape_key="ck1:b4096")
    assert resilience.is_demoted("unfused_pallas", "ck1:b4096")
    assert not resilience.is_demoted("unfused_pallas", "ck1:b128")
    assert not resilience.is_demoted("unfused_pallas")


def test_numerical_error_never_triggers_engine_rescue():
    """A NaN is the sentinel's to roll back — it must not demote the
    engine that computed it."""
    from splatt_tpu.cpd import _try_engine_rescue
    from tests.test_resilience import _blocked

    _, bs = _blocked()
    resilience.note_engine_attempt("unfused_pallas", "ck1:b256")
    assert _try_engine_rescue(
        bs, _opts(), NumericalHealthError("non-finite outputs")) is False
    assert not resilience.is_demoted("unfused_pallas")


# -- deadline watchdog ------------------------------------------------------

def test_deadline_blows_and_reports():
    with pytest.raises(DeadlineExceeded, match="deadline blown at d1"):
        with resilience.deadline("d1", 0.2):
            time.sleep(0.8)
    ev = resilience.run_report().events("deadline_blown")
    assert len(ev) == 1 and ev[0]["site"] == "d1"


def test_deadline_disabled_is_noop():
    with resilience.deadline("d2", 0):
        time.sleep(0.05)
    with resilience.deadline("d3", None):
        pass
    # default env (SPLATT_DEADLINE_S=0) disables too
    with resilience.deadline("d4"):
        pass
    assert not resilience.run_report().events("deadline_blown")


def test_deadline_override_and_generous_budget():
    resilience.set_deadline(5.0)
    assert resilience.deadline_seconds() == 5.0
    with resilience.deadline("d5"):
        time.sleep(0.02)               # well under budget: no raise
    resilience.set_deadline(None)
    assert resilience.deadline_seconds(default=240.0) == 240.0


def test_deadline_explicit_disable_beats_env(monkeypatch):
    """set_deadline(0) disables the optional sites even with
    SPLATT_DEADLINE_S exported — but a site's own default (the probe's
    always-on 240 s) survives the disable."""
    monkeypatch.setenv("SPLATT_DEADLINE_S", "300")
    assert resilience.deadline_seconds() == 300.0
    resilience.set_deadline(0)
    assert resilience.deadline_seconds() is None
    assert resilience.deadline_seconds(default=240.0) == 240.0
    with resilience.deadline("d7"):    # disabled: no timer, no raise
        time.sleep(0.02)


def test_deadline_off_main_thread_raises_post_hoc():
    """Off the main thread there is no interrupt; the blown deadline
    still converts 'slow' into a classified error on completion."""
    result = {}

    def work():
        try:
            with resilience.deadline("d6", 0.1):
                time.sleep(0.3)
            result["ok"] = True
        except DeadlineExceeded as e:
            result["err"] = e

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=5)
    assert isinstance(result.get("err"), DeadlineExceeded)


def test_deadline_fault_injectable_via_slow():
    """The watchdog is fault-injectable: a `slow` fault at a guarded
    site makes the REAL timer fire."""
    with faults.inject("slow_site", "slow", delay=0.5):
        with pytest.raises(DeadlineExceeded):
            with resilience.deadline("slow_site", 0.15):
                faults.maybe_fail("slow_site")


def test_tuner_deadline_skips_but_never_persists(tmp_path):
    """A tuner measurement that blows the deadline is skipped this
    session (tuner_negative, failure_class=timeout) but NOT persisted
    as a negative plan-cache entry — a re-tune measures it again."""
    tt = gen.fixture_tensor("med")
    tune.set_cache_path(str(tmp_path / "tc.json"))
    resilience.set_deadline(0.2)
    # pinned format + packing + reorder: exactly ONE candidate (the
    # sorted_scatter chain is ["xla"]), so the single blown measurement
    # leaves the mode planless
    opts = _opts(use_pallas=False, idx_width="i32", val_storage="auto",
                 fiber_packing="fixed", reorder="identity")
    with faults.inject("tuner.measure", "slow", delay=0.7, times=1):
        res = tune.tune(tt, rank=3, opts=opts, modes=[0],
                        blocks=(256,), reps=1)
    assert res.plans == {} and res.skipped == 1
    negs = resilience.run_report().events("tuner_negative")
    assert len(negs) == 1 and negs[0]["failure_class"] == "timeout"
    blown = resilience.run_report().events("deadline_blown")
    assert blown and blown[0]["site"] == "tuner.measure"
    text = (tmp_path / "tc.json").read_text() \
        if (tmp_path / "tc.json").exists() else "{}"
    assert "neg:" not in text          # never persisted
    # the fault is exhausted: a re-tune measures the candidate fine
    tune.reset_memo()
    res2 = tune.tune(tt, rank=3, opts=opts, modes=[0], blocks=(256,),
                     reps=1)
    assert 0 in res2.plans


# -- numerical-health sentinel + rollback -----------------------------------

@pytest.mark.parametrize("k", [1, 3])
def test_nan_at_iteration_k_rolls_back_to_finite(k):
    """Property (acceptance): an injected NaN at iteration k triggers
    rollback and yields finite final factors with fit within tolerance
    of the fault-free run."""
    tt = gen.fixture_tensor("med")
    opts = _opts(max_iterations=8)
    base = cpd_als(tt, rank=3, opts=opts)
    resilience.run_report().clear()
    with faults.inject("cpd.sweep", "nan", iter_at=k):
        out = cpd_als(tt, rank=3, opts=opts)
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)
    assert np.isfinite(float(out.fit))
    # the rollback re-randomizes the offending factor, so the retry
    # converges from a different start: same-ballpark fit, not bitwise
    assert abs(float(out.fit) - float(base.fit)) < 0.01
    last = tt.nmodes - 1
    report = resilience.run_report()
    nf = report.events("health_nonfinite")
    assert nf and nf[0]["iteration"] == k and nf[0]["modes"] == [last]
    rb = report.events("health_rollback")
    assert rb and rb[0]["rerandomized"] == [last]
    assert not report.events("health_degraded")


def test_engine_site_nan_rolls_back_through_sweep_rebuild():
    """An `engine.<name>:nan` schedule: a poison-armed engine fault
    corrupts the engine's output inside the fused sweep's TRACE;
    the rollback's sweep rebuild flushes the poisoned program and the
    run recovers."""
    from splatt_tpu.ops.mttkrp import engine_plan
    from tests.test_resilience import _blocked

    _, bs = _blocked()
    facs = [jnp.zeros((d, 3), jnp.float32) for d in bs.dims]
    lay = bs.layouts[0]
    head = engine_plan(lay, facs, lay.mode, "sorted_onehot",
                       "pallas_interpret")
    opts = _opts(max_iterations=6, use_pallas=True)
    base = cpd_als(bs, rank=3, opts=opts)
    resilience.run_report().clear()
    with faults.inject(f"engine.{head}", "nan", iter_at=1):
        out = cpd_als(bs, rank=3, opts=opts)
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)
    # the rollback re-randomizes the offending factor(s), so the retry
    # converges from a different start: same-ballpark fit, not bitwise
    assert abs(float(out.fit) - float(base.fit)) < 0.05
    assert resilience.run_report().events("health_rollback")
    # the engine was NOT demoted: NaN is not a capability statement
    assert not resilience.is_demoted(head)


def test_health_budget_exhaustion_degrades_with_checkpoint(tmp_path):
    """Every retry poisoned: the run degrades to checkpoint-and-abort —
    finite last-good factors, a health_degraded event, a loadable
    checkpoint — instead of diverging or raising."""
    tt = gen.fixture_tensor("med")
    ck = str(tmp_path / "ck.npz")
    with faults.inject("cpd.sweep", "nan", times=faults.ALWAYS):
        out = cpd_als(tt, rank=3, opts=_opts(max_iterations=6),
                      checkpoint_path=ck, checkpoint_every=100)
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)
    report = resilience.run_report()
    assert report.events("health_degraded")
    # budget respected: retries == SPLATT_HEALTH_RETRIES default (3)
    assert len(report.events("health_rollback")) == 3
    factors, lam, it, fit = load_checkpoint(ck)
    assert all(np.isfinite(np.asarray(U)).all() for U in factors)
    # the checkpoint records the last HEALTHY check's iteration (the
    # snapshot's provenance — here the pre-loop init), so a resume
    # redoes the rolled-back window instead of skipping it
    assert it == 0


def test_health_guard_disabled_by_env(monkeypatch):
    """SPLATT_HEALTH_RETRIES=0 disables the sentinel: the NaN flows
    through (legacy behavior) rather than being rolled back."""
    monkeypatch.setenv("SPLATT_HEALTH_RETRIES", "0")
    tt = gen.fixture_tensor("med")
    with faults.inject("cpd.sweep", "nan", iter_at=1):
        out = cpd_als(tt, rank=3, opts=_opts(max_iterations=3))
    assert not resilience.run_report().events("health_nonfinite")
    assert not all(np.isfinite(np.asarray(U)).all()
                   for U in out.factors)


def test_rollback_with_donated_sweep_preserves_callers_init():
    """Donated-sweep + rollback interaction: the donated fused sweep
    consumes its inputs, the rollback re-materializes from the host
    snapshot, and the CALLER's init arrays survive untouched."""
    from tests.test_resilience import _blocked

    _, bs = _blocked()
    rng = np.random.default_rng(5)
    init = [jnp.asarray(rng.random((d, 3)), dtype=jnp.float32)
            for d in bs.dims]
    init_copy = [np.asarray(u).copy() for u in init]
    opts = _opts(max_iterations=6, use_pallas=True, donate_sweep=True)
    with faults.inject("cpd.sweep", "nan", iter_at=2):
        out = cpd_als(bs, rank=3, opts=opts, init=init)
    assert resilience.run_report().events("health_rollback")
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)
    for u, want in zip(init, init_copy):
        np.testing.assert_array_equal(np.asarray(u), want)


def test_rollback_bumps_regularization_each_attempt():
    tt = gen.fixture_tensor("med")
    with faults.inject("cpd.sweep", "nan", times=2):
        cpd_als(tt, rank=3, opts=_opts(max_iterations=8))
    regs = [e["regularization"] for e in
            resilience.run_report().events("health_rollback")]
    assert len(regs) == 2 and regs[1] > regs[0] > 0


# -- distributed rollback ---------------------------------------------------

def test_distributed_nan_rolls_back_to_finite():
    from splatt_tpu.parallel.sharded import sharded_cpd_als

    tt = gen.fixture_tensor("med")
    opts = _opts(random_seed=42, val_dtype=np.float64, max_iterations=6)
    base = sharded_cpd_als(tt, rank=4, opts=opts)
    resilience.run_report().clear()
    with faults.inject("cpd.sweep", "nan", iter_at=2):
        out = sharded_cpd_als(tt, rank=4, opts=opts)
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)
    report = resilience.run_report()
    assert report.events("health_nonfinite")
    rb = report.events("health_rollback")
    assert rb and rb[0]["rerandomized"] == [tt.nmodes - 1]
    # distributed rollback re-randomizes without a reg bump (the step
    # closure owns reg; docs/MULTIHOST.md)
    assert rb[0]["regularization"] is None
    assert abs(float(out.fit) - float(base.fit)) < 0.05


def test_distributed_budget_exhaustion_degrades():
    from splatt_tpu.parallel.sharded import sharded_cpd_als

    tt = gen.fixture_tensor("med")
    opts = _opts(random_seed=42, val_dtype=np.float64, max_iterations=5)
    with faults.inject("cpd.sweep", "nan", times=faults.ALWAYS):
        out = sharded_cpd_als(tt, rank=3, opts=opts)
    assert resilience.run_report().events("health_degraded")
    assert all(np.isfinite(np.asarray(U)).all() for U in out.factors)


# -- registries -------------------------------------------------------------

def test_new_events_and_sites_registered():
    for kind in ("health_nonfinite", "health_rollback",
                 "health_degraded", "deadline_blown",
                 "bench_path_error"):
        assert kind in resilience.RUN_REPORT_EVENTS, kind
    assert "cpd.sweep" in faults.SITES
    from splatt_tpu.utils.env import ENV_VARS

    for var in ("SPLATT_HEALTH_RETRIES", "SPLATT_DEADLINE_S",
                "SPLATT_CHAOS_SCHEDULE"):
        assert var in ENV_VARS, var


def test_record_path_error_classifies():
    ev = resilience.record_path_error(
        "blocked", RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert ev["failure_class"] == "resource" and ev["path"] == "blocked"
    assert resilience.run_report().events("bench_path_error")
    assert any("bench path blocked" in line
               for line in resilience.run_report().summary())
