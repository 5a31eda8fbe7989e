"""`splatt serve` — the isolated, crash-resumable multi-tenant daemon.

The contracts under test (docs/serve.md):

- durability-first accept: a job is journaled before the submitter
  hears "accepted"; journal failure rejects instead of silently
  forgetting; re-submission is idempotent;
- bounded queue with explicit `queue_full` load shedding;
- journal replay: a fresh Server over a crashed daemon's root
  re-enqueues every accepted-but-non-terminal job (torn final lines
  skipped) and the jobs resume from their checkpoints;
- THE ISOLATION INVARIANT: two concurrent jobs — one driven to a
  NUMERICAL rollback, one to an OOM engine demotion via per-job fault
  schedules — finish with each other's demotion tables and health
  verdicts untouched, and a later same-regime job hits the warm shared
  plan cache with zero measurements;
- graceful drain: SIGTERM interrupts running jobs at a fit check,
  checkpoints them, and the next start resumes them;
- the serve fault sites (serve.submit / serve.journal_write /
  serve.job_run) degrade, classified, never killing the daemon.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from splatt_tpu import resilience, serve, tune
from splatt_tpu.utils import faults

SYN = {"dims": [20, 16, 12], "nnz": 1200, "seed": 0}


def _spec(jid, **kw):
    spec = {"id": jid, "rank": 3, "iters": 6, "seed": 0,
            "synthetic": dict(SYN)}
    spec.update(kw)
    return spec


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    def clean():
        faults.reset()
        resilience.reset_demotions()
        resilience.run_report().clear()
        # the global scope's async attempt note (other modules' dispatch
        # tests leave one behind)
        resilience._state().last_attempt = None

    clean()
    yield
    clean()


@pytest.fixture()
def private_caches(tmp_path, monkeypatch):
    """Throwaway probe/plan caches so tuning jobs cannot dirty (or be
    steered by) the repo's real shared caches."""
    monkeypatch.setenv("SPLATT_TUNE_CACHE", str(tmp_path / "tc.json"))
    monkeypatch.setenv("SPLATT_PROBE_CACHE", str(tmp_path / "pc.json"))
    tune.reset_memo()
    yield
    tune.reset_memo()


def _journal_kinds(root, jid):
    recs, _ = serve.Journal(os.path.join(root, "journal.jsonl")).replay()
    return [r["rec"] for r in recs if r.get("job") == jid]


# -- queue / API basics ------------------------------------------------------

def test_submit_run_result_and_lineage(tmp_path):
    srv = serve.Server(str(tmp_path), workers=1)
    r = srv.submit(_spec("j1"))
    assert r["state"] == serve.ACCEPTED
    assert resilience.run_report().events("job_accepted")
    summary = srv.run_once()
    assert summary["counts"] == {serve.DONE: 1}
    res = srv.result("j1")
    assert res["status"] == "converged" and res["fit"] > 0
    assert res["demotions"] == [] and res["resumed"] is False
    assert _journal_kinds(str(tmp_path), "j1") == [
        serve.ACCEPTED, serve.STARTED, serve.DONE]
    assert srv.status("j1")["status"] == "converged"


def test_filed_request_roundtrip(tmp_path):
    root = str(tmp_path)
    jid = serve.file_request(root, _spec("filed1"))
    assert jid == "filed1"
    assert serve.read_status(root, jid)["state"] == "filed"
    srv = serve.Server(root, workers=1)
    srv.run_once()
    # spool file consumed, result published, status journal-derived
    assert not os.path.exists(
        os.path.join(root, "requests", "filed1.json"))
    st = serve.read_status(root, jid)
    assert st["state"] == serve.DONE and st["status"] == "converged"
    assert st["result"]["fit"] > 0
    assert serve.read_result(root, jid)["job"] == jid


def test_duplicate_submission_is_idempotent(tmp_path):
    srv = serve.Server(str(tmp_path), workers=1)
    srv.submit(_spec("dup"))
    again = srv.submit(_spec("dup"))
    assert again["duplicate"] is True
    srv.run_once()
    # a crashed client retrying after completion: still deduped
    after = srv.submit(_spec("dup"))
    assert after["duplicate"] is True and after["state"] == serve.DONE
    assert _journal_kinds(str(tmp_path), "dup").count(serve.ACCEPTED) == 1


def test_invalid_spec_rejected(tmp_path):
    srv = serve.Server(str(tmp_path))
    r = srv.submit({"id": "bad", "rank": 3})  # no workload
    assert r["state"] == serve.REJECTED and "invalid" in r["reason"]
    assert serve.read_result(str(tmp_path), "bad")["status"] == "rejected"
    with pytest.raises(ValueError):
        srv.submit({"id": "../escape", "synthetic": SYN})


def test_queue_full_load_shedding(tmp_path):
    srv = serve.Server(str(tmp_path), workers=1, queue_max=1)
    assert srv.submit(_spec("q1"))["state"] == serve.ACCEPTED
    r2 = srv.submit(_spec("q2"))
    assert r2["state"] == serve.REJECTED and r2["reason"] == "queue_full"
    evs = resilience.run_report().events("queue_full")
    assert len(evs) == 1 and evs[0]["job"] == "q2"
    # the rejection is a published, machine-readable verdict
    res = serve.read_result(str(tmp_path), "q2")
    assert res["status"] == "rejected" and res["reason"] == "queue_full"
    assert serve.REJECTED in _journal_kinds(str(tmp_path), "q2")
    # the accepted job still runs to done; the queue frees up again
    srv.run_once()
    assert srv.status("q1")["status"] == "converged"
    assert srv.submit(_spec("q3"))["state"] == serve.ACCEPTED


def test_malformed_request_quarantined(tmp_path):
    root = str(tmp_path)
    srv = serve.Server(root, workers=1)
    bad = os.path.join(root, "requests", "broken.json")
    with open(bad, "w") as f:
        f.write("{not json")
    srv.scan_requests()
    assert not os.path.exists(bad)
    assert os.path.exists(bad + ".bad")
    # the scanner does not spin on the quarantined file
    assert srv.scan_requests() == 0


# -- crash-resume ------------------------------------------------------------

def test_replay_resumes_accepted_jobs(tmp_path):
    """CRASH-RESUME INVARIANT (in-process half; the SIGKILL half lives
    in test_chaos.py's serve soak): accepted-but-non-terminal jobs are
    re-enqueued on restart, reach terminal states, and their journal
    lineage is intact."""
    root = str(tmp_path)
    s1 = serve.Server(root, workers=1)
    s1.submit(_spec("r1"))
    s1.submit(_spec("r2", synthetic=dict(SYN, seed=1)))
    del s1  # "crash": accepted, never run
    s2 = serve.Server(root, workers=1)
    resumed = {e["job"] for e in
               resilience.run_report().events("job_resumed")}
    assert {"r1", "r2"} <= resumed
    assert s2.status("r1")["resumed"] is True
    summary = s2.run_once()
    assert summary["counts"] == {serve.DONE: 2}
    for jid in ("r1", "r2"):
        res = serve.read_result(root, jid)
        assert res["status"] == "converged" and res["resumed"] is True
        assert _journal_kinds(root, jid) == [
            serve.ACCEPTED, serve.RESUMED, serve.STARTED, serve.DONE]


def test_torn_journal_line_is_skipped(tmp_path):
    """A SIGKILL can tear the final journal line; replay must skip it
    and keep every complete record."""
    root = str(tmp_path)
    s1 = serve.Server(root, workers=1)
    s1.submit(_spec("t1"))
    s1.run_once()
    with open(os.path.join(root, "journal.jsonl"), "a") as f:
        f.write('{"rec": "acce')  # torn mid-append
    s2 = serve.Server(root)
    assert s2.status("t1")["status"] == "converged"
    recs, torn = s2.journal.replay()
    assert torn == 1 and len(recs) == 3


def test_unknown_journal_kind_is_skipped_and_classified(tmp_path):
    """FORWARD-COMPAT INVARIANT: a record kind this version does not
    know (a newer writer's journal, or corruption that still parses)
    is SKIPPED with a classified ``journal_unknown_kind`` event — it
    must neither wedge replay nor invent job-table state."""
    root = str(tmp_path)
    s1 = serve.Server(root, workers=1)
    s1.submit(_spec("u1"))
    s1.run_once()
    with open(os.path.join(root, "journal.jsonl"), "a") as f:
        f.write('{"rec": "paused_v99", "job": "u1", "ts": 1.0}\n')
        f.write('{"rec": "paused_v99", "job": "u2", "ts": 2.0}\n')
    s2 = serve.Server(root, workers=1)
    # the known lineage replays untouched; the unknown kinds are
    # dropped on the floor rather than mutating (or creating) jobs
    assert s2.status("u1")["status"] == "converged"
    # no job-table entry was invented for u2 (absent jobs report a
    # bare state-None shell with no status field)
    assert s2.status("u2") == {"job": "u2", "state": None}
    evs = resilience.run_report().events("journal_unknown_kind")
    assert {e["job"] for e in evs} == {"u1", "u2"}
    assert all(e["record_kind"] == "paused_v99" for e in evs)
    # the declared vocabulary is what replay checks against
    assert "paused_v99" not in serve.KNOWN_KINDS
    assert serve.DONE in serve.KNOWN_KINDS
    # and the scheduler is not wedged: u1 stays terminal, started once
    assert s2.run_once()["counts"] == {serve.DONE: 1}
    assert _journal_kinds(root, "u1").count(serve.STARTED) == 1


def test_terminal_jobs_are_not_rerun(tmp_path):
    root = str(tmp_path)
    s1 = serve.Server(root, workers=1)
    s1.submit(_spec("fin"))
    s1.run_once()
    s2 = serve.Server(root, workers=1)
    assert s2.summary()["pending"] == 0
    assert s2.run_once()["counts"] == {serve.DONE: 1}
    # started exactly once: the journal shows a single start
    assert _journal_kinds(root, "fin").count(serve.STARTED) == 1


# -- THE isolation invariant -------------------------------------------------

def test_isolation_two_concurrent_jobs_and_warm_cache(tmp_path,
                                                      private_caches):
    """ISOLATION INVARIANT (acceptance): two concurrent jobs — one
    driven to a NUMERICAL rollback by a per-job NaN schedule, one to an
    OOM engine demotion — finish with the *other* job's demotion table
    and health verdicts untouched (and the global scope clean), while a
    later same-regime job records a warm plan-cache hit with zero
    measurements."""
    srv = serve.Server(str(tmp_path), workers=2, queue_max=8)
    nan_job = _spec("nanjob", iters=8, tune=True, health_retries=2,
                    faults="cpd.sweep:nan:iter=2")
    # interpret-mode pallas gives a real multi-engine chain on CPU;
    # every non-terminal engine is OOM-armed once, so whichever heads
    # the chain demotes per-shape (RESOURCE) and dispatch degrades
    oom_job = _spec("oomjob", iters=8, use_pallas=True, autotune=False,
                    synthetic=dict(SYN, seed=1),
                    faults="engine.unfused_pallas:oom:1,"
                           "engine.xla_scan:oom:1")
    srv.submit(nan_job)
    srv.submit(oom_job)
    summary = srv.run_once()
    assert summary["counts"] == {serve.DONE: 2}, summary

    ra = serve.read_result(str(tmp_path), "nanjob")
    rb = serve.read_result(str(tmp_path), "oomjob")
    kinds_a = {e["kind"] for e in ra["events"]}
    kinds_b = {e["kind"] for e in rb["events"]}

    # job A: rolled back, converged, demoted NOTHING (NUMERICAL is the
    # sentinel's, never the demotion registry's)
    assert ra["status"] == "converged"
    assert {"health_nonfinite", "health_rollback"} <= kinds_a
    assert ra["demotions"] == []
    assert ra["faults_fired"] == {"cpd.sweep": 1}
    # every event in A's report is attributed to A
    assert all(e.get("job", "nanjob") == "nanjob" for e in ra["events"])

    # job B: OOM-demoted per-shape, degraded to the next engine,
    # converged — and saw NONE of A's health trouble
    assert rb["status"] == "converged"
    assert "engine_demotion" in kinds_b
    assert rb["demotions"], "the OOM never demoted an engine"
    assert all(d["failure_class"] == "resource" and d["shape_key"]
               for d in rb["demotions"])
    assert not (kinds_b & {"health_nonfinite", "health_rollback",
                           "health_degraded"})

    # the global scope is untouched by either tenant
    assert resilience.demotions() == []
    for d in rb["demotions"]:
        assert not resilience.is_demoted(d["engine"], d["shape_key"])
    global_kinds = {e["kind"] for e in resilience.run_report().events()}
    assert not (global_kinds & {"engine_demotion", "health_nonfinite",
                                "health_rollback"})

    # the second same-regime tuning job (same shape regime as the NaN
    # tenant's — regimes bucket by power-of-two dims/nnz): warm shared
    # plan cache — zero measurements, one cache hit per mode
    warm = _spec("warmjob", tune=True, synthetic=dict(SYN))
    srv.submit(warm)
    srv.run_once()
    rc = serve.read_result(str(tmp_path), "warmjob")
    assert rc["status"] == "converged"
    assert rc["tune"]["measured"] == 0
    assert rc["tune"]["cache_hits"] == len(SYN["dims"])


# -- graceful drain ----------------------------------------------------------

def test_drain_checkpoints_running_job_and_restart_resumes(tmp_path):
    """SIGTERM semantics: a running job is interrupted at a fit check
    through the cpd stop hook, checkpoints, is journaled
    `interrupted`, and the next start resumes it to convergence."""
    root = str(tmp_path)
    s1 = serve.Server(root, workers=1)
    # the slow fault pins the job open at start so the drain
    # deterministically lands while it runs
    s1.submit(_spec("d1", iters=50, tol=0.0, checkpoint_every=100,
                    synthetic=dict(SYN, nnz=3000),
                    faults="serve.job_run:slow:delay=1.5"))
    t = threading.Thread(target=s1.run_once)
    t.start()
    time.sleep(0.6)  # inside the slow-fault window
    s1.drain()
    t.join(timeout=180)
    assert not t.is_alive()
    assert s1.status("d1")["state"] == serve.INTERRUPTED
    ck = os.path.join(root, "ckpt", "d1.npz")
    assert os.path.exists(ck)
    from splatt_tpu.cpd import load_checkpoint

    _, _, it, _ = load_checkpoint(ck)
    assert 1 <= it < 50  # checkpointed mid-run by the stop hook

    s2 = serve.Server(root, workers=1)
    assert s2.status("d1")["resumed"] is True
    assert s2.run_once()["counts"] == {serve.DONE: 1}
    res = serve.read_result(root, "d1")
    assert res["resumed"] is True and res["status"] in ("converged",)
    assert _journal_kinds(root, "d1") == [
        serve.ACCEPTED, serve.STARTED, serve.INTERRUPTED,
        serve.RESUMED, serve.STARTED, serve.DONE]


def test_drain_leaves_queued_jobs_journaled(tmp_path):
    root = str(tmp_path)
    srv = serve.Server(root, workers=1)
    srv.submit(_spec("never-ran"))
    srv.drain()
    assert srv.run_once()["counts"] == {serve.ACCEPTED: 1}
    # the restart picks it up
    s2 = serve.Server(root, workers=1)
    assert s2.run_once()["counts"] == {serve.DONE: 1}


# -- serve fault sites (SPL006) ----------------------------------------------

def test_submit_fault_quarantines_filed_request(tmp_path):
    """serve.submit: a raised fault rejects THAT submission (the spool
    scanner quarantines the request, classified) — the daemon lives."""
    root = str(tmp_path)
    srv = serve.Server(root, workers=1)
    serve.file_request(root, _spec("sf1"))
    with faults.inject("serve.submit", "runtime", times=1):
        assert srv.scan_requests() == 0
    assert os.path.exists(
        os.path.join(root, "requests", "sf1.json.bad"))
    # the daemon keeps serving
    srv.submit(_spec("sf2"))
    srv.run_once()
    assert srv.status("sf2")["status"] == "converged"


def test_journal_fault_rejects_submission_durability_first(tmp_path):
    """serve.journal_write: a submission the journal cannot record is
    REJECTED — a crash would silently forget it otherwise."""
    srv = serve.Server(str(tmp_path), workers=1)
    with faults.inject("serve.journal_write", "runtime", times=1):
        r = srv.submit(_spec("jf1"))
    assert r["state"] == serve.REJECTED
    assert "journal_error" in r["reason"]
    assert "unknown" in r["reason"]  # classified
    # nothing queued, nothing journaled as accepted
    assert srv.summary()["pending"] == 0
    assert serve.ACCEPTED not in _journal_kinds(str(tmp_path), "jf1")
    # the next submission (journal healthy again) is accepted
    assert srv.submit(_spec("jf2"))["state"] == serve.ACCEPTED


def test_job_run_fault_fails_job_classified(tmp_path):
    """serve.job_run: a raising fault marks the job failed with the
    failure class, a job_degraded event, and a nonzero --once-style
    verdict — never a dead worker."""
    srv = serve.Server(str(tmp_path), workers=1)
    srv.submit(_spec("jr1"))
    with faults.inject("serve.job_run", "oom", times=1):
        summary = srv.run_once()
    assert summary["counts"] == {serve.FAILED: 1}
    res = serve.read_result(str(tmp_path), "jr1")
    assert res["status"] == "failed"
    assert res["failure_class"] == "resource"
    kinds = {e["kind"] for e in res["events"]}
    assert "job_degraded" in kinds
    assert serve.FAILED in _journal_kinds(str(tmp_path), "jr1")
    # the failure stayed in the job's scope
    assert not resilience.run_report().events("job_degraded")


def test_job_deadline_blows_classified_timeout(tmp_path):
    """A per-job deadline (spec deadline_s + the PR 5 watchdog) bounds
    a wedged job: the slow fault holds the job past its budget and the
    job finishes failed/TIMEOUT, not hung."""
    srv = serve.Server(str(tmp_path), workers=1)
    srv.submit(_spec("dl1", deadline_s=0.2,
                     faults="serve.job_run:slow:delay=0.8"))
    summary = srv.run_once()
    assert summary["counts"] == {serve.FAILED: 1}
    res = serve.read_result(str(tmp_path), "dl1")
    assert res["failure_class"] == "timeout"
    assert any(e["kind"] == "deadline_blown" for e in res["events"])


# -- per-job resilience scope (unit) -----------------------------------------

def test_scope_isolates_demotions_and_attributes_events():
    resilience.demote_engine("outer", RuntimeError("Mosaic dead"))
    with resilience.scope("tenant1") as sc:
        assert not resilience.is_demoted("outer")
        assert resilience.demotions() == []
        resilience.demote_engine(
            "inner", RuntimeError("RESOURCE_EXHAUSTED: x"),
            shape_key="s1")
        assert resilience.is_demoted("inner", "s1")
        ev = resilience.run_report().add("transient_retry", label="x",
                                         attempt=1, delay_s=0, error="e")
        assert ev["job"] == "tenant1"
        assert resilience.current_job() == "tenant1"
    assert not resilience.is_demoted("inner", "s1")
    assert resilience.is_demoted("outer")
    assert resilience.current_job() is None
    # the scope object keeps its evidence after exit (serve reads it)
    assert sc.report.events("engine_demotion")


def test_scope_is_thread_local():
    """contextvars: a scope entered in one thread is invisible in
    another — the property concurrent serve workers rely on."""
    seen = {}

    def worker(name):
        with resilience.scope(name):
            resilience.note_engine_attempt(name, None)
            resilience.demote_engine(
                name, RuntimeError("RESOURCE_EXHAUSTED: x"),
                shape_key="sk")
            time.sleep(0.05)  # overlap the two scopes
            seen[name] = (resilience.last_engine_attempt()[0],
                          [d.engine for d in resilience.demotions()])

    ts = [threading.Thread(target=worker, args=(f"t{i}",))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert seen["t0"] == ("t0", ["t0"])
    assert seen["t1"] == ("t1", ["t1"])
    assert resilience.demotions() == []
    assert resilience.last_engine_attempt() is None


def test_scope_overrides_health_budget_and_deadline():
    from splatt_tpu.cpd import health_retries

    with resilience.scope("j", health_retries=7, deadline_s=3.5):
        assert health_retries() == 7
        assert resilience.deadline_seconds() == 3.5
        assert resilience.deadline_seconds(default=240) == 3.5
    with resilience.scope("j2", deadline_s=0):
        # 0 = explicitly disabled for this job; site defaults survive
        assert resilience.deadline_seconds() is None
        assert resilience.deadline_seconds(default=240) == 240
    assert resilience.deadline_seconds() is None


def test_scoped_faults_shadow_global_per_context():
    faults.arm("shadow.site", faults.FaultSpec(kind="runtime",
                                               times=faults.ALWAYS))
    with faults.scoped("shadow.site:oom:1"):
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            faults.maybe_fail("shadow.site")
        faults.maybe_fail("shadow.site")  # overlay exhausted: silent
        # un-named sites fall through to the global registry
        with pytest.raises(RuntimeError, match="injected engine"):
            with faults.scoped("other.site:oom:1"):
                faults.maybe_fail("shadow.site")
    with pytest.raises(RuntimeError):  # global spec untouched
        faults.maybe_fail("shadow.site")


def test_scoped_faults_are_context_local():
    res = {}
    with faults.scoped("ctx.site:runtime:*"):
        def w():
            try:
                faults.maybe_fail("ctx.site")
                res["fired"] = False
            except RuntimeError:
                res["fired"] = True
        t = threading.Thread(target=w)
        t.start()
        t.join()
        with pytest.raises(RuntimeError):
            faults.maybe_fail("ctx.site")
    assert res["fired"] is False


# -- review-driven hardening -------------------------------------------------

def test_bad_faults_schedule_rejected_at_submit(tmp_path):
    """A tenant's chaos-schedule typo is rejected at the door with the
    parse error — it can never reach (let alone kill) a supervisor
    worker."""
    srv = serve.Server(str(tmp_path), workers=1)
    r = srv.submit(_spec("typo", faults="cpd.sweep:bogus_kind"))
    assert r["state"] == serve.REJECTED
    assert "bad faults schedule" in r["reason"]
    # the daemon keeps serving its other tenants
    srv.submit(_spec("fine"))
    assert srv.run_once()["counts"][serve.DONE] == 1


def test_rejected_id_may_be_resubmitted(tmp_path):
    """Load shedding invites a retry: once the queue drains, the SAME
    job id is accepted and runs — a queue_full rejection is not a
    permanent verdict."""
    srv = serve.Server(str(tmp_path), workers=1, queue_max=1)
    srv.submit(_spec("first"))
    assert srv.submit(_spec("again"))["state"] == serve.REJECTED
    srv.run_once()  # drains the queue
    retry = srv.submit(_spec("again"))
    assert retry["state"] == serve.ACCEPTED and "duplicate" not in retry
    srv.run_once()
    assert serve.read_result(str(tmp_path), "again")["status"] == \
        "converged"


def test_cooperative_deadline_preempts_worker_thread(tmp_path):
    """The watchdog timer cannot interrupt a non-main worker thread,
    so the job deadline is ALSO enforced through the fit-check stop
    poll: a runaway job releases its worker at the next check,
    TIMEOUT-classified, instead of running its full iteration count."""
    srv = serve.Server(str(tmp_path), workers=1)
    t0 = time.time()
    srv.submit(_spec("runaway", iters=5000, tol=0.0, deadline_s=0.5,
                     synthetic=dict(SYN, nnz=3000)))
    summary = srv.run_once()
    elapsed = time.time() - t0
    assert summary["counts"] == {serve.FAILED: 1}
    res = serve.read_result(str(tmp_path), "runaway")
    assert res["failure_class"] == "timeout"
    # released the worker promptly: nowhere near 5000 iterations
    assert elapsed < 60


def test_explicit_deadline_zero_opts_out_of_server_default(tmp_path):
    """A spec's deadline_s=0 is a documented opt-out: the server-wide
    default must NOT be applied over it."""
    srv = serve.Server(str(tmp_path), workers=1, job_deadline_s=0.2)
    srv.submit(_spec("optout", deadline_s=0,
                     faults="serve.job_run:slow:delay=0.5"))
    summary = srv.run_once()
    assert summary["counts"] == {serve.DONE: 1}, summary
    assert serve.read_result(str(tmp_path), "optout")["status"] == \
        "converged"


def test_idle_run_once_spawns_no_workers(tmp_path, monkeypatch):
    """The serve_forever steady state: an empty queue skips worker-
    thread construction entirely (no per-poll thread churn)."""
    srv = serve.Server(str(tmp_path), workers=4)
    spawned = []
    real = threading.Thread

    class CountingThread(real):
        def __init__(self, *a, **kw):
            spawned.append(kw.get("name"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(threading, "Thread", CountingThread)
    assert srv.run_once()["pending"] == 0
    assert spawned == []


def test_read_status_reports_terminal_status_for_failed_and_rejected(
        tmp_path):
    """The filed-request status API agrees with Server.status() on
    terminal verdicts: failed and rejected jobs report their status,
    and a re-accepted id clears the stale rejection verdict."""
    root = str(tmp_path)
    srv = serve.Server(root, workers=1, queue_max=1)
    srv.submit(_spec("ok"))
    srv.submit(_spec("shed"))  # queue_full -> rejected
    with faults.inject("serve.job_run", "oom", times=1):
        srv.run_once()
    assert serve.read_status(root, "ok")["status"] == "failed"
    assert serve.read_status(root, "shed")["status"] == "rejected"
    # resubmitted after the queue drained: no longer terminal
    srv.submit(_spec("shed"))
    st = serve.read_status(root, "shed")
    assert st["state"] == serve.ACCEPTED and st["status"] is None
