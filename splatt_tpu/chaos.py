"""Chaos-schedule soak harness — `splatt chaos` (docs/guarded-als.md).

Resilience machinery that is only exercised by unit tests decays the
moment two guards interact in a way no unit test composed.  This module
runs a REAL (small, seeded, synthetic) CPD under a declarative fault
schedule — NaN poisoning, blown deadlines, transient service failures,
engine crashes, all at once — and asserts the single invariant the
guarded execution layer promises:

    **converged-or-gracefully-degraded, with zero unhandled exceptions
    and a complete run report.**

Concretely, a chaos run passes iff:

1. no exception escapes the drivers (the guards caught everything);
2. every armed fault that actually FIRED left a matching run-report
   event (``health_*`` for poison, ``deadline_blown``/demotion for
   slow, ``transient_retry``/demotion for raising kinds) — degradation
   is observable, never silent;
3. every emitted event kind is declared in
   :data:`splatt_tpu.resilience.RUN_REPORT_EVENTS` (the report is
   complete/documented);
4. the final factors are finite, or the run explicitly reported a
   ``health_degraded`` verdict.

``splatt chaos --smoke`` is the tier-1 entry: a seconds-scale seeded
run on a tiny tensor, exercised on every PR so the soak invariant
cannot rot.  The full-size invocation (bigger tensor, more iterations,
probabilistic schedules) is the soak tool operators run against new
jax/device combinations before trusting them.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

import numpy as np

#: default schedule: one of each guard's quarry — a NaN poisoning at a
#: fixed iteration (sentinel + rollback), a slow tuner measurement
#: under the deadline watchdog (TIMEOUT), a transient service failure at
#: an engine's first compile (retry-with-backoff; ``engine.xla`` is
#: the terminal engine, live on every backend), and a ring-exchange
#: failure in the distributed comm drill (the async-ring sweep must
#: degrade classified down the comm chain — docs/ring.md).
#: Deterministic: every trigger is count- or iteration-keyed; add a
#: probabilistic leg via --schedule 'site:kind:p=0.1:seed=N'.
DEFAULT_SCHEDULE = ("cpd.sweep:nan:iter=2,"
                    "tuner.measure:slow:delay=1.5,"
                    "engine.xla:internal:1,"
                    "comm.ring_exchange:runtime:1")

#: expected run-report evidence per fired fault kind: at least one of
#: these event kinds must appear when a fault of that kind fired
_EVIDENCE = {
    "nan": ("health_nonfinite", "health_rollback", "health_degraded"),
    "inf": ("health_nonfinite", "health_rollback", "health_degraded"),
    "slow": ("deadline_blown",),
    "http500": ("transient_retry", "engine_demotion",
                "tuner_negative", "probe_downgrade", "comm_fallback"),
    "internal": ("transient_retry", "engine_demotion",
                 "tuner_negative", "probe_downgrade", "comm_fallback"),
    "unavailable": ("transient_retry", "engine_demotion",
                    "tuner_negative", "probe_downgrade", "comm_fallback"),
    "timeout": ("transient_retry", "engine_demotion",
                "tuner_negative", "probe_downgrade", "comm_fallback"),
    "oom": ("engine_demotion", "tuner_negative", "probe_downgrade",
            "comm_fallback"),
    "mosaic": ("engine_demotion", "tuner_negative", "probe_downgrade",
               "comm_fallback"),
    "runtime": ("engine_demotion", "tuner_negative",
                "checkpoint_recovery", "probe_downgrade",
                "comm_fallback"),
}


@dataclasses.dataclass
class ChaosResult:
    """One chaos run's verdict and its evidence."""

    verdict: str                  # "converged" | "degraded" | "violated"
    fit: Optional[float]
    finite: bool
    fired: Dict[str, int]         # site -> how often its fault fired
    events: List[dict]            # the full run report
    violations: List[str]         # invariant breaches (empty = pass)
    error: Optional[str] = None   # the escaped exception, if any
    schedule: str = ""            # the RESOLVED schedule that ran

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return dict(verdict=self.verdict, fit=self.fit,
                    finite=self.finite, fired=self.fired,
                    violations=self.violations, error=self.error,
                    schedule=self.schedule,
                    events=[{k: v for k, v in e.items() if k != "ts"}
                            for e in self.events])


def synthetic_tensor(dims, nnz: int, seed: int):
    """Seeded power-law synthetic tensor (every slice nonempty so the
    CPD shapes are exact) — shared by the chaos soak and the serve
    daemon's ``{"synthetic": ...}`` job workloads (serve.py)."""
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(seed)
    inds = np.empty((len(dims), nnz), dtype=np.int64)
    for m, d in enumerate(dims):
        raw = rng.zipf(1.4, size=nnz).astype(np.int64)
        inds[m] = (raw + rng.integers(0, d, size=nnz)) % d
    vals = rng.random(nnz) + 0.1
    return SparseTensor(inds, vals, dims).deduplicate() \
                                         .remove_empty_slices()


def run_chaos(schedule: Optional[str] = None, seed: int = 0,
              dims=(40, 32, 24), nnz: int = 3000, rank: int = 4,
              iters: int = 8, deadline_s: float = 0.5,
              tune_first: bool = True, smoke: bool = False,
              verbose: bool = False,
              trace_path: Optional[str] = None) -> ChaosResult:
    """Run one seeded CPD soak under a chaos schedule and check the
    guarded-execution invariant.  Owns process-global resilience state
    (faults, demotions, the run report, the deadline override): a chaos
    run is a diagnostic, not a library call — it resets that state on
    entry and disarms on exit.

    With `trace_path` the soak additionally exercises the exporter end
    to end (docs/observability.md): span recording is enabled for the
    run, the recorder is exported to a Chrome trace-event file at
    `trace_path`, and the invariant gains two legs — the export must
    succeed (a ``trace_written`` ok event), and every fired fault's
    run-report evidence must ALSO appear as point events on the trace
    (the event-on-span wiring cannot silently rot).
    """
    from splatt_tpu import resilience, trace, tune
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.cpd import cpd_als
    from splatt_tpu.utils import faults
    from splatt_tpu.utils.env import read_env

    if schedule is None:
        schedule = str(read_env("SPLATT_CHAOS_SCHEDULE") or "") \
            or DEFAULT_SCHEDULE
    if smoke:
        dims, nnz, rank, iters = (20, 16, 12), 1200, 3, 6
    specs = faults.parse_schedule(schedule)

    faults.reset()
    resilience.reset_demotions()
    resilience.run_report().clear()
    if trace_path:
        # the exporter leg: a fresh recorder, spans ON for the whole
        # soak (the guards' own spans included), exported in `finally`
        trace.reset()
        trace.set_enabled(True)
    # 0 = explicit disable (beats an exported SPLATT_DEADLINE_S); the
    # probe's own always-on default survives either way
    resilience.set_deadline(deadline_s if deadline_s > 0 else 0.0)
    for site, spec in specs.items():
        faults.arm(site, spec)

    tt = synthetic_tensor(dims, nnz, seed)
    opts = Options(random_seed=seed, max_iterations=iters,
                   verbosity=Verbosity.LOW if verbose
                   else Verbosity.NONE,
                   use_pallas=False,   # CPU-safe: xla_scan/xla engines
                   autotune=False)     # plans measured live, not cached
    error = None
    fit = None
    finite = False
    out = None
    try:
        import tempfile

        with tempfile.TemporaryDirectory(prefix="splatt-chaos-") as td:
            # a throwaway plan cache: plans measured under injected
            # faults must never leak into the real cache
            tune.set_cache_path(f"{td}/tune_cache.json")
            if tune_first and "tuner.measure" in specs:
                # exercise the tuner leg of the schedule: measurements
                # run under the deadline watchdog and must degrade,
                # not crash
                tune.tune(tt, rank=rank, opts=opts, blocks=(512,),
                          scan_targets=(1 << 21,), reps=1)
            bs = BlockedSparse.from_coo(tt, opts)
            out = cpd_als(bs, rank=rank, opts=opts)
            if "comm.ring_exchange" in specs:
                # distributed comm drill (docs/ring.md): a small FINE
                # async-ring CPD under the armed ring-exchange fault —
                # the failure must degrade classified down the comm
                # chain (async_ring -> ring -> all2all, comm_fallback
                # evidence) and still converge, never escape
                from splatt_tpu.config import CommPattern
                from splatt_tpu.parallel.sharded import sharded_cpd_als

                dopts = Options(random_seed=seed, max_iterations=3,
                                verbosity=opts.verbosity,
                                use_pallas=False, autotune=False,
                                comm_pattern=CommPattern.ASYNC_RING)
                dout = sharded_cpd_als(tt, rank=rank, opts=dopts,
                                       measure_overlap=False)
                if not all(np.isfinite(np.asarray(U)).all()
                           for U in dout.factors):
                    raise RuntimeError(
                        "comm drill produced non-finite factors")
        fit = float(out.fit)
        finite = bool(all(np.isfinite(np.asarray(U)).all()
                          for U in out.factors)
                      and np.isfinite(np.asarray(out.lam)).all())
    except Exception as e:  # the invariant IS "nothing escapes"
        error = (f"{resilience.classify_failure(e).value}: "
                 f"{resilience.failure_message(e)[:300]}")
    finally:
        fired = faults.fired()
        faults.reset()
        resilience.set_deadline(None)
        tune.set_cache_path(None)
        trace_ev = None
        trace_points: List[dict] = []
        if trace_path:
            trace_points = trace.points()
            trace_ev = trace.write_chrome_trace(trace_path)
            trace.set_enabled(None)

    report = resilience.run_report()
    events = report.events()
    degraded = bool(report.events("health_degraded"))

    violations: List[str] = []
    if error is not None:
        violations.append(f"unhandled exception escaped the guarded "
                          f"drivers: {error}")
    if trace_path:
        # the exporter legs of the invariant (docs/observability.md)
        if not (trace_ev and trace_ev.get("ok")):
            violations.append(
                f"trace export to {trace_path} failed: "
                f"{(trace_ev or {}).get('failure_class')}: "
                f"{(trace_ev or {}).get('error')}")
        else:
            try:
                exported = trace.load_trace(trace_path)
                if not any(e.get("ph") == "X" for e in exported):
                    violations.append(
                        f"exported trace {trace_path} holds no spans — "
                        f"the soak ran with recording on")
            except (OSError, ValueError) as e:
                violations.append(
                    f"exported trace {trace_path} is not loadable "
                    f"Chrome trace-event JSON: {e}")
        point_kinds = {p["name"] for p in trace_points}
        for site, spec in specs.items():
            if fired.get(site, 0) == 0:
                continue
            want = _EVIDENCE.get(spec.kind, ())
            if want and not point_kinds & set(want):
                violations.append(
                    f"fault {site}:{spec.kind} fired but none of its "
                    f"evidence events {list(want)} reached the trace "
                    f"as point events — the event-on-span wiring is "
                    f"broken")
    for site, spec in specs.items():
        if fired.get(site, 0) == 0:
            continue
        want = _EVIDENCE.get(spec.kind, ())
        if want and not any(report.events(kind) for kind in want):
            violations.append(
                f"fault {site}:{spec.kind} fired "
                f"{fired[site]}x but left none of the expected "
                f"run-report events {list(want)} — silent degradation")
    undeclared = sorted({e["kind"] for e in events}
                        - set(resilience.RUN_REPORT_EVENTS))
    if undeclared:
        violations.append(f"run report contains undeclared event "
                          f"kinds {undeclared} (SPL012 contract)")
    if error is None and not finite and not degraded:
        violations.append("final factors are non-finite and the run "
                          "did not report a health_degraded verdict")

    verdict = ("violated" if violations
               else "degraded" if degraded else "converged")
    return ChaosResult(verdict=verdict, fit=fit, finite=finite,
                       fired=dict(fired), events=events,
                       violations=violations, error=error,
                       schedule=schedule)


# -- bench regression gate (docs/format.md, ROADMAP open item 1) ------------
#
# `splatt chaos --smoke --bench-gate` folds the PR 6 bench regression
# gate into the chaos smoke tier: a smoke-sized `python bench.py
# --gate` run in a subprocess, so a format/engine change that regresses
# >10% against the newest same-metric prior BENCH_*.json fails the PR
# loudly next to the resilience invariant — not silently in a later
# full-scale bench.

def run_bench_gate(smoke: bool = True,
                   timeout_s: Optional[float] = None) -> dict:
    """Run ``python bench.py --gate`` as a subprocess (smoke-sized env
    defaults unless the caller already pinned SPLATT_BENCH_* knobs) and
    return ``{ok, returncode, record, stderr_tail}``.  The record is
    the parsed headline JSON line — including the per-path achieved
    bytes (``model_gb_per_path``) and format summaries the gate
    compares.  The default timeout scales with the tier: the smoke
    bench is seconds, the full-scale default bench (20M nnz + the
    stream oracle) legitimately runs tens of minutes."""
    import json
    import os
    import subprocess
    import sys

    if timeout_s is None:
        timeout_s = 900.0 if smoke else 3 * 3600.0

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "bench.py")
    # splint: ignore[SPL001] forwarding the whole environment to the
    # bench subprocess, not reading config — no single ENV_VARS name
    env = dict(os.environ)
    # the gate child benches the CPU on purpose: the parent may hold the
    # chip (one process per chip), and bench.py measures the CPU only
    # when asked to by name
    env["JAX_PLATFORMS"] = "cpu"
    if smoke:
        # seconds-scale: small tensor, the two format rows the gate is
        # really about; "tuned"/"stream" stay out of the smoke tier
        env.setdefault("SPLATT_BENCH_NNZ", "60000")
        env.setdefault("SPLATT_BENCH_RANK", "8")
        env.setdefault("SPLATT_BENCH_ITERS", "2")
        env.setdefault("SPLATT_BENCH_PATHS", "blocked,compact")
    try:
        p = subprocess.run([sys.executable, bench, "--gate"], env=env,
                           capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.SubprocessError as e:
        return dict(ok=False, returncode=-1, record=None,
                    stderr_tail=str(e)[-400:])
    record = None
    for line in reversed(p.stdout.splitlines()):
        if line.startswith("{"):
            try:
                record = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    return dict(ok=(p.returncode == 0 and record is not None),
                returncode=p.returncode, record=record,
                stderr_tail=p.stderr[-400:])


# -- serve soak (docs/serve.md) ---------------------------------------------
#
# The single-run soak above cannot exercise the serve daemon's two
# load-bearing promises: (1) kill-and-restart mid-queue loses no
# accepted job, and (2) one tenant's injected NaN never demotes (or
# otherwise poisons) a neighbor's engines.  This soak proves both with
# a REAL daemon subprocess: file jobs, start `splatt serve --once`,
# SIGKILL it mid-job (a per-job `serve.job_run:slow` fault pins the
# first job open so the kill window is deterministic), restart, and
# assert every accepted job reached a terminal state with the
# isolation evidence in its result record.

@dataclasses.dataclass
class ServeChaosResult:
    """One serve kill-and-restart soak's verdict and evidence."""

    verdict: str                  # "survived" | "violated"
    jobs: Dict[str, str]          # job id -> terminal status
    killed_mid_queue: bool        # the SIGKILL landed before drain
    resumed: List[str]            # jobs the restart re-enqueued
    violations: List[str]         # invariant breaches (empty = pass)
    error: Optional[str] = None
    #: which durable-op crash windows the SIGKILL actually landed in
    #: (ids from the crash-point checker's vocabulary,
    #: tools/splint/crashpoint.py) — the dynamic half of the
    #: static-vs-dynamic coverage comparison in docs/static-analysis.md
    crash_windows: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _crash_windows_exercised(root: str) -> List[str]:
    """Classify the spool's post-kill state into the durable-op crash
    windows the kill evidently landed in.

    The ids come from the crash-point checker's window vocabulary
    (``tools/splint/crashpoint.py``), which enumerates EVERY window
    exhaustively; a soak's SIGKILL samples a handful per run.  Emitting
    the sampled set (the ``crash_windows_exercised`` run-report event)
    makes that gap measurable instead of anecdotal — the comparison
    lives in docs/static-analysis.md.  Classification is conservative:
    only states that are unambiguous evidence of a window are counted.
    """
    from splatt_tpu import serve

    windows = set()
    jpath = os.path.join(root, "journal.jsonl")
    try:
        with open(jpath, "rb") as f:
            data = f.read()
    except OSError:
        data = b""
    if data:
        windows.add("journal.append")
        if not data.endswith(b"\n"):
            windows.add("journal.append.torn")
    # publish-window debris: a crash between the tmp write and the
    # atomic rename leaves the pid-stamped tmp beside the destination
    for dirpath, _dirs, names in os.walk(root):
        for name in names:
            if ".tmp" not in name:
                continue
            if "gen.json.bak" in name:
                windows.add("stamp.bak.publish")
            elif "gen.json" in name:
                windows.add("stamp.publish")
            elif ".npz" in name:
                windows.add("ckpt.publish")
            elif os.path.basename(dirpath) == "results":
                windows.add("result.publish")
            elif os.path.basename(dirpath) == "leases":
                windows.add("lease.publish")
    try:
        recs, torn = serve.Journal(jpath).replay()
    # splint: ignore[SPL002] post-mortem classification is best-effort
    # evidence gathering — an unreadable journal yields no windows,
    # and the soak's own invariant audit reports the breakage
    except Exception:
        recs, torn = [], 0
    if torn:
        windows.add("journal.append.torn")
    by_job: Dict[str, List[str]] = {}
    for r in recs:
        if r.get("job"):
            by_job.setdefault(r["job"], []).append(r.get("rec"))
    for jid, kinds in by_job.items():
        terminal = any(k in serve.TERMINAL for k in kinds)
        res = serve.read_result(root, jid)
        if res is not None and not terminal:
            # the terminal-commit protocol is result publish THEN the
            # terminal append: a result with no terminal record means
            # the crash landed before the final journal append
            windows.add("journal.append[done]")
        elif terminal and res is None:
            windows.add("result.publish")
    return sorted(windows)


def run_serve_chaos(seed: int = 0, smoke: bool = True,
                    verbose: bool = False) -> ServeChaosResult:
    """Kill-and-restart soak of the serve daemon (docs/serve.md).

    Files three jobs — one NaN-poisoned (sentinel + rollback), pinned
    open by a slow fault so the SIGKILL deterministically lands
    mid-job, and two clean neighbors — starts the daemon, SIGKILLs it,
    restarts with ``--once`` and checks:

    1. every accepted job reached a terminal state (no accepted job is
       lost to the crash);
    2. the journal replays cleanly and shows a resume lineage;
    3. the NaN job's result carries the health evidence
       (``health_rollback``/``health_degraded``) and demoted NOTHING;
    4. the clean jobs' results carry no health events and no demotions
       — the poisoned tenant stayed contained.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile
    import time

    from splatt_tpu import resilience, serve

    dims, nnz, rank, iters = (20, 16, 12), 1200, 3, 6
    if not smoke:
        dims, nnz, rank, iters = (40, 32, 24), 3000, 4, 10
    syn = {"dims": list(dims), "nnz": nnz, "seed": seed}
    violations: List[str] = []
    jobs: Dict[str, str] = {}
    resumed: List[str] = []
    crash_windows: List[str] = []
    killed_mid_queue = False
    error = None
    tmp = tempfile.mkdtemp(prefix="splatt-serve-chaos-")
    # splint: ignore[SPL001] forwarding the whole environment to the
    # daemon subprocess, not reading config — no single ENV_VARS name
    env = dict(os.environ)
    # a throwaway plan cache: plans the soak's jobs measure must never
    # leak into the real shared cache
    env["SPLATT_TUNE_CACHE"] = os.path.join(tmp, "tune_cache.json")
    # persistent executable cache shared across the kill (utils/
    # env.py): the restarted daemon re-adopts its jobs WITHOUT paying
    # the original's XLA compiles — single-device programs only, the
    # CPU-safe scope (see run_fleet_chaos)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "xla_cache")
    # a CPU-only soak: the daemon is pinned to the CPU so it never
    # contends with a parent that may hold the chip (one process per
    # chip)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        # the NaN job's id sorts FIRST ("0" < "c" in the spool's
        # sorted-filename ingest order), so with one worker it is the
        # job the slow fault pins open — the kill window below is
        # keyed to ITS started record, not to whichever job happened
        # to start first
        nan_id = "chaos-0-nan"
        nan_job = {"id": nan_id, "rank": rank, "iters": iters,
                   "synthetic": syn, "health_retries": 2,
                   "faults": "serve.job_run:slow:delay=4,"
                             "cpd.sweep:nan:iter=2"}
        clean = [{"id": f"chaos-clean{i}", "rank": rank, "iters": iters,
                  "synthetic": dict(syn, seed=seed + 1 + i)}
                 for i in range(2)]
        for spec in [nan_job] + clean:
            serve.file_request(tmp, spec)
        cmd = [sys.executable, "-m", "splatt_tpu.cli", "serve", tmp,
               "--once", "--workers", "1"]
        jpath = os.path.join(tmp, "journal.jsonl")
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE)
        deadline = time.time() + 180
        started = False
        while time.time() < deadline and proc.poll() is None:
            started = any(
                r.get("rec") == "started" and r.get("job") == nan_id
                for r in serve.Journal(jpath).replay()[0])
            if started:
                break
            time.sleep(0.1)
        if started and proc.poll() is None:
            time.sleep(0.5)  # well inside the 4 s slow-fault window
            proc.kill()      # SIGKILL: no drain, no cleanup
            killed_mid_queue = True
        else:
            violations.append(
                "daemon finished (or died) before the kill — the soak "
                "did not exercise a mid-queue restart")
        proc.wait(timeout=60)

        # post-mortem, BEFORE the restart heals anything: which crash
        # windows did this kill actually land in?
        crash_windows = _crash_windows_exercised(tmp)
        resilience.run_report().add(
            "crash_windows_exercised", soak="serve",
            windows=",".join(crash_windows))

        restart = subprocess.run(cmd + ["--json"], env=env,
                                 capture_output=True, text=True,
                                 timeout=600)
        if restart.returncode != 0:
            violations.append(
                f"restarted daemon exited nonzero "
                f"({restart.returncode}): {restart.stderr[-300:]}")

        recs, torn = serve.Journal(jpath).replay()
        accepted = {r["job"] for r in recs if r.get("rec") == "accepted"}
        resumed = sorted({r["job"] for r in recs
                          if r.get("rec") == "resumed"})
        if killed_mid_queue and not resumed:
            violations.append("kill landed mid-queue but the restart "
                              "resumed nothing — journal replay broken")
        for jid in sorted(accepted):
            res = serve.read_result(tmp, jid)
            states = [r.get("rec") for r in recs if r.get("job") == jid]
            if not any(s in serve.TERMINAL for s in states):
                violations.append(f"accepted job {jid} never reached a "
                                  f"terminal state — a job was LOST")
                jobs[jid] = "lost"
                continue
            if res is None:
                violations.append(f"job {jid} is terminal but published "
                                  f"no result record")
                jobs[jid] = "no-result"
                continue
            jobs[jid] = res["status"]
            kinds = {e["kind"] for e in res.get("events", [])}
            if jid == nan_id:
                if res["status"] == "converged" \
                        and not kinds & {"health_rollback",
                                         "health_degraded"}:
                    violations.append(
                        "the NaN job converged with no health evidence "
                        "— the injected fault was silently lost")
                if res.get("demotions"):
                    violations.append(
                        "the NaN job demoted engines — NUMERICAL "
                        "failures must roll back, never demote")
            else:
                if kinds & {"health_nonfinite", "health_rollback",
                            "health_degraded"}:
                    violations.append(
                        f"clean job {jid} carries health events — the "
                        f"NaN tenant leaked into a neighbor")
                if res.get("demotions"):
                    violations.append(
                        f"clean job {jid} carries engine demotions "
                        f"{res['demotions']} — cross-job poisoning")
                if res["status"] != "converged":
                    violations.append(
                        f"clean job {jid} finished {res['status']!r} "
                        f"instead of converging")
    except Exception as e:  # the harness itself must not crash the CLI
        error = (f"{resilience.classify_failure(e).value}: "
                 f"{resilience.failure_message(e)[:300]}")
        violations.append(f"serve-chaos harness error: {error}")
    verdict = "violated" if violations else "survived"
    return ServeChaosResult(verdict=verdict, jobs=jobs,
                            killed_mid_queue=killed_mid_queue,
                            resumed=resumed, violations=violations,
                            error=error, crash_windows=crash_windows)


# -- fleet soak (docs/fleet.md) ---------------------------------------------
#
# The single-daemon soak above proves kill-and-RESTART; the fleet's
# promise is kill-and-FAILOVER: with N replicas over one spool, a
# SIGKILLed replica's accepted jobs must be adopted by live peers
# (lease takeover after expiry), finish exactly once, and — when the
# adopted job shares a shape regime with work a peer already ran —
# hit the warm shared caches (the Nth-request-is-free property
# surviving the failover).  This soak drives a REAL fleet of daemon
# subprocesses under multi-tenant load and audits all of it from the
# shared journal, the per-replica Prometheus snapshots and the
# per-replica span traces.

@dataclasses.dataclass
class FleetChaosResult:
    """One fleet kill-and-failover soak's verdict and evidence."""

    verdict: str                  # "survived" | "violated"
    jobs: Dict[str, str]          # job id -> terminal status
    replicas: List[str]           # replica ids (incl. the restart)
    victim: Optional[str]         # the SIGKILLed replica
    adopted: List[str]            # jobs that changed hands
    affinity: Dict[str, dict]     # adopted tune jobs' warm-cache stats
    violations: List[str]         # invariant breaches (empty = pass)
    error: Optional[str] = None
    #: fleet-aggregate evidence the kill is visible end-to-end
    #: (docs/observability.md): merged adoption/lease/slo-burn
    #: counters, the liveness census, and the victim's flight-ring
    #: event count
    observability: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    #: which durable-op crash windows the victim's SIGKILL landed in
    #: (crash-point checker vocabulary, tools/splint/crashpoint.py)
    crash_windows: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _fleet_lineage_violations(recs: List[dict]) -> List[str]:
    """Audit the shared journal's per-job ownership lineage: a job may
    only start on a second replica after an ``adopted`` takeover (or a
    clean ``interrupted`` handback), and reaches at most one terminal
    record — the journal-level face of 'no job runs on two replicas
    at once'."""
    from splatt_tpu import serve

    out: List[str] = []
    by_job: Dict[str, List[dict]] = {}
    for r in recs:
        if r.get("job") and r.get("rec"):
            by_job.setdefault(r["job"], []).append(r)
    for jid, rl in sorted(by_job.items()):
        owner = None
        terminals = 0
        for r in rl:
            k, rid = r["rec"], r.get("replica")
            if k == serve.STARTED:
                if owner is not None and rid != owner:
                    out.append(
                        f"job {jid} started on {rid} while owned by "
                        f"{owner} with no adoption/interruption "
                        f"between — double execution")
                owner = rid
            elif k == serve.ADOPTED:
                owner = rid
            elif k == serve.INTERRUPTED:
                owner = None
            elif k in (serve.DONE, serve.FAILED):
                terminals += 1
        if terminals > 1:
            out.append(f"job {jid} reached {terminals} terminal "
                       f"records — committed more than once")
    return out


def _predict_staleness_violations(recs: List[dict]) -> List[str]:
    """Audit the generation fence from the journal ALONE
    (docs/predict.md): every served prediction's generation must be >=
    the newest generation COMMITTED before that predict was accepted.
    Commit ``done`` records carry ``model``/``model_gen``; predict
    ``accepted`` records carry ``gen_pinned`` (the marker) and their
    spec; predict ``done`` records carry the served ``gen``.  The
    journal is totally ordered (one flocked file), so walking it in
    order reconstructs what any reader could have known."""
    from splatt_tpu import serve

    out: List[str] = []
    committed: Dict[str, int] = {}
    floor: Dict[str, int] = {}
    for r in recs:
        k, jid = r.get("rec"), r.get("job")
        if k == serve.ACCEPTED and "gen_pinned" in r:
            model = str((r.get("spec") or {}).get("model") or "")
            floor[jid] = committed.get(model, 0)
        elif k == serve.DONE:
            if r.get("model_gen") is not None:
                m = str(r.get("model"))
                committed[m] = max(committed.get(m, 0),
                                   int(r["model_gen"]))
            if r.get("status") == "served" and jid in floor:
                gen = int(r.get("gen") or 0)
                if gen < floor[jid]:
                    out.append(
                        f"predict {jid} served generation {gen} but "
                        f"generation {floor[jid]} was committed before "
                        f"it was accepted — a STALE read")
    return out


def run_fleet_chaos(seed: int = 0, smoke: bool = True,
                    replicas: Optional[int] = None,
                    verbose: bool = False) -> FleetChaosResult:
    """Kill-and-failover soak of a serve fleet (docs/fleet.md).

    Starts N ``splatt serve --fleet`` replica daemons over one shared
    spool (short leases, shared warm caches, per-replica metrics and
    traces), warms one shape regime, then files multi-tenant load
    including a same-regime job pinned open by a slow fault.  SIGKILLs
    the replica that claimed the pinned job mid-run, restarts a
    replacement, and checks:

    1. every accepted job reaches a terminal state (zero jobs lost to
       the kill);
    2. the pinned job changed hands: an ``adopted`` journal record
       from the victim, its terminal record on a survivor, and the
       single-owner lineage audit clean for EVERY job (no job ever
       ran on two replicas at once);
    3. the adopted same-regime job hit the warm shared caches
       (``tune.cache_hits > 0`` with zero measurements) — affinity
       evidence surviving failover;
    4. per-tenant isolation: the NaN tenant rolled back/degraded with
       zero demotions, every clean tenant finished converged with no
       health events and no demotions;
    5. the fleet's observability accounts for the failover: the
       adopter's Prometheus snapshot counts the adoption and its span
       trace carries the ``job_adopted`` point event;
    6. the kill is visible END-TO-END in the fleet observability
       plane (docs/observability.md): the merged fleet aggregate
       shows the lease expiry + adoption + an ``slo_burn`` spike (the
       replicas run with tight ``SPLATT_SLO_*`` knobs, so the
       adoption's queue-wait outage burns the error budget) that
       RECOVERS once the fleet is quiet; the victim's flight-recorder
       ring replays its timeline up to the kill — the pinned job's
       ``job_started`` liveness mark included; and ``splatt status``
       agrees with the journal about every job's state;
    7. the generation-fenced predict plane (docs/predict.md) under
       the same kill: a predict filed in the mid-kill burst is never
       lost, predicts interleaved with the update commit violate no
       staleness (every served generation >= the newest generation
       committed before acceptance, audited from the journal alone),
       >= 1 predict is served once the base model commits, and a
       predict against a shredded model (checkpoint + .bak + both
       generation stamps) REFUSES instead of serving garbage.
    """
    import json
    import os
    import signal as _signal
    import subprocess
    import sys
    import tempfile
    import time

    from splatt_tpu import resilience, serve, trace

    nrep = int(replicas) if replicas else (2 if smoke else 3)
    dims, nnz, rank, iters = (20, 16, 12), 1200, 3, 6
    if not smoke:
        dims, nnz, rank, iters = (40, 32, 24), 3000, 4, 10
    syn = {"dims": list(dims), "nnz": nnz, "seed": seed}
    violations: List[str] = []
    jobs: Dict[str, str] = {}
    adopted: List[str] = []
    affinity: Dict[str, dict] = {}
    crash_windows: List[str] = []
    rids = [f"r{i}" for i in range(nrep)]
    victim = None
    error = None
    procs: Dict[str, object] = {}
    logs = []
    tmp = tempfile.mkdtemp(prefix="splatt-fleet-chaos-")
    jpath = os.path.join(tmp, "journal.jsonl")
    # splint: ignore[SPL001] forwarding the whole environment to the
    # daemon subprocesses, not reading config — no single ENV_VARS name
    base_env = dict(os.environ)
    # shared WARM caches (the point of the fleet) but throwaway ones
    # (soak plans must not leak into the real caches); short leases so
    # failover fits a smoke budget.  The observability plane runs at
    # soak scale too: metrics/aggregation/SLO ticks sub-second, TIGHT
    # SLO knobs (any queue wait past 1s — e.g. the adoption outage —
    # burns the whole error budget at once, so the kill must show as
    # an slo_burn spike), and a flush-every-record flight ring so the
    # victim's black box is current up to the SIGKILL.
    base_env.update(
        SPLATT_TUNE_CACHE=os.path.join(tmp, "tune_cache.json"),
        SPLATT_PROBE_CACHE=os.path.join(tmp, "probe_cache.json"),
        SPLATT_FLEET_LEASE_S="2.0", SPLATT_FLEET_HEARTBEAT_S="0.5",
        SPLATT_SERVE_POLL_S="0.25",
        SPLATT_METRICS_INTERVAL_S="0.7",
        SPLATT_SLO_QUEUE_WAIT_P95_S="1.0",
        SPLATT_SLO_WINDOW_S="3.0", SPLATT_SLO_LONG_WINDOWS="4",
        SPLATT_SLO_BURN="1.5", SPLATT_FLIGHT_FLUSH="1",
        # batched + update tenant mix (docs/batched.md): two queued
        # same-regime jobs coalesce into one vmapped batch, and the
        # update tenant exercises the model store under failover
        SPLATT_SERVE_BATCH_MIN="2", SPLATT_UPDATE_SWEEPS="2",
        # shared persistent executable cache (utils/env.py,
        # ROADMAP item 4): replica 0's first compile of the common
        # job regime warms every peer, respawn and failover adoptee —
        # the cold-replica-skips-compile path, exercised under kills.
        # Safe here because replica jobs are single-device programs;
        # the suite's own process must NOT set this (sharded CPU
        # executables corrupt the heap when deserialized — see
        # tests/conftest.py)
        JAX_COMPILATION_CACHE_DIR=os.path.join(tmp, "xla_cache"),
        # a CPU-only soak: several replica daemons share one host, so
        # they are pinned to the CPU and never contend for the chip
        JAX_PLATFORMS="cpu")
    # SPLATT_METRICS_PATH stays UNSET: fleet mode defaults each
    # replica's snapshot into <root>/fleet/metrics/<rid>.prom, which
    # is where the aggregator (and this soak's post-mortem) finds
    # them — retired/killed replicas' files included

    def spawn(rid: str):
        env = dict(base_env)
        log = open(os.path.join(tmp, f"{rid}.log"), "w")
        logs.append(log)
        cmd = [sys.executable, "-m", "splatt_tpu.cli", "serve", tmp,
               "--fleet", "--replica", rid, "--workers", "1",
               "--trace", os.path.join(tmp, f"trace-{rid}.json")]
        if verbose:
            cmd.append("-v")
        procs[rid] = subprocess.Popen(cmd, env=env, stdout=log,
                                      stderr=subprocess.STDOUT)

    def states() -> Dict[str, tuple]:
        recs, _ = serve.Journal(jpath).replay()
        out: Dict[str, tuple] = {}
        for r in recs:
            if r.get("job") and r.get("rec"):
                out[r["job"]] = (r["rec"], r.get("replica"))
        return out

    def wait_for(pred, deadline_s: float, what: str) -> bool:
        end = time.time() + deadline_s
        while time.time() < end:
            if pred():
                return True
            if all(p.poll() is not None for p in procs.values()):
                violations.append(
                    f"every replica exited while waiting for {what}")
                return False
            time.sleep(0.15)
        violations.append(f"timed out waiting for {what}")
        return False

    try:
        for rid in rids:
            spawn(rid)
        # phase 1 — warm one shape regime fleet-wide: the shared plan
        # cache is what makes the later adoption's Nth request free
        warm = {"id": "fleet-0-warm", "tenant": "acme", "rank": rank,
                "iters": iters, "tune": True, "synthetic": syn}
        serve.file_request(tmp, warm)
        if not wait_for(lambda: states().get("fleet-0-warm",
                                             (None,))[0]
                        in serve.TERMINAL, 300, "the warm job"):
            raise RuntimeError("fleet soak setup failed")
        # phase 2 — multi-tenant load, including the pinned
        # same-regime job the kill will orphan mid-run
        pin = {"id": "fleet-1-pin", "tenant": "acme", "rank": rank,
               "iters": iters, "tune": True,
               "synthetic": dict(syn, seed=seed + 1),
               "faults": "serve.job_run:slow:delay=5"}
        nan = {"id": "fleet-2-nan", "tenant": "beta", "rank": rank,
               "iters": iters, "health_retries": 2,
               "synthetic": dict(syn, seed=seed + 2),
               "faults": "cpd.sweep:nan:iter=2"}
        clean = {"id": "fleet-3-clean", "tenant": "coyote",
                 "rank": rank, "iters": iters,
                 "synthetic": dict(syn, seed=seed + 3)}
        # the update tenant's base model: a plain cpd job whose
        # checkpoint becomes the model store the later update advances
        # (iters offset by one: a distinct coalescing key, so the base
        # never rides a batch — batched runs do not checkpoint, and
        # the update wants the warm model)
        base_job = {"id": "fleet-4-base", "tenant": "epsilon",
                    "rank": rank, "iters": iters + 1,
                    "checkpoint_every": 2,
                    "synthetic": dict(syn, seed=seed + 4)}
        for spec in (pin, nan, clean, base_job):
            serve.file_request(tmp, spec)
        if not wait_for(
                lambda: states().get("fleet-1-pin",
                                     (None,))[0] == serve.STARTED,
                120, "the pinned job to start"):
            raise RuntimeError("fleet soak setup failed")
        victim = states()["fleet-1-pin"][1]
        if victim not in procs:
            raise RuntimeError(f"journal names unknown replica "
                               f"{victim!r} for the pinned job")
        time.sleep(0.5)  # well inside the 5 s slow-fault window
        procs[victim].kill()  # SIGKILL: no drain, no lease release
        procs[victim].wait(timeout=60)
        # post-mortem before the survivors heal the spool: which crash
        # windows did this kill actually land in?
        crash_windows = _crash_windows_exercised(tmp)
        resilience.run_report().add(
            "crash_windows_exercised", soak="fleet",
            windows=",".join(crash_windows))
        # batched tenant mix (docs/batched.md): filed in one burst
        # while the victim is dead, so one survivor ingests the set
        # together and its >= SPLATT_SERVE_BATCH_MIN same-key queue
        # coalesces into one vmapped batch (ingestion races across
        # replicas can still split the set — the post-mortem records
        # achieved coverage, the lineage audit holds either way)
        bsyn = {"dims": [16, 12, 10], "nnz": 800}
        batch_jobs = [f"fleet-b{i}" for i in range(3)]
        for i, bid in enumerate(batch_jobs):
            serve.file_request(tmp, {
                "id": bid, "tenant": "delta", "rank": 3, "iters": 4,
                "synthetic": dict(bsyn, seed=seed + 10 + i),
                "seed": seed + 10 + i})
        # ...plus a predict riding the SAME mid-kill burst: accepted
        # while the victim is dead, it must reach a terminal answer
        # (served if the base model commits first, REFUSED if it runs
        # before the commit — either is honest; losing it is not)
        serve.file_request(tmp, {
            "id": "fleet-p0", "kind": "predict", "tenant": "epsilon",
            "model": "fleet-4-base", "coords": [[0, 0, 0], [1, 1, 1]]})
        # kill-and-RESTART: a replacement joins under a fresh id (a
        # new incarnation — the dead id's leases must EXPIRE, not be
        # silently re-owned)
        restart = f"{victim}b"
        rids.append(restart)
        spawn(restart)
        # the update tenant needs its base model DONE first: the
        # journal/checkpoint store must hold the model to advance
        all_jobs = ["fleet-0-warm", "fleet-1-pin", "fleet-2-nan",
                    "fleet-3-clean", "fleet-4-base", "fleet-p0",
                    *batch_jobs]
        if wait_for(lambda: states().get("fleet-4-base",
                                         (None,))[0]
                    in serve.TERMINAL, 300, "the update base job"):
            # the predict stream around the update commit: p1 is
            # pinned at the base generation, the update advances it,
            # p2 files after the update — the journal staleness audit
            # must hold across the whole interleaving
            serve.file_request(tmp, {
                "id": "fleet-p1", "kind": "predict",
                "tenant": "epsilon", "model": "fleet-4-base",
                "coords": [[0, 0, 0], [1, 2, 3]]})
            serve.file_request(tmp, {
                "id": "fleet-5-up", "kind": "update",
                "base": "fleet-4-base", "tenant": "epsilon",
                "delta": {"dims": list(dims), "nnz": max(nnz // 20, 8),
                          "seed": seed + 99}})
            serve.file_request(tmp, {
                "id": "fleet-p2", "kind": "predict",
                "tenant": "epsilon", "model": "fleet-4-base",
                "top_k": {"fixed": {"1": 0, "2": 0}, "mode": 0,
                          "k": 3}})
            # only a FILED update is waited on: a base-job timeout is
            # its own (already recorded) violation, not a reason to
            # burn the final wait polling a job that never existed
            all_jobs += ["fleet-p1", "fleet-5-up", "fleet-p2"]
        wait_for(lambda: all(states().get(j, (None,))[0]
                             in serve.TERMINAL for j in all_jobs),
                 300 if smoke else 900, "all jobs to finish")
        # phase 5 — corrupt-model refusal drill (docs/predict.md):
        # shred the base model's checkpoint AND its .bak, drop both
        # generation stamps, then predict against it — the fenced read
        # finds no intact (checkpoint, stamp) pair and must REFUSE
        # classified, never serve garbage
        ckdir = os.path.join(tmp, "ckpt")
        for name in ("fleet-4-base.npz", "fleet-4-base.npz.bak"):
            fp = os.path.join(ckdir, name)
            if os.path.exists(fp):
                with open(fp, "wb") as f:
                    f.write(b"shredded by the chaos drill")
        for name in ("fleet-4-base.gen.json",
                     "fleet-4-base.gen.json.bak"):
            try:
                os.remove(os.path.join(ckdir, name))
            except FileNotFoundError:
                pass
        serve.file_request(tmp, {
            "id": "fleet-p3", "kind": "predict", "tenant": "epsilon",
            "model": "fleet-4-base", "coords": [[0, 0, 0]]})
        all_jobs.append("fleet-p3")
        wait_for(lambda: states().get("fleet-p3", (None,))[0]
                 in serve.TERMINAL, 180, "the corrupt-model predict")
    except Exception as e:  # the harness itself must not crash the CLI
        error = (f"{resilience.classify_failure(e).value}: "
                 f"{resilience.failure_message(e)[:300]}")
        violations.append(f"fleet-chaos harness error: {error}")
    finally:
        for rid, p in procs.items():
            if p.poll() is None:
                p.send_signal(_signal.SIGTERM)
        for p in procs.values():
            try:
                p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()

    recs, _torn = serve.Journal(jpath).replay()
    accepted = sorted({r["job"] for r in recs
                       if r.get("rec") == serve.ACCEPTED})
    adopted = sorted({r["job"] for r in recs
                      if r.get("rec") == serve.ADOPTED})
    # 1. zero accepted jobs lost
    for jid in accepted:
        last = states().get(jid, (None, None))
        res = serve.read_result(tmp, jid)
        if last[0] not in serve.TERMINAL:
            violations.append(f"accepted job {jid} never reached a "
                              f"terminal state — a job was LOST")
            jobs[jid] = "lost"
            continue
        if res is None:
            violations.append(f"job {jid} is terminal but published "
                              f"no result record")
            jobs[jid] = "no-result"
            continue
        jobs[jid] = res["status"]
    # 2. the failover actually happened, and lineage is single-owner
    if victim is not None:
        pin_last = states().get("fleet-1-pin", (None, None))
        if pin_last[1] == victim:
            violations.append(
                f"the pinned job's terminal record is on the killed "
                f"replica {victim} — the kill exercised no failover")
        if not any(r.get("rec") == serve.ADOPTED
                   and r.get("job") == "fleet-1-pin"
                   and r.get("from_replica") == victim for r in recs):
            violations.append(
                "no adopted record shows the pinned job taken over "
                "from the killed replica — adoption lineage missing")
    violations.extend(_fleet_lineage_violations(recs))
    violations.extend(_predict_staleness_violations(recs))
    # 3./4. per-job evidence: warm-cache affinity + tenant isolation
    for jid, status in sorted(jobs.items()):
        res = serve.read_result(tmp, jid)
        if res is None:
            continue
        kinds = {e["kind"] for e in res.get("events", [])}
        if jid.startswith("fleet-p"):
            # predicts answer "served" or an honest classified
            # "refused" — anything else (or a served answer with no
            # generation stamp) breaks the fence contract
            if status not in ("served", "refused"):
                violations.append(
                    f"predict {jid} finished {status!r} — a predict "
                    f"either serves or refuses, never fails open")
            elif status == "served" and not res.get("gen"):
                violations.append(
                    f"predict {jid} served with no generation stamp "
                    f"— the answer is unauditable")
            continue
        if jid == "fleet-2-nan":
            if status == "converged" \
                    and not kinds & {"health_rollback",
                                     "health_degraded"}:
                violations.append(
                    "the NaN job converged with no health evidence — "
                    "the injected fault was silently lost")
            if res.get("demotions"):
                violations.append(
                    "the NaN job demoted engines — NUMERICAL failures "
                    "must roll back, never demote")
        else:
            if kinds & {"health_nonfinite", "health_rollback",
                        "health_degraded"}:
                violations.append(
                    f"clean job {jid} carries health events — the NaN "
                    f"tenant leaked into a neighbor")
            if res.get("demotions"):
                violations.append(
                    f"clean job {jid} carries engine demotions — "
                    f"cross-tenant poisoning")
            if status != "converged":
                violations.append(
                    f"clean job {jid} finished {status!r} instead of "
                    f"converging")
        if jid == "fleet-1-pin":
            tune_info = res.get("tune") or {}
            affinity[jid] = dict(
                cache_hits=tune_info.get("cache_hits"),
                measured=tune_info.get("measured"),
                adopted_from=res.get("adopted_from"),
                replica=res.get("replica"))
            if not tune_info or not tune_info.get("cache_hits"):
                violations.append(
                    "the adopted same-regime job reports no warm "
                    "plan-cache hits — the Nth-request-is-free "
                    "property did not survive the failover")
            elif tune_info.get("measured"):
                violations.append(
                    f"the adopted job re-measured "
                    f"{tune_info['measured']} plans despite the warm "
                    f"shared cache")
    # 5. the adopter's metrics + trace account for the failover
    pin_replica = states().get("fleet-1-pin", (None, None))[1]
    if pin_replica and pin_replica != victim:
        mpath = os.path.join(tmp, "fleet", "metrics",
                             f"{pin_replica}.prom")
        try:
            with open(mpath) as f:
                mtext = f.read()
            if "splatt_fleet_adoptions_total" not in mtext:
                violations.append(
                    f"the adopter {pin_replica}'s Prometheus snapshot "
                    f"carries no splatt_fleet_adoptions_total sample "
                    f"— the failover is unaccounted")
        except OSError as e:
            violations.append(f"no metrics snapshot from the adopter "
                              f"{pin_replica}: {e}")
        tpath = os.path.join(tmp, f"trace-{pin_replica}.json")
        try:
            summ = trace.summarize(trace.load_trace(tpath))
            fl = summ.get("fleet") or {}
            if not fl.get("adoptions"):
                violations.append(
                    f"the adopter {pin_replica}'s span trace carries "
                    f"no job_adopted point event — the failover left "
                    f"no trace evidence")
        except (OSError, ValueError) as e:
            violations.append(f"no loadable span trace from the "
                              f"adopter {pin_replica}: {e}")
    # 6. the fleet observability plane shows the kill end-to-end
    # (docs/observability.md): merged aggregate + SLO burn/recovery +
    # the victim's flight-recorder black box + status↔journal agreement
    from splatt_tpu import fleetobs

    agg = fleetobs.aggregate(tmp)
    observability: Dict[str, float] = {
        "adoptions": agg.counter("splatt_fleet_adoptions_total"),
        "lease_expired": agg.counter(
            "splatt_fleet_lease_expired_total"),
        "slo_burns": agg.counter("splatt_slo_burn_total"),
        "replicas_dead": float(agg.samples.get(
            ("splatt_fleet_replicas", (("state", "dead"),)), 0.0)),
    }
    if victim is not None:
        if observability["adoptions"] < 1:
            violations.append(
                "the merged fleet aggregate counts no "
                "splatt_fleet_adoptions_total — the failover is "
                "invisible fleet-wide")
        if observability["lease_expired"] < 1:
            violations.append(
                "the merged fleet aggregate counts no "
                "splatt_fleet_lease_expired_total — the lease expiry "
                "is invisible fleet-wide")
        if observability["replicas_dead"] < 1:
            violations.append(
                "the liveness census counts no dead replica — the "
                "SIGKILLed victim's expired heartbeat went uncounted")
        if observability["slo_burns"] < 1:
            violations.append(
                "no slo_burn was counted anywhere in the fleet — the "
                "adoption outage burned no error budget, so a real "
                "incident would page nobody")
        else:
            # ...and the burn RECOVERS: a fresh two-point evaluation
            # over the now-quiet fleet (identical samples = zero new
            # errors in the window) must not be burning
            ev = fleetobs.SloEvaluator(window_s=3.0, long_windows=4,
                                       burn=1.5)
            t0 = time.time()
            ev.evaluate(agg.samples, now=t0)
            res2 = ev.evaluate(agg.samples, now=t0 + 60.0)
            still = [n for n, s in res2["slos"].items()
                     if s["burning"]]
            if still:
                violations.append(
                    f"SLOs {still} still burning over a quiet window "
                    f"— the burn evaluator cannot recover")
        # the victim's black box: its flight ring must replay the
        # timeline up to the kill, the pinned job's liveness mark
        # included (SPLATT_FLIGHT_FLUSH=1 makes every record durable
        # before the 0.5s kill window)
        fpath = os.path.join(tmp, "fleet", "flight",
                             f"{victim}.jsonl")
        try:
            fevs = trace.load_flight(fpath)
            observability["flight_events"] = float(len(fevs))
            if not any((e.get("args") or {}).get("job")
                       == "fleet-1-pin" and e.get("name")
                       == "job_started" for e in fevs):
                violations.append(
                    "the victim's flight ring carries no job_started "
                    "mark for the pinned job — the black box does "
                    "not show what the victim was running when killed")
        except (OSError, ValueError) as e:
            violations.append(
                f"the victim {victim}'s flight ring is unreadable — "
                f"the SIGKILL erased the black box: {e}")
    # 7. batched + update tenant mix (docs/batched.md): the lineage
    # audit above already proves no batch member double-ran or double-
    # committed; here the batch/update evidence itself is checked.
    # (Spool-claim races can split the batched set across replicas, so
    # achieved coalescing coverage is recorded — and required of the
    # full-size soak, where the burst lands on the lone survivor.)
    batched_jobs = 0
    for jid in accepted:
        res = serve.read_result(tmp, jid)
        if res and res.get("batched"):
            batched_jobs += 1
            if res["batched"].get("k", 0) < 2:
                violations.append(
                    f"job {jid} claims a coalesced batch of "
                    f"k={res['batched'].get('k')} — a batch is >= 2")
    observability["batched_jobs"] = float(batched_jobs)
    if not smoke and batched_jobs < 2:
        violations.append(
            "no coalesced batch formed in the full soak — the batched "
            "tenant mix exercised nothing")
    if "fleet-5-up" in accepted:
        up = serve.read_result(tmp, "fleet-5-up")
        if up is not None:
            kinds = {e["kind"] for e in up.get("events", [])}
            if not kinds & {"update_applied", "refit_scheduled"}:
                violations.append(
                    "the update job left no update_applied/"
                    "refit_scheduled evidence — the model-store "
                    "lineage is unauditable")
            if not os.path.exists(os.path.join(
                    tmp, "ckpt", "fleet-4-base.npz")):
                violations.append(
                    "the update base model checkpoint is missing from "
                    "the store after the update committed")
    # 8. the generation-fenced predict plane (docs/predict.md): the
    # staleness audit above already walked the journal; here the
    # predict stream's coverage and refusal honesty are checked
    served = refused = 0
    for jid in accepted:
        if not jid.startswith("fleet-p"):
            continue
        res = serve.read_result(tmp, jid)
        if res and res.get("status") == "served":
            served += 1
        elif res and res.get("status") == "refused":
            refused += 1
    observability["predicts_served"] = float(served)
    observability["predicts_refused"] = float(refused)
    observability["predict_latency_obs"] = float(sum(
        int(v.get("count", 0)) for (n, _lk), v in agg.samples.items()
        if n == "splatt_predict_latency_seconds"
        and isinstance(v, dict)))
    if "fleet-p1" in accepted and served < 1:
        violations.append(
            "no predict was served across the kill despite a "
            "committed base model — the prediction plane never "
            "answered")
    if "fleet-p3" in accepted:
        p3 = serve.read_result(tmp, "fleet-p3")
        if p3 is None or p3.get("status") != "refused":
            violations.append(
                f"the corrupt-model predict finished "
                f"{(p3 or {}).get('status')!r} instead of refusing — "
                f"a torn model must REFUSE, never serve garbage")
    st = fleetobs.fleet_status(tmp)
    jstates = states()
    for jid in accepted:
        if st["jobs"].get(jid) != jstates.get(jid, (None,))[0]:
            violations.append(
                f"splatt status disagrees with the journal about "
                f"{jid}: {st['jobs'].get(jid)!r} vs "
                f"{jstates.get(jid, (None,))[0]!r}")
    verdict = "violated" if violations else "survived"
    return FleetChaosResult(verdict=verdict, jobs=jobs, replicas=rids,
                            victim=victim, adopted=adopted,
                            affinity=affinity, violations=violations,
                            error=error, observability=observability,
                            crash_windows=crash_windows)


def format_fleet_report(res: FleetChaosResult) -> List[str]:
    """Human-readable fleet-soak verdict lines for the CLI."""
    lines = [f"fleet chaos: replicas {', '.join(res.replicas)}; "
             f"SIGKILLed {res.victim or '(nobody)'}; adopted: "
             f"{', '.join(res.adopted) or '(none)'}"]
    for jid, status in sorted(res.jobs.items()):
        lines.append(f"  job {jid}: {status}")
    for jid, ev in sorted(res.affinity.items()):
        lines.append(f"  affinity {jid}: cache_hits={ev['cache_hits']} "
                     f"measured={ev['measured']} "
                     f"adopted_from={ev['adopted_from']} "
                     f"ran_on={ev['replica']}")
    if res.observability:
        ob = res.observability
        lines.append(
            f"  observability: adoptions={ob.get('adoptions', 0):g} "
            f"lease_expired={ob.get('lease_expired', 0):g} "
            f"slo_burns={ob.get('slo_burns', 0):g} "
            f"dead_replicas={ob.get('replicas_dead', 0):g} "
            f"victim_flight_events={ob.get('flight_events', 0):g}")
        lines.append(
            f"  predict plane: served={ob.get('predicts_served', 0):g} "
            f"refused={ob.get('predicts_refused', 0):g} "
            f"latency_obs={ob.get('predict_latency_obs', 0):g}")
    for v in res.violations:
        lines.append(f"INVARIANT VIOLATED: {v}")
    lines.append(f"fleet chaos verdict: {res.verdict.upper()}")
    return lines


def format_serve_report(res: ServeChaosResult) -> List[str]:
    """Human-readable serve-soak verdict lines for the CLI."""
    lines = [f"serve chaos: SIGKILL mid-queue "
             f"{'landed' if res.killed_mid_queue else 'MISSED'}; "
             f"resumed after restart: "
             f"{', '.join(res.resumed) or '(none)'}"]
    for jid, status in sorted(res.jobs.items()):
        lines.append(f"  job {jid}: {status}")
    for v in res.violations:
        lines.append(f"INVARIANT VIOLATED: {v}")
    lines.append(f"serve chaos verdict: {res.verdict.upper()}")
    return lines


# -- ingest soak (docs/ingest.md) -------------------------------------------
#
# The unit tests prove each ingest pillar in isolation; the soak proves
# the one that only a REAL kill can: SIGKILL a `splatt ingest`
# subprocess mid-stream, restart it, and audit the chunk journal ALONE
# for the exactly-once invariant — zero records lost, zero duplicated,
# every quarantined record accounted, the final tensor byte-exact with
# what an uninterrupted run would have built.

@dataclasses.dataclass
class IngestChaosResult:
    """One ingest kill-and-resume soak's verdict and evidence."""

    verdict: str                  # "survived" | "violated"
    killed_mid_stream: bool       # the SIGKILL landed before finalize
    watermark_at_kill: int        # journal watermark at the post-mortem
    chunks: int                   # chunks committed end-to-end
    nnz: int                      # nonzeros in the finalized tensor
    quarantined: int              # records quarantined end-to-end
    resumed: bool                 # the restart reported a journal resume
    violations: List[str]         # invariant breaches (empty = pass)
    error: Optional[str] = None
    #: which durable-op crash windows the SIGKILL actually landed in
    #: (crash-point checker vocabulary, tools/splint/crashpoint.py —
    #: the ingest_chunk_commit protocol's windows)
    crash_windows: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _ingest_crash_windows(dest: str) -> List[str]:
    """Classify the ingest directory's post-kill state into the
    durable-op crash windows the kill evidently landed in (same
    vocabulary as the crash-point checker's ``ingest_chunk_commit``
    protocol).  Conservative: only unambiguous debris counts."""
    windows = set()
    jpath = os.path.join(dest, "journal.jsonl")
    try:
        with open(jpath, "rb") as f:
            data = f.read()
    except OSError:
        data = b""
    if data:
        if not data.endswith(b"\n"):
            windows.add("journal.append.torn")
        import json as _json

        for ln in data.split(b"\n"):
            if not ln.strip():
                continue
            try:
                kind = _json.loads(ln).get("rec")
            except ValueError:
                continue
            if kind:
                windows.add(f"journal.append[{kind}]")
    for dirpath, _dirs, names in os.walk(dest):
        base = os.path.basename(dirpath)
        for name in names:
            if ".tmp" not in name and ".build" not in name:
                continue
            if base == "seg":
                windows.add("ingest.seg.publish")
            elif base == "vocab":
                windows.add("ingest.vocab.publish")
            elif "tensor.bin" in name:
                windows.add("ingest.bin.publish")
    return sorted(windows)


def run_ingest_chaos(seed: int = 0, smoke: bool = True,
                     verbose: bool = False) -> IngestChaosResult:
    """Kill-and-resume soak of the streaming ingest plane
    (docs/ingest.md).

    Generates a seeded record stream — string keys in mode 0 (the
    vocab store is in the blast radius) and a deterministic sprinkle
    of malformed records (the quarantine sidecar too) — then:

    1. runs a REAL ``splatt ingest`` subprocess with a slow fault
       armed at ``ingest.commit`` so each chunk commit dawdles and the
       kill window is deterministic;
    2. SIGKILLs it once the journal shows >= 2 committed chunks
       (mid-stream, no drain, no cleanup);
    3. audits the surviving journal ALONE (``ingest.audit_journal``):
       every journaled chunk's segment/vocab intact under its recorded
       sha, no watermark gaps, sidecar accounting covered;
    4. restarts the same command unfaulted and checks it RESUMES from
       the watermark and converges;
    5. checks end-to-end exactly-once accounting against the
       generator's ground truth: records seen == lines written, nnz ==
       good records, quarantined == malformed records, and the
       finalized ``tensor.bin`` loads with exactly that nnz.
    """
    import json
    import subprocess
    import sys
    import tempfile
    import time

    from splatt_tpu import ingest, resilience

    chunk_records = 120 if smoke else 1000
    nchunks_target = 12 if smoke else 40
    violations: List[str] = []
    crash_windows: List[str] = []
    killed = False
    watermark_at_kill = -1
    resumed = False
    chunks = nnz = quarantined = 0
    error = None
    tmp = tempfile.mkdtemp(prefix="splatt-ingest-chaos-")
    src = os.path.join(tmp, "stream.tns")
    dest = os.path.join(tmp, "ingest")

    # seeded ground truth: every 23rd line malformed (bad arity), the
    # rest "u<k> <i> <j> <val>" — string keys force the vocab path
    rng = np.random.default_rng(seed)
    total = chunk_records * nchunks_target
    good = bad = 0
    with open(src, "w") as f:
        f.write("# ingest soak stream\n")
        for n in range(total):
            if n and n % 23 == 0:
                f.write("malformed\n")
                bad += 1
            else:
                f.write(f"u{rng.integers(0, 500)} "
                        f"{rng.integers(0, 64)} {rng.integers(0, 48)} "
                        f"{rng.random() + 0.1:.6f}\n")
                good += 1

    cmd = [sys.executable, "-m", "splatt_tpu.cli", "ingest", src, dest,
           "--format", "tns", "--chunk", str(chunk_records), "--json"]
    # splint: ignore[SPL001] forwarding the whole environment to the
    # ingest subprocess, not reading config — no single ENV_VARS name
    env = dict(os.environ)
    env["SPLATT_FAULTS"] = "ingest.commit:slow:delay=0.25:*"
    try:
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)
        deadline = time.time() + 180
        while time.time() < deadline and proc.poll() is None:
            recs, _torn = ingest.replay_journal(dest)
            if sum(1 for r in recs
                   if r.get("rec") == ingest.REC_CHUNK) >= 2:
                break
            time.sleep(0.05)
        if proc.poll() is None:
            proc.kill()      # SIGKILL: no drain, no cleanup
            killed = True
        else:
            violations.append(
                "ingest finished (or died) before the kill — the soak "
                "did not exercise a mid-stream resume")
        proc.wait(timeout=60)

        # post-mortem, BEFORE the restart heals anything
        crash_windows = _ingest_crash_windows(dest)
        resilience.run_report().add(
            "crash_windows_exercised", soak="ingest",
            windows=",".join(crash_windows))
        aud = ingest.audit_journal(dest)
        watermark_at_kill = aud["watermark"]
        if not aud["ok"]:
            violations.append(
                f"journal audit after the SIGKILL found "
                f"{len(aud['violations'])} exactly-once violation(s): "
                f"{'; '.join(aud['violations'][:3])}")

        # the resume leg: same command, faults disarmed
        env.pop("SPLATT_FAULTS", None)
        restart = subprocess.run(cmd, env=env, capture_output=True,
                                 text=True, timeout=600)
        if restart.returncode != 0:
            violations.append(
                f"restarted ingest exited nonzero "
                f"({restart.returncode}): {restart.stdout[-300:]}")
        summary = None
        for line in reversed(restart.stdout.splitlines()):
            if line.startswith("{"):
                try:
                    summary = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
        if summary is None:
            violations.append("restarted ingest printed no JSON "
                              "summary — accounting unauditable")
        else:
            resumed = bool(summary.get("resumed"))
            chunks = int(summary.get("chunks") or 0)
            nnz = int(summary.get("nnz") or 0)
            quarantined = int(summary.get("quarantined") or 0)
            if summary.get("status") != "converged":
                violations.append(
                    f"restarted ingest finished "
                    f"{summary.get('status')!r} instead of converging")
            if killed and watermark_at_kill >= 0 and not resumed:
                violations.append(
                    "the kill landed mid-stream but the restart did "
                    "not resume from the journal watermark")
            for name, got, want in (
                    ("records", summary.get("records"), good + bad),
                    ("nnz", nnz, good),
                    ("quarantined", quarantined, bad)):
                if got != want:
                    violations.append(
                        f"end-to-end {name} accounted {got}, ground "
                        f"truth is {want} — records were LOST or "
                        f"DUPLICATED across the kill")

        aud2 = ingest.audit_journal(dest)
        if not aud2["ok"]:
            violations.append(
                f"final journal audit found violations: "
                f"{'; '.join(aud2['violations'][:3])}")
        elif not aud2["finalized"]:
            violations.append("the journal carries no finalize record "
                              "after a converged run")
        from splatt_tpu import io as _io

        binp = os.path.join(dest, "tensor.bin")
        try:
            tt = _io.load_memmap(binp)
            if tt.nnz != good:
                violations.append(
                    f"finalized tensor holds {tt.nnz} nnz, ground "
                    f"truth is {good}")
        except (OSError, ValueError) as e:
            violations.append(f"finalized tensor.bin unloadable: {e}")
    except Exception as e:  # the harness itself must not crash the CLI
        error = (f"{resilience.classify_failure(e).value}: "
                 f"{resilience.failure_message(e)[:300]}")
        violations.append(f"ingest-chaos harness error: {error}")
    verdict = "violated" if violations else "survived"
    return IngestChaosResult(verdict=verdict, killed_mid_stream=killed,
                             watermark_at_kill=watermark_at_kill,
                             chunks=chunks, nnz=nnz,
                             quarantined=quarantined, resumed=resumed,
                             violations=violations, error=error,
                             crash_windows=crash_windows)


def format_ingest_report(res: IngestChaosResult) -> List[str]:
    """Human-readable ingest-soak verdict lines for the CLI."""
    lines = [f"ingest chaos: SIGKILL mid-stream "
             f"{'landed' if res.killed_mid_stream else 'MISSED'} at "
             f"watermark {res.watermark_at_kill}; resume "
             f"{'replayed the journal' if res.resumed else 'MISSING'}",
             f"  end-to-end: {res.chunks} chunk(s), {res.nnz} nnz, "
             f"{res.quarantined} quarantined",
             f"  crash windows exercised: "
             f"{', '.join(res.crash_windows) or '(none)'}"]
    for v in res.violations:
        lines.append(f"INVARIANT VIOLATED: {v}")
    lines.append(f"ingest chaos verdict: {res.verdict.upper()}")
    return lines


def format_report(res: ChaosResult) -> List[str]:
    """Human-readable chaos verdict lines for the CLI."""
    lines = [f"chaos schedule: {res.schedule}",
             f"faults fired: " + (", ".join(
                 f"{s}x{n}" for s, n in sorted(res.fired.items()) if n)
                 or "(none)")]
    from splatt_tpu import resilience

    lines += ["run report:"] + (resilience.run_report().summary()
                                or ["  (no resilience events)"])
    if res.fit is not None:
        lines.append(f"final fit: {res.fit:0.5f} "
                     f"({'finite' if res.finite else 'NON-FINITE'})")
    for v in res.violations:
        lines.append(f"INVARIANT VIOLATED: {v}")
    lines.append(f"chaos verdict: {res.verdict.upper()}")
    return lines
