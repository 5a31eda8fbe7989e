"""Resilience layer: failure taxonomy, retries, demotions, run report.

Failure HANDLING matters as much as speed: one transient compile 500
used to be persisted as a permanent "compile_failed" verdict, demoting the flagship Pallas engine for every future session.
Production tensor-decomposition stacks (GenTen's performance-portable
MTTKRP; the emerging-architectures survey) keep multiple backends live
so one backend's failure degrades, not kills, the run.  This module is
the single place that decides what a failure MEANS:

Failure taxonomy
    :func:`classify_failure` sorts probe/compile/runtime errors into

    - ``DETERMINISTIC`` — a proven kernel-compiler rejection (Mosaic
      signatures).  Safe to persist: the same sources on the same
      device will always fail.
    - ``TRANSIENT``     — a compile or runtime service hiccuping
      (HTTP 5xx, bare ``INTERNAL:``, ``UNAVAILABLE``, resets,
      timeouts).  Retried with capped exponential backoff + jitter,
      NEVER persisted.
    - ``RESOURCE``      — capacity, not capability (OOM / VMEM
      exhaustion).  Demotes the engine for this shape only.
    - ``UNKNOWN``       — anything unrecognized.  Treated like
      transient for persistence purposes (rejected this session,
      re-probed next process) but not retried in-place.
    - ``NUMERICAL``     — non-finite factors/λ/fit caught by the
      numerical-health sentinel (docs/guarded-als.md).  Handled by
      rollback + re-conditioning in the ALS drivers, never by the
      engine-demotion registry.
    - ``TIMEOUT``       — our own deadline watchdog (:func:`deadline`)
      blew on a host-side compile/measure/probe call.  Demotes
      per-shape exactly like RESOURCE.

Deadline watchdog
    :func:`deadline` — a thread-timer context manager bounding
    host-side compile/measure/probe calls (probe compiles, tuner
    measurements, first-call engine compiles); configured via
    ``SPLATT_DEADLINE_S`` / :func:`set_deadline`, fault-injectable via
    the ``slow`` kind (utils/faults.py).

Engine demotion registry
    :func:`demote_engine` / :func:`is_demoted` — runtime failures of a
    dispatch engine demote it (process-wide, or per-shape for RESOURCE
    and TIMEOUT failures) so the ordered fallback chain in
    :func:`splatt_tpu.ops.mttkrp.engine_chain` skips it mid-run instead
    of crashing ``cpd_als``.

Run report
    :func:`run_report` — an append-only event log (demotions, probe
    retries, checkpoint recoveries) the CLI prints at the end of a run,
    so silent degradation is observable (≙ the reference's stats
    reporting philosophy, src/stats.c).

Per-job scoping (docs/serve.md)
    All of the mutable state above — the demotion table, the
    last-attempt note, the run report, plus overrides for the
    health-retry budget and the deadline watchdog — lives in a
    :class:`ResilienceScope`.  Outside any scope the process-global
    scope applies (single-run CLI behavior, unchanged); the serve
    daemon wraps each supervised job in :func:`scope`, a contextvars-
    backed context manager, so one tenant's NUMERICAL rollback or OOM
    demotion is attributed to (and contained within) that job while the
    probe/tune/compile caches stay shared and warm across jobs (≙ the
    reference's per-run ``splatt_opts``/workspace separation).

Nothing here imports jax: classification is pure string logic so the
fault-injection tests exercise every branch without a device.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import enum
import random
import threading
import time
from typing import Callable, Dict, List, Optional


class FailureClass(enum.Enum):
    """What a probe/compile/runtime failure means for future dispatch."""

    DETERMINISTIC = "deterministic"   # persist: will always fail here
    TRANSIENT = "transient"           # retry w/ backoff; never persist
    RESOURCE = "resource"             # demote for this shape only
    UNKNOWN = "unknown"               # unproven; re-probe next process
    NUMERICAL = "numerical"           # non-finite factors/fit: roll back
    TIMEOUT = "timeout"               # our own deadline watchdog blew:
                                      # demote per-shape, like RESOURCE


# Capacity failures first: an OOM message may also mention the kernel
# compiler ("Mosaic ... scoped vmem limit exceeded"), and the right
# verdict there is shape-scoped demotion, not a permanent rejection.
RESOURCE_MARKERS = (
    "RESOURCE_EXHAUSTED", "Out of memory", "out of memory", "OOM",
    "vmem limit", "VMEM limit", "scoped vmem", "exceeds the limit",
    "Attempting to allocate", "Attempting to reserve",
)

# Deterministic Mosaic/kernel-compiler rejection signatures — the ONLY
# class that may be persisted as "compile_failed" (a persisted
# misclassification demotes the flagship engine for every future
# session, so this is a whitelist, not a transient-error blocklist).
# 'HTTP code 500' and bare 'INTERNAL: ' were deliberately REMOVED from
# this set (ADVICE.md round 5): they are classic transient service
# failures and live in TRANSIENT_MARKERS below.
DETERMINISTIC_MARKERS = (
    "Mosaic", "mosaic", "Internal TPU kernel compiler",
    "Invalid input layout", "Unsupported lowering",
    "not implemented", "NotImplementedError",
    # io.py/ingest.py deliberate refusals: a torn .bin, a ragged text
    # tensor, or a corrupt ingest journal is content-deterministic —
    # retrying the same bytes reproduces the same refusal
    "truncated or torn", "ragged row", "bad token",
)

# Transient compile / runtime service failures: retried with
# backoff, rejected only for this attempt window, never persisted.
TRANSIENT_MARKERS = (
    "HTTP code 500", "HTTP code 502", "HTTP code 503", "HTTP code 504",
    "INTERNAL: ", "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED",
    "Connection reset", "Connection refused", "Socket closed",
    "Broken pipe", "timed out", "TimeoutError",
    "temporarily unavailable", "Transient",
)

# Our OWN watchdog's signature (resilience.deadline), checked before
# everything else: "DEADLINE_EXCEEDED"/"timed out" above are RPC-level
# transients worth retrying, but a deadline WE set and blew is a local
# capacity verdict for this shape — retrying the same slow compile
# would burn the budget again, so it demotes per-shape like OOM.
TIMEOUT_MARKERS = ("splatt deadline blown",)

# The health sentinel's signature (non-finite factors/λ/fit).  Never an
# engine-capability statement: rollback + re-conditioning owns it, not
# the demotion registry (docs/guarded-als.md).
NUMERICAL_MARKERS = ("non-finite", "NonFinite", "NumericalHealthError")


class DeadlineExceeded(RuntimeError):
    """The deadline watchdog (:func:`deadline`) blew on a host-side
    compile/measure/probe call.  Classifies as TIMEOUT: demoted
    per-shape like a RESOURCE failure — the same shapes will be slow
    again, other shapes are unindicted."""


class NumericalHealthError(RuntimeError):
    """The numerical-health sentinel found non-finite factors/λ/fit in
    a sweep's outputs (docs/guarded-als.md).  Classifies as NUMERICAL:
    handled by rollback + re-conditioning in the ALS drivers, never by
    the engine-demotion registry."""


def failure_message(exc) -> str:
    """The string classification runs on: "ExcType: message"."""
    if isinstance(exc, str):
        return exc
    return f"{type(exc).__name__}: {exc}"


def classify_failure(exc) -> FailureClass:
    """Classify a probe/compile/runtime error (exception or message).

    Order matters: RESOURCE outranks DETERMINISTIC (a Mosaic VMEM
    message is capacity, not capability), and DETERMINISTIC outranks
    TRANSIENT — "INTERNAL: Mosaic failed ..." carries a real compiler
    signature, so the transient 'INTERNAL: ' prefix must not launder it
    into a retry loop (ADVICE.md: bare 500/INTERNAL are transient
    UNLESS they co-occur with a Mosaic/kernel-compiler marker).
    """
    msg = failure_message(exc)
    # the two project-raised classes first: their markers are exact and
    # their messages may echo infrastructure noise (a blown deadline
    # message quoting 'timed out' must not become a retry loop)
    if isinstance(exc, DeadlineExceeded) \
            or any(m in msg for m in TIMEOUT_MARKERS):
        return FailureClass.TIMEOUT
    if isinstance(exc, NumericalHealthError) \
            or any(m in msg for m in NUMERICAL_MARKERS):
        return FailureClass.NUMERICAL
    if any(m in msg for m in RESOURCE_MARKERS):
        return FailureClass.RESOURCE
    if any(m in msg for m in DETERMINISTIC_MARKERS):
        return FailureClass.DETERMINISTIC
    if any(m in msg for m in TRANSIENT_MARKERS):
        return FailureClass.TRANSIENT
    return FailureClass.UNKNOWN


# -- transient retry --------------------------------------------------------

#: default retry budget for transient failures.  Small and capped: a
#: wedged service must degrade the session in bounded time.
TRANSIENT_RETRIES = 3
BACKOFF_BASE_S = 0.5
BACKOFF_CAP_S = 8.0


def retry_transient(fn: Callable, attempts: int = None,
                    base: float = BACKOFF_BASE_S,
                    cap: float = BACKOFF_CAP_S,
                    sleep: Optional[Callable] = None,
                    rng: Optional[Callable] = None,
                    label: str = "") -> object:
    """Run `fn`, retrying ONLY transient failures with capped
    exponential backoff + full jitter (delay ~ U(0, min(cap, base·2^a))
    — the decorrelated pattern that avoids thundering-herd re-compiles
    against a shared service).  Deterministic / resource / unknown
    failures propagate immediately: retrying a proven rejection wastes
    the chip window.  `sleep`/`rng` are injectable for tests.
    """
    if attempts is None:
        attempts = TRANSIENT_RETRIES
    if sleep is None:
        sleep = time.sleep
    if rng is None:
        rng = random.random
    last = None
    for a in range(max(attempts, 1)):
        try:
            return fn()
        except Exception as e:
            last = e
            if (classify_failure(e) is not FailureClass.TRANSIENT
                    or a == attempts - 1):
                raise
            delay = min(cap, base * (2 ** a)) * rng()
            run_report().add("transient_retry", label=label,
                             attempt=a + 1, delay_s=round(delay, 3),
                             error=failure_message(e)[:200])
            sleep(delay)
    raise last  # pragma: no cover — loop always returns or raises


# -- engine demotion registry -----------------------------------------------

@dataclasses.dataclass
class Demotion:
    """One runtime engine demotion: which engine, why, and its scope
    (shape_key=None means process-wide; otherwise this shape only)."""

    engine: str
    failure_class: FailureClass
    error: str
    shape_key: Optional[str] = None
    ts: float = dataclasses.field(default_factory=time.time)


def _demotion_key(engine: str, shape_key: Optional[str]) -> str:
    return engine if shape_key is None else f"{engine}@{shape_key}"


def demote_engine(engine: str, error, shape_key: Optional[str] = None
                  ) -> Demotion:
    """Record a runtime demotion of `engine`; the fallback chain skips
    it from now on.  RESOURCE and TIMEOUT failures demote per-shape
    (pass the shape_key — an OOM or a blown compile deadline indicts
    only shapes of that size); everything else process-wide.  Never
    persisted to disk: a demotion lasts one process — the probe cache
    owns cross-process verdicts with its own (stricter) persistence
    rules.  Inside a :func:`scope` the demotion is confined to that
    job: one tenant's OOM must not steer its neighbors' dispatch."""
    cls = classify_failure(error)
    if cls not in (FailureClass.RESOURCE, FailureClass.TIMEOUT):
        shape_key = None
    d = Demotion(engine=engine, failure_class=cls,
                 error=failure_message(error)[:500], shape_key=shape_key)
    _state().demoted[_demotion_key(engine, shape_key)] = d
    run_report().add("engine_demotion", engine=engine,
                     failure_class=cls.value, shape_key=shape_key,
                     error=d.error[:200])
    return d


def is_demoted(engine: str, shape_key: Optional[str] = None) -> bool:
    """Whether `engine` was demoted in the current scope (process-wide
    outside any :func:`scope`), or for this shape."""
    demoted = _state().demoted
    if engine in demoted:
        return True
    return (shape_key is not None
            and _demotion_key(engine, shape_key) in demoted)


def demotions() -> List[Demotion]:
    return list(_state().demoted.values())


def reset_demotions() -> None:
    """Clear the current scope's runtime demotions (tests; a fresh run
    in one process)."""
    _state().demoted.clear()


# -- last-attempt tracking --------------------------------------------------
#
# Failures on accelerators can surface ASYNCHRONOUSLY — not at the
# mttkrp_blocked call that picked the engine, but at the next host sync
# inside the sweep.  The dispatch layer notes which engine it handed
# work to; the driver-level handler (cpd_als) uses it to demote the
# right engine when an exception arrives with no call-site context.
# Scope-local: two concurrent jobs' dispatches must not cross-attribute.


def note_engine_attempt(engine: str, shape_key: Optional[str] = None
                        ) -> None:
    _state().last_attempt = (engine, shape_key)


def last_engine_attempt() -> Optional[tuple]:
    """(engine, shape_key) of the current scope's most recent dispatch,
    or None."""
    return _state().last_attempt


# -- engine fallback switch -------------------------------------------------

_FALLBACK_ENV = "SPLATT_ENGINE_FALLBACK"
_fallback_override: Optional[bool] = None


def fallback_enabled() -> bool:
    """Whether runtime engine fallback is on (default yes).  CLI
    --engine-fallback off / SPLATT_ENGINE_FALLBACK=0 disable it — a
    differential test chasing a kernel bug wants the crash, not the
    silent rescue."""
    if _fallback_override is not None:
        return _fallback_override
    from splatt_tpu.utils.env import read_env

    return str(read_env(_FALLBACK_ENV)).lower() not in (
        "0", "off", "false", "no")


def set_fallback(enabled: Optional[bool]) -> None:
    """Process-wide override (None restores the env default)."""
    global _fallback_override
    _fallback_override = enabled


# -- deadline watchdog ------------------------------------------------------
#
# A pathological shape can hang a compile or a measurement long past
# any useful deadline.  The watchdog bounds
# host-side compile/measure/probe calls with a plain threading.Timer —
# no signals (they do not compose with jax's own handlers or with
# non-main threads), and jit-safe because it only ever wraps HOST-side
# work: inside a trace it wraps tracing time, which is bounded anyway.

_DEADLINE_ENV = "SPLATT_DEADLINE_S"
_deadline_override: Optional[float] = None


def set_deadline(seconds: Optional[float]) -> None:
    """Process-wide deadline override for :func:`deadline` sites (None
    restores the env default; <= 0 disables the optional sites even
    when SPLATT_DEADLINE_S is exported — sites with their own default,
    like the probe, keep it).  The chaos harness uses this instead of
    mutating the environment."""
    global _deadline_override
    _deadline_override = seconds


def deadline_seconds(default: Optional[float] = None) -> Optional[float]:
    """The configured watchdog deadline: the current job scope's
    override if set (serve gives each job its own budget), else the
    process override (<= 0 meaning "disabled" — the caller's `default`
    still applies, so the probe's always-on 240 s survives an explicit
    disable), else SPLATT_DEADLINE_S, else `default`.  None = disabled.
    """
    sc = _SCOPE.get()
    if sc is not None and sc.deadline_s is not None:
        if sc.deadline_s > 0:
            return sc.deadline_s
        return default
    if _deadline_override is not None:
        if _deadline_override > 0:
            return _deadline_override
        return default
    from splatt_tpu.utils.env import read_env_float

    env = read_env_float(_DEADLINE_ENV)
    if env is not None and float(env) > 0:
        return float(env)
    return default


@contextlib.contextmanager
def deadline(site: str, seconds: Optional[float] = None):
    """Bound a host-side compile/measure/probe call: if the wrapped
    block runs longer than `seconds` (default: the configured
    :func:`deadline_seconds`), raise :class:`DeadlineExceeded`
    (→ TIMEOUT: demoted per-shape exactly like OOM) and record a
    ``deadline_blown`` run-report event.

    Mechanics: a daemon ``threading.Timer`` fires after `seconds`.
    From the MAIN thread it additionally calls
    ``_thread.interrupt_main()`` so a blocked Python-level call is
    interrupted between bytecodes (a call hung inside C that never
    releases the GIL still gets the after-the-fact raise when it
    returns); from any other thread the blown deadline raises when the
    block completes.  Either way the failure is classified the same —
    the watchdog's job is converting "slow" into a *classified* error
    instead of an unbounded hang.
    """
    if seconds is None:
        seconds = deadline_seconds()
    if not seconds or seconds <= 0:
        yield
        return
    state = {"fired": False, "done": False}
    lock = threading.Lock()
    on_main = threading.current_thread() is threading.main_thread()

    def fire():
        # fired-flag and interrupt are one critical section: once the
        # main thread observes fired=True (it reads under this lock's
        # ordering in the finally below), the interrupt is already
        # pending, so the absorb sleep deterministically receives it —
        # no window where a stray KeyboardInterrupt can outlive the
        # context manager and kill a later, unguarded sweep
        with lock:
            if state["done"]:
                return
            state["fired"] = True
            if on_main:
                import _thread

                _thread.interrupt_main()

    # guard work is explicitly attributed (docs/observability.md):
    # arming the watchdog gets its own span so ROADMAP open item 1's
    # "are the guards taxing the hot loop?" is a trace query
    from splatt_tpu import trace

    with trace.span("guard.deadline.arm", site=site,
                    seconds=float(seconds)):
        timer = threading.Timer(seconds, fire)
        timer.daemon = True
        timer.start()

    def blew() -> "DeadlineExceeded":
        run_report().add("deadline_blown", site=site,
                         seconds=float(seconds))
        return DeadlineExceeded(
            f"splatt deadline blown at {site} after {seconds:g}s "
            f"(host-side call exceeded the watchdog budget)")

    try:
        try:
            yield
        finally:
            with trace.span("guard.deadline.disarm", site=site):
                with lock:
                    state["done"] = True
                timer.cancel()
                if state["fired"] and on_main:
                    # the timer fired (possibly while we were already
                    # exiting): absorb the pending interrupt_main HERE,
                    # inside the guarded region, so it cannot escape as
                    # a bare KeyboardInterrupt after the with-block
                    try:
                        time.sleep(0.05)
                    except KeyboardInterrupt:
                        pass
    except KeyboardInterrupt:
        # covers both the yield and the cleanup above: an interrupt
        # delivered mid-finally (lock acquire, timer.cancel) still
        # converts to the classified error instead of leaking.  Known
        # ambiguity: a GENUINE Ctrl-C landing inside a blown-deadline
        # window is indistinguishable from the watchdog's own interrupt
        # (no signal handlers by design) and is reclassified as the
        # timeout; the window is one blown call per site, after which
        # the demotion prevents repeats — a second Ctrl-C aborts.
        if state["fired"]:
            raise blew() from None
        raise
    if state["fired"]:
        raise blew()


# -- run report -------------------------------------------------------------

#: Every run-report event kind the code emits, name -> one-line doc —
#: the authoritative documentation of the observability surface,
#: mirroring utils/env.py:ENV_VARS.  `splint` rule SPL012 statically
#: checks every ``run_report().add("<kind>", ...)`` emission site
#: against this registry (both directions: undeclared emissions and
#: declared-but-never-emitted kinds are findings), so the docs and the
#: code cannot drift apart.  Tests may add ad-hoc kinds through a
#: RunReport instance directly; the registry governs production
#: emissions only.
RUN_REPORT_EVENTS = {
    "transient_retry": "a transient failure was retried in place with "
                       "capped backoff+jitter (retry_transient)",
    "engine_demotion": "a dispatch engine was demoted at runtime "
                       "(process-wide, or per-shape for RESOURCE "
                       "failures) and the fallback chain skips it",
    "checkpoint_recovery": "a corrupt/torn checkpoint degraded the "
                           "resume to the .bak generation or a fresh "
                           "start (cpd.load_checkpoint_resilient)",
    "probe_downgrade": "a capability-probe verdict was downgraded to "
                       "unproven for this session (re-probed next "
                       "process)",
    "probe_cache_io_error": "probe-cache IO failed and was degraded "
                            "(cache stays best-effort; verdicts are "
                            "re-earned)",
    "tune_cache_io_error": "plan-cache IO failed and was degraded "
                           "(dispatch falls back to re-tuning or the "
                           "heuristic chain)",
    "tuned_plan": "cpd_als dispatched through autotuned MTTKRP plans "
                  "(docs/autotune.md); carries the per-mode plans",
    "tuner_negative": "an autotuner candidate failed to measure; "
                      "deterministic/resource failures persist as "
                      "negative plan-cache entries",
    "tuner_degraded": "a mode keeps the heuristic chain instead of a "
                      "tuned plan: no candidate was measurable, or the "
                      "plan's storage verdict could not apply under "
                      "the resolved whole-tensor policy (blocked.py)",
    "block_clamp": "build_layout clamped the requested nnz block to "
                   "the tensor's size (blocked.py); carries the "
                   "requested format so v1/v2 plans stay "
                   "distinguishable in the log",
    "format_v2": "blocked layouts were built at a non-default encoding "
                 "(compact v2 local/segment indices and/or narrowed "
                 "value storage, docs/format.md); carries the achieved "
                 "per-mode format descriptions",
    "format_fallback": "a compact-format encode failed (blocked.py, "
                       "the format.encode fault site), its native "
                       "stream consumption failed at dispatch "
                       "(ops/mttkrp.py, the format.decode site — "
                       "site=decode), or a dense tile-layout build "
                       "failed (blocked.py, the format.dense site — "
                       "site=dense, docs/dense.md) and the run "
                       "degraded CLASSIFIED to the v1 i32 / sparse "
                       "path — slower bytes, never a failed build or "
                       "run",
    "dense_dispatch": "first dispatch of a dense-tile MTTKRP engine "
                      "over a dense-mode layout (ops/mttkrp.py, "
                      "docs/dense.md): records the engine, mode, row "
                      "tile, span and density bucket — the "
                      "zero-index-bytes contract made observable",
    "format_decode": "first dispatch of an engine over a compact "
                     "layout: records the consumed encoding and "
                     "whether decode runs natively in-kernel/per-"
                     "chunk (xla_scan/xla) or at operand "
                     "prep (unfused_pallas) — the achieved-"
                     "bytes≈encoded-bytes contract made observable "
                     "(ops/mttkrp.py, docs/format.md)",
    "packing_fallback": "a balanced fiber pack failed and the build "
                        "degraded CLASSIFIED to the fixed slicing "
                        "(blocked.py, the layout.pack fault site; "
                        "docs/layout-balance.md) — worse balance, "
                        "never a failed build",
    "reorder_fallback": "a reorder recipe's permutation compute/apply "
                        "failed and the layout build degraded "
                        "CLASSIFIED to identity order (reorder.py "
                        "apply_reorder, the reorder.apply fault site; "
                        "docs/layout-balance.md) — worse locality, "
                        "never a failed run",
    "layout_imbalance": "achieved load-balance of a built layout or "
                        "distributed sharding (max/mean nnz per "
                        "block/span/shard, one-hot work "
                        "amplification; docs/layout-balance.md) — "
                        "carried by splatt cpd --json, bench and "
                        "MULTICHIP artifacts",
    "health_nonfinite": "the numerical-health sentinel found "
                        "non-finite factors/λ/fit in a sweep's outputs "
                        "at a fit-check iteration "
                        "(docs/guarded-als.md)",
    "health_rollback": "the ALS driver rolled back to the last-good "
                       "host snapshot, bumped regularization and/or "
                       "re-randomized the offending factor, and "
                       "retried the sweep",
    "health_degraded": "the rollback budget (SPLATT_HEALTH_RETRIES) "
                       "was exhausted: the run checkpointed the "
                       "last-good state and stopped early with a "
                       "degraded verdict instead of diverging",
    "deadline_blown": "the deadline watchdog (resilience.deadline) "
                      "expired on a host-side compile/measure/probe "
                      "call; classified TIMEOUT and demoted per-shape "
                      "like OOM",
    "bench_path_error": "one benchmark path failed mid-run; the error "
                        "was classified and recorded and the "
                        "remaining paths continued (bench.py)",
    "bench_regression": "the fresh benchmark ran >10% slower than the "
                        "newest prior BENCH_*.json on the same metric; "
                        "bench.py --gate turns this into a nonzero "
                        "exit (record_bench_regression)",
    "job_accepted": "the serve daemon accepted a job submission and "
                    "journaled it durably (docs/serve.md); an accepted "
                    "job reaches a terminal state even across daemon "
                    "crashes",
    "job_resumed": "journal replay re-enqueued a non-terminal job "
                   "after a daemon restart; the job resumes from its "
                   "last hardened checkpoint (docs/serve.md)",
    "job_started": "a worker began running an accepted job (emitted "
                   "next to the journal's started record); as a trace "
                   "point event it is the flight recorder's "
                   "deterministic 'this job was live HERE' mark — the "
                   "fleet soak post-mortems a SIGKILLed replica's "
                   "ring for it (docs/observability.md)",
    "queue_full": "the serve daemon's bounded queue load-shed a "
                  "submission (SPLATT_SERVE_QUEUE_MAX); the client "
                  "gets an explicit rejection instead of unbounded "
                  "queueing (docs/serve.md)",
    "job_degraded": "a supervised job finished degraded or failed "
                    "(health budget exhausted, blown deadline, or a "
                    "classified error) instead of converging; the "
                    "job's own run report carries the evidence "
                    "(docs/serve.md)",
    "journal_torn": "journal replay skipped one unparseable record — "
                    "final OR mid-file, the debris a writer dying "
                    "mid-append (or a SIGKILLed fleet replica) can "
                    "leave; classified and skipped, never fatal, and "
                    "the next append heals a torn tail before writing "
                    "(serve.py Journal, docs/fleet.md)",
    "journal_unknown_kind": "journal replay skipped a record whose "
                            "kind this version does not know "
                            "(serve.KNOWN_KINDS) — a newer writer's "
                            "journal or hand-edited debris; skipped "
                            "classified instead of wedging the job "
                            "table (the SPL022 forward-compat gate, "
                            "docs/static-analysis.md)",
    "crash_windows_exercised": "which durable-op crash windows a "
                               "chaos soak's kills actually landed in "
                               "(window ids from the crash-point "
                               "checker's vocabulary, tools/splint/"
                               "crashpoint.py) — the dynamic-coverage "
                               "half of the static-vs-dynamic "
                               "comparison in docs/static-analysis.md",
    "job_adopted": "a fleet replica took over a dead peer's "
                   "non-terminal job after its lease expired (the "
                   "fleet.adopt takeover path); the job resumes from "
                   "its hardened checkpoint on the adopter "
                   "(docs/fleet.md)",
    "lease_expired": "a job lease expired: role=owner — this "
                     "replica's renew was refused and the running job "
                     "was abandoned uncommitted; role=adopter — an "
                     "expired lease was observed and taken over "
                     "(fleet.py/serve.py, docs/fleet.md)",
    "quota_rejected": "admission control shed a submission because "
                      "its tenant is at the per-tenant non-terminal-"
                      "job quota (SPLATT_FLEET_TENANT_QUOTA) — one "
                      "tenant flooding the spool cannot crowd out "
                      "the rest (serve.py, docs/fleet.md)",
    "affinity_routed": "the fleet scheduler made a cache-affinity "
                       "decision: a job dispatched to this replica's "
                       "warm caches (warm_local), deferred to a warm "
                       "peer (deferred), or taken anyway on the load "
                       "tiebreaker / deferral cap (load_tiebreak) "
                       "(serve.py, docs/fleet.md)",
    "comm_fallback": "a distributed comm engine failed its probe and "
                     "the sweep degraded down the comm chain — "
                     "async_ring -> ring -> all2all — with the failed "
                     "strategy demoted under its own comm shape key "
                     "(parallel/sharded.py, docs/ring.md)",
    "ring_overlap": "achieved comm/compute overlap of a ring-variant "
                    "distributed sweep: standalone exchange time vs "
                    "the fraction hidden under the local MTTKRP, next "
                    "to the wire model's per-device bytes "
                    "(docs/ring.md; carried into MULTICHIP artifacts "
                    "and `splatt cpd --json`)",
    "bench_noisy": "a bench --gate timing comparison was too noisy to "
                   "judge: one side's coefficient of variation "
                   "exceeded the absolute ceiling, or the delta was "
                   "smaller than CV_NOISE_MULT x the measured CV (the "
                   "carried threshold names whichever bound fired), "
                   "so the slowdown is a warning, not a gate failure "
                   "(bench.py)",
    "trace_written": "a Chrome trace-event JSON export "
                     "(trace.write_chrome_trace, the --trace <path> "
                     "flag; docs/observability.md) was written, or "
                     "failed classified — losing the trace must never "
                     "lose the run; ok=False with path '(annotation)' "
                     "records a degraded TPU trace-annotation probe",
    "metrics_snapshot": "the metrics registry was snapshotted to a "
                        "Prometheus text file (trace.write_metrics — "
                        "the serve cadence via SPLATT_METRICS_PATH / "
                        "SPLATT_METRICS_INTERVAL_S; "
                        "docs/observability.md); a write failure "
                        "degrades classified, never kills the daemon "
                        "it observes",
    "slo_burn": "an SLO's error-budget burn rate exceeded the alert "
                "threshold on BOTH the short and long windows "
                "(fleetobs.SloEvaluator, the multi-window burn-rate "
                "policy of docs/observability.md): carries the slo "
                "name, both burn rates and the window; counted into "
                "splatt_slo_burn_total so a burn spike is visible in "
                "every later fleet aggregate",
    "flight_degraded": "a flight-recorder ring flush failed (the "
                       "trace.flight fault site): the recorder is "
                       "DISARMED for the rest of the process and the "
                       "failure classified — the black box must never "
                       "take down the run it records "
                       "(docs/observability.md)",
    "batch_dispatched": "the serve daemon coalesced >= "
                        "SPLATT_SERVE_BATCH_MIN queued same-regime "
                        "jobs into ONE vmapped batched CPD "
                        "(serve.py _run_batch -> cpd.cpd_als_batched; "
                        "docs/batched.md): carries the member job "
                        "ids, the regime key and k — per-job journal "
                        "lineage, results and quotas are preserved "
                        "through the batch",
    "batch_degraded": "a coalesced batch failed at dispatch or "
                      "mid-run (the serve.batch fault site included) "
                      "and degraded CLASSIFIED to per-tensor "
                      "dispatch of its members (docs/batched.md) — "
                      "batching is an optimization, never a new way "
                      "to lose a job",
    "update_applied": "an `update` job appended its delta COO to a "
                      "checkpointed model and committed the "
                      "warm-started sweeps (serve.py _run_update; "
                      "docs/batched.md): carries base, update "
                      "ordinal, sweep count, delta nnz and the "
                      "reached fit — the model-store lineage `splatt "
                      "status --json` audits",
    "refit_scheduled": "an `update` job took the full-refit repair "
                       "path instead of (or after) the warm update: "
                       "reason records why — no_model, the periodic "
                       "SPLATT_UPDATE_REFIT_EVERY boundary, a "
                       "health-sentinel degrade, or a classified "
                       "warm-path failure (docs/batched.md)",
    "model_torn": "a model-store artifact failed its integrity fence "
                  "— a checkpoint whose factor content does not "
                  "match the generation stamp, a stamp-less or "
                  "unparseable generation file, or a `.model.npz` "
                  "missing its `applied` array / failing checksum "
                  "(serve.py _load_model_tensor, predict.py "
                  "load_model_generation): carries the failure class "
                  "and which piece tore; readers degrade to the "
                  "`.bak` generation or refuse, writers route to the "
                  "refit repair path — never a silent consume "
                  "(docs/predict.md)",
    "model_generation_advanced": "a model-store commit atomically "
                                 "advanced the model's generation "
                                 "stamp (predict.py "
                                 "advance_generation from serve.py's "
                                 "update/fit commits): carries model, "
                                 "the new gen ordinal and the factor "
                                 "content sha — the fence every "
                                 "predict pins against "
                                 "(docs/predict.md)",
    "predict_served": "a predict job answered from an intact, "
                      "generation-fenced model (serve.py "
                      "_run_predict): carries model, the served "
                      "generation, the pinned-at-admission "
                      "generation and the cache outcome — the "
                      "journal-auditable staleness evidence "
                      "(docs/predict.md)",
    "predict_degraded": "a predict's preferred path failed "
                        "classified: a poisoned cache fell back to "
                        "the direct read, or no intact generation "
                        "survived the fence and the predict was "
                        "REFUSED (reason records which) — a refusal, "
                        "never garbage (docs/predict.md)",
    "record_quarantined": "streaming ingest quarantined one malformed "
                          "stream record to the sidecar (ingest.py "
                          "parse_chunk; docs/ingest.md): carries the "
                          "chunk ordinal, source line and byte "
                          "offset, and the quarantine class — "
                          "bad_arity, bad_token, bad_index or "
                          "nonfinite_value — so a 100M-line corpus "
                          "names its bad records exactly",
    "watermark_advanced": "one ingest chunk passed its journal-append "
                          "fence (ingest.py IngestState.advance — "
                          "AFTER the durable append, docs/ingest.md "
                          "fence order): carries the chunk ordinal, "
                          "its nnz/records/quarantined counts and "
                          "the resume byte offset — the exactly-once "
                          "commit made journal-auditable",
    "ingest_resumed": "an ingest run opened against a non-empty "
                      "chunk journal and resumed from its watermark "
                      "(ingest.py IngestState._replay): carries the "
                      "watermark, skipped-chunk count and the resume "
                      "offset — the crash-recovery evidence the "
                      "SIGKILL soak asserts on (docs/ingest.md)",
    "ingest_degraded": "the quarantine budget tripped (count over "
                       "SPLATT_INGEST_QUARANTINE_MAX or rate over "
                       "SPLATT_INGEST_QUARANTINE_RATE) and the run "
                       "stopped CLASSIFIED with its committed "
                       "watermark intact (ingest.py ingest_stream; "
                       "docs/ingest.md) — degraded and resumable, "
                       "never a silently corrupt tensor",
    "vocab_stats": "ingest finalize's vocabulary report (ingest.py "
                   "IngestState.finalize; docs/ingest.md): which "
                   "modes are vocab-mapped and each mode's final "
                   "cardinality — the power-law structure evidence "
                   "ROADMAP item 1 wants from real corpora",
}


def record_path_error(label: str, exc) -> dict:
    """Classify a benchmark path failure into a ``bench_path_error``
    run-report event and return the event — the shared emission point
    bench.py uses so a failing path is recorded and skipped instead of
    aborting the whole benchmark."""
    return run_report().add(
        "bench_path_error", path=label,
        failure_class=classify_failure(exc).value,
        error=failure_message(exc)[:200])


def record_bench_regression(path: str, sec: float, prior_sec: float,
                            pct: float, prior_file: str) -> dict:
    """Record a ``bench_regression`` run-report event — the shared
    emission point bench.py's gate uses when a fresh timing runs >10%
    slower than the newest prior BENCH_*.json on the same metric, so
    every future PR ships with a perf verdict instead of a bare number
    (ROADMAP open item 1)."""
    return run_report().add(
        "bench_regression", path=path, sec=round(float(sec), 4),
        prior_sec=round(float(prior_sec), 4), pct=round(float(pct), 1),
        prior_file=prior_file)


def record_bench_noisy(path: str, cv: float, threshold: float,
                       sec: float, prior_sec: float,
                       prior_file: str) -> dict:
    """Record a ``bench_noisy`` run-report event — the shared emission
    point bench.py's gate uses when a would-be regression's timing
    distribution is too noisy to trust (CV above `threshold` on either
    side): the comparison becomes a loud warning instead of a hard
    gate failure, so regression verdicts stay verdicts rather than
    noise (ROADMAP open item 1 remnant)."""
    return run_report().add(
        "bench_noisy", path=path, cv=round(float(cv), 4),
        threshold=round(float(threshold), 4), sec=round(float(sec), 4),
        prior_sec=round(float(prior_sec), 4), prior_file=prior_file)


class RunReport:
    """Append-only log of resilience events for one run: engine
    demotions, transient retries, probe verdict downgrades, checkpoint
    recoveries.  The CLI prints :meth:`summary` after the run so silent
    degradation is observable; tests assert on :meth:`events`.  A
    report owned by a job :func:`scope` stamps its ``job_id`` onto
    every event so multi-tenant logs stay attributable."""

    def __init__(self, job_id: Optional[str] = None):
        self._events: List[dict] = []
        self.job_id = job_id

    def add(self, kind: str, **info) -> dict:
        ev = dict(kind=kind, ts=time.time(), **info)
        if self.job_id is not None and "job" not in ev:
            ev["job"] = self.job_id
        self._events.append(ev)
        # every emission is ALSO a timestamped point event attached to
        # the enclosing trace span (and feeds the always-on metrics
        # registry): demotions, fallbacks and rollbacks become visible
        # in time order on the exported trace (docs/observability.md)
        from splatt_tpu import trace

        trace.point(kind, ev)
        return ev

    def events(self, kind: Optional[str] = None) -> List[dict]:
        if kind is None:
            return list(self._events)
        return [e for e in self._events if e["kind"] == kind]

    def clear(self) -> None:
        self._events.clear()

    def summary(self) -> List[str]:
        """Human-readable lines, one per noteworthy event (retries are
        aggregated — their details matter for debugging, not reporting)."""
        lines = []
        retries = self.events("transient_retry")
        if retries:
            lines.append(f"  {len(retries)} transient failure(s) retried "
                         f"with backoff")
        for e in self.events("engine_demotion"):
            scope = (f"shape {e['shape_key']}" if e.get("shape_key")
                     else "this process")
            lines.append(f"  engine {e['engine']} demoted for {scope} "
                         f"({e['failure_class']}: {e['error'][:80]})")
        for e in self.events("checkpoint_recovery"):
            lines.append(f"  checkpoint {e['path']} was corrupt "
                         f"({e['error'][:80]}); {e['action']}")
        for e in self.events("probe_downgrade"):
            lines.append(f"  probe {e['state_key']}: {e['verdict']} "
                         f"(unproven — re-probed next process)")
        negatives = self.events("tuner_negative")
        if negatives:
            lines.append(f"  {len(negatives)} autotuner candidate(s) "
                         f"failed to measure (deterministic failures "
                         f"recorded as negative plan-cache entries)")
        for e in self.events("tuner_degraded"):
            why = e.get("reason") or ("no measurable candidate — "
                                      "dispatch keeps the heuristic "
                                      "chain")
            lines.append(f"  autotuner: mode {e['mode']}: {why}")
        nonfinite = self.events("health_nonfinite")
        if nonfinite:
            its = sorted({e.get("iteration") for e in nonfinite})
            lines.append(f"  numerical-health sentinel: non-finite "
                         f"sweep outputs at iteration(s) "
                         f"{', '.join(str(i) for i in its)}")
        for e in self.events("health_rollback"):
            lines.append(f"  rolled back to the last-good snapshot at "
                         f"iteration {e.get('iteration')} (attempt "
                         f"{e.get('attempt')}: reg={e.get('regularization')}"
                         f", re-randomized modes "
                         f"{e.get('rerandomized') or []})")
        for e in self.events("health_degraded"):
            lines.append(f"  HEALTH BUDGET EXHAUSTED at iteration "
                         f"{e.get('iteration')}: returned the last-good "
                         f"state ({e.get('action')})")
        for e in self.events("deadline_blown"):
            lines.append(f"  deadline watchdog blew at {e['site']} "
                         f"({e['seconds']:g}s budget)")
        for e in self.events("bench_path_error"):
            lines.append(f"  bench path {e['path']} failed "
                         f"({e['failure_class']}: {e['error'][:80]}); "
                         f"remaining paths continued")
        for e in self.events("format_fallback"):
            if e.get("site") == "decode":
                lines.append(f"  compact-format decode failed at "
                             f"dispatch for mode {e.get('mode')} "
                             f"({e['failure_class']}: "
                             f"{e['error'][:80]}); degraded to the "
                             f"materialized v1 i32 path")
            elif e.get("site") == "dense":
                lines.append(f"  dense tile-layout build failed for "
                             f"mode {e.get('mode')} "
                             f"({e['failure_class']}: "
                             f"{e['error'][:80]}); mode keeps the "
                             f"sparse blocked encoding")
            else:
                lines.append(f"  compact-format encode failed for mode "
                             f"{e.get('mode')} "
                             f"(requested {e.get('idx_width')}; "
                             f"{e['failure_class']}: {e['error'][:80]}); "
                             f"degraded to the v1 i32 encoding")
        for e in self.events("dense_dispatch"):
            lines.append(f"  dense-mode dispatch [{e.get('engine')}]: "
                         f"mode {e.get('mode')} as "
                         f"{e.get('tile')}x{e.get('span')} value tiles "
                         f"({e.get('density_bucket') or 'dense'}; zero "
                         f"index bytes)")
        for e in self.events("packing_fallback"):
            lines.append(f"  balanced fiber pack failed for mode "
                         f"{e.get('mode')} ({e['failure_class']}: "
                         f"{e['error'][:80]}); degraded to fixed "
                         f"slicing")
        for e in self.events("reorder_fallback"):
            lines.append(f"  reorder recipe {e.get('how')!r} failed "
                         f"({e['failure_class']}: {e['error'][:80]}); "
                         f"degraded to identity order")
        for e in self.events("layout_imbalance"):
            # only imbalanced layouts/shards are worth a summary line;
            # the full stats always ride in the --json events
            worst = max(e.get("block_nnz_max_mean", 1.0) or 1.0,
                        e.get("shard_max_mean", 1.0) or 1.0)
            if worst > 1.5:
                where = (f"{e.get('scope', 'layout')} mode {e['mode']}"
                         if "mode" in e else e.get("scope", "sharding"))
                lines.append(f"  load imbalance at {where} "
                             f"[{e.get('packing', e.get('policy', '?'))}]"
                             f": max/mean {worst} "
                             f"(seg_width {e.get('seg_width', '-')}, "
                             f"work x{e.get('work_amp', '-')}/nnz)")
        for e in self.events("bench_regression"):
            lines.append(f"  BENCH REGRESSION on {e['path']}: "
                         f"{e['sec']}s vs {e['prior_sec']}s in "
                         f"{e['prior_file']} (+{e['pct']}%)")
        for e in self.events("bench_noisy"):
            lines.append(f"  bench comparison on {e['path']} too noisy "
                         f"to gate (CV {e['cv']} > {e['threshold']}): "
                         f"{e['sec']}s vs {e['prior_sec']}s in "
                         f"{e['prior_file']} — warning, not a verdict")
        for e in self.events("comm_fallback"):
            lines.append(f"  comm engine {e['strategy']} degraded to "
                         f"{e['fallback_to']} ({e['failure_class']}: "
                         f"{e['error'][:80]})")
        for e in self.events("ring_overlap"):
            lines.append(f"  ring overlap [{e.get('engine')}]: "
                         f"{100 * e.get('overlap_frac', 0):.0f}% of "
                         f"{e.get('exchange_s')}s exchange hidden under "
                         f"compute ({e.get('model_mb_per_device')}MB/dev "
                         f"modeled)")
        for e in self.events("queue_full"):
            lines.append(f"  job {e.get('job')} load-shed: the serve "
                         f"queue was full ({e.get('queue_max')} pending)")
        for e in self.events("job_resumed"):
            lines.append(f"  job {e.get('job')} resumed from the "
                         f"journal after a daemon restart")
        torn = self.events("journal_torn")
        if torn:
            lines.append(f"  journal replay skipped {len(torn)} torn "
                         f"record(s) (crash debris; healed on the "
                         f"next append)")
        for e in self.events("job_adopted"):
            lines.append(f"  job {e.get('job')} ADOPTED by "
                         f"{e.get('replica')} from dead peer "
                         f"{e.get('from_replica')}")
        for e in self.events("lease_expired"):
            if e.get("role") == "owner":
                lines.append(f"  job {e.get('job')}: lease expired "
                             f"under {e.get('replica')} — abandoned "
                             f"uncommitted (a peer may adopt)")
        for e in self.events("quota_rejected"):
            lines.append(f"  job {e.get('job')} shed: tenant "
                         f"{e.get('tenant')} at quota "
                         f"({e.get('live')}/{e.get('quota')} "
                         f"non-terminal)")
        routed = self.events("affinity_routed")
        if routed:
            by_reason: Dict[str, int] = {}
            for e in routed:
                by_reason[e.get("reason", "?")] = \
                    by_reason.get(e.get("reason", "?"), 0) + 1
            lines.append("  affinity routing: " + ", ".join(
                f"{k}x{v}" for k, v in sorted(by_reason.items())))
        for e in self.events("job_degraded"):
            lines.append(f"  job {e.get('job')} finished degraded "
                         f"({e.get('failure_class')}: "
                         f"{str(e.get('error', ''))[:80]})")
        for e in self.events("trace_written"):
            if e.get("ok"):
                lines.append(f"  trace written to {e.get('path')} "
                             f"({e.get('spans')} spans, "
                             f"{e.get('events')} point events)")
            else:
                lines.append(f"  trace export {e.get('path')} degraded "
                             f"({e.get('failure_class')}: "
                             f"{str(e.get('error', ''))[:80]})")
        snaps = self.events("metrics_snapshot")
        ok_snaps = [e for e in snaps if e.get("ok")]
        if ok_snaps:
            lines.append(f"  {len(ok_snaps)} metrics snapshot(s) "
                         f"written to {ok_snaps[-1].get('path')}")
        for e in snaps:
            if not e.get("ok"):
                lines.append(f"  metrics snapshot to {e.get('path')} "
                             f"FAILED ({e.get('failure_class')}: "
                             f"{str(e.get('error', ''))[:80]})")
        burns = self.events("slo_burn")
        if burns:
            by_slo: Dict[str, int] = {}
            for e in burns:
                by_slo[e.get("slo", "?")] = \
                    by_slo.get(e.get("slo", "?"), 0) + 1
            worst = max(burns, key=lambda e: e.get("burn_short", 0))
            lines.append(f"  SLO BURN: " + ", ".join(
                f"{k}x{v}" for k, v in sorted(by_slo.items()))
                + f" (worst {worst.get('slo')}: "
                f"{worst.get('burn_short', 0):g}x short / "
                f"{worst.get('burn_long', 0):g}x long over "
                f"{worst.get('window_s', 0):g}s)")
        for e in self.events("flight_degraded"):
            lines.append(f"  flight recorder {e.get('path')} DISARMED "
                         f"({e.get('failure_class')}: "
                         f"{str(e.get('error', ''))[:80]})")
        for e in self.events("batch_dispatched"):
            lines.append(f"  batch of {e.get('k')} same-regime jobs "
                         f"dispatched as one vmapped CPD "
                         f"(regime {e.get('regime')})")
        for e in self.events("batch_degraded"):
            lines.append(f"  BATCH DEGRADED to per-tensor dispatch "
                         f"({e.get('failure_class')}: "
                         f"{str(e.get('error', ''))[:80]}; "
                         f"{len(e.get('jobs') or [])} member(s) re-run "
                         f"individually)")
        for e in self.events("update_applied"):
            lines.append(f"  update #{e.get('update_n')} applied to "
                         f"model {e.get('base')}: {e.get('delta_nnz')} "
                         f"delta nnz folded in over {e.get('sweeps')} "
                         f"warm sweeps (fit {e.get('fit'):.5f})"
                         if e.get("fit") is not None else
                         f"  update #{e.get('update_n')} applied to "
                         f"model {e.get('base')}")
        for e in self.events("refit_scheduled"):
            lines.append(f"  model {e.get('base')}: full refit "
                         f"scheduled at update #{e.get('update_n')} "
                         f"({e.get('reason')})")
        for e in self.events("model_torn"):
            lines.append(f"  MODEL TORN: {e.get('piece')} of "
                         f"{e.get('path')} "
                         f"({e.get('failure_class')}: "
                         f"{str(e.get('error', ''))[:80]})")
        for e in self.events("predict_degraded"):
            lines.append(f"  predict on model {e.get('model')} "
                         f"degraded ({e.get('reason')}: "
                         f"{str(e.get('error', ''))[:80]})")
        quarantined = self.events("record_quarantined")
        if quarantined:
            by_cls: Dict[str, int] = {}
            for e in quarantined:
                k = e.get("quarantine_class", "?")
                by_cls[k] = by_cls.get(k, 0) + 1
            first = quarantined[0]
            lines.append(f"  ingest quarantined {len(quarantined)} "
                         f"record(s): " + ", ".join(
                             f"{k}x{v}"
                             for k, v in sorted(by_cls.items()))
                         + f" (first at line {first.get('line')}, "
                         f"offset {first.get('offset')})")
        advanced = self.events("watermark_advanced")
        if advanced:
            last = advanced[-1]
            lines.append(f"  ingest committed {len(advanced)} "
                         f"chunk(s) this run (watermark "
                         f"{last.get('chunk')}, total nnz "
                         f"{last.get('total_nnz')})")
        for e in self.events("ingest_resumed"):
            lines.append(f"  ingest RESUMED from watermark "
                         f"{e.get('watermark')} ({e.get('chunks')} "
                         f"committed chunk(s) replayed from the "
                         f"journal, offset {e.get('offset')})")
        for e in self.events("ingest_degraded"):
            lines.append(f"  INGEST DEGRADED: quarantine budget "
                         f"tripped at watermark {e.get('watermark')} "
                         f"({e.get('quarantined')} quarantined; "
                         f"{str(e.get('error', ''))[:80]})")
        for e in self.events("vocab_stats"):
            lines.append(f"  ingest vocab: modes "
                         f"[{e.get('vocab_modes')}] vocab-mapped, "
                         f"cardinalities {e.get('cardinalities')}")
        return lines


# -- per-job scoping (docs/serve.md) ----------------------------------------
#
# One serve daemon runs many tenants' decompositions in one process.
# The mutable resilience state — the demotion table, the async
# last-attempt note, the run report — used to be module-global, so one
# tenant's OOM demotion silently steered every neighbor's dispatch and
# one job's health rollback polluted every other job's report.  A
# ResilienceScope is the isolation unit: contextvars-backed, so each
# supervised job (one thread/async context) sees its own state while
# code outside any scope keeps the process-global scope — the
# single-run CLI behavior, unchanged.  The probe/tune/compile caches
# are deliberately NOT scoped: capability and plan verdicts are
# facts about the environment, not about a tenant, and sharing them
# warm is the point of serving many jobs from one process.

@dataclasses.dataclass
class ResilienceScope:
    """One isolation unit of mutable resilience state: the engine
    demotion table, the last-attempt note, the run report, and
    per-scope overrides for the health-retry budget and the deadline
    watchdog (None = inherit the env/process default)."""

    job_id: Optional[str] = None
    demoted: Dict[str, Demotion] = dataclasses.field(default_factory=dict)
    last_attempt: Optional[tuple] = None
    health_retries: Optional[int] = None
    deadline_s: Optional[float] = None
    report: RunReport = None

    def __post_init__(self):
        if self.report is None:
            self.report = RunReport(job_id=self.job_id)


_GLOBAL_SCOPE = ResilienceScope()
_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "splatt_resilience_scope", default=None)


def _state() -> ResilienceScope:
    """The active scope: the contextvar's if a job scope is entered on
    this thread/context, else the process-global scope."""
    return _SCOPE.get() or _GLOBAL_SCOPE


def current_job() -> Optional[str]:
    """The job id of the active scope, or None outside any scope."""
    sc = _SCOPE.get()
    return sc.job_id if sc is not None else None


def scope_health_retries() -> Optional[int]:
    """The active scope's health-retry budget override, or None (the
    env default applies) — consulted by cpd.health_retries()."""
    sc = _SCOPE.get()
    return sc.health_retries if sc is not None else None


@contextlib.contextmanager
def scope(job_id: str, health_retries: Optional[int] = None,
          deadline_s: Optional[float] = None):
    """Enter a fresh per-job resilience scope: demotions, health
    verdicts, the last-attempt note and every run-report event inside
    the block are attributed to `job_id` and isolated from the global
    scope and from every sibling job.  Scopes start EMPTY (no inherited
    demotions): a neighbor's capacity verdict is not evidence against
    this tenant's shapes — cross-job capability facts belong to the
    shared probe cache, which has stricter persistence rules.

    `health_retries` / `deadline_s` override the env-configured
    sentinel budget and watchdog deadline for this job only."""
    st = ResilienceScope(job_id=str(job_id), health_retries=health_retries,
                         deadline_s=deadline_s)
    token = _SCOPE.set(st)
    try:
        yield st
    finally:
        _SCOPE.reset(token)


def run_report() -> RunReport:
    """The active scope's resilience event log (the process-wide log
    outside any :func:`scope`)."""
    return _state().report
