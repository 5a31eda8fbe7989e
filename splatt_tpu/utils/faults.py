"""Fault injection harness — the test hook points of the resilience layer.

Resilience code that only runs when the infrastructure misbehaves is
dead code until the day it matters; this module makes the misbehavior
reproducible.  Production call sites (probe compiles, engine dispatch,
checkpoint writes, sweep outputs) call :func:`maybe_fail` /
:func:`consume` / :func:`poison` with a site name; tests (or an
operator, via env var) arm faults against those sites and the real
error-handling paths execute.

Arming a fault
    - context manager (tests)::

        with faults.inject("probe_compile", "http500", times=2):
            ...   # the first two probe compiles raise an HTTP 500

    - env var (whole-process, e.g. under the CLI)::

        SPLATT_FAULTS="probe_compile:http500:2,engine.unfused_pallas:runtime"

      Comma-separated ``site[:kind][:modifier]...`` specs; ``times``
      defaults to 1, ``*`` means every eligible call.

Chaos schedules (docs/guarded-als.md)
    Beyond the one-shot ``times`` counter, a spec may carry seeded,
    declarative *schedule* modifiers deciding WHEN the armed fault is
    eligible to fire:

    - ``site:kind:iter=k``          — fire on exactly the k-th call to
      the site (1-based; each check at the site counts one call)
    - ``site:kind:p=0.1:seed=N``    — fire each call with probability
      p, drawn from a per-spec ``random.Random(seed)`` so the firing
      pattern is deterministic and replayable
    - ``site:kind:after=t``         — fire on any call once t seconds
      have elapsed since arming
    - ``site:slow:delay=s``         — the ``slow`` kind's sleep length

    Modifiers compose; ``times`` still bounds the TOTAL number of
    firings once a call is eligible.  A spec whose kind is omitted
    (``engine.unfused_pallas:iter=3``) defaults to ``runtime``.
    :func:`parse_schedule` / :func:`format_schedule` round-trip the
    grammar; ``splatt chaos`` (splatt_tpu/chaos.py) drives a CPD under
    a schedule and asserts the soak invariant.

Sites used by the production code:
    - ``probe_compile``          — the capability-probe compile
    - ``engine.<name>``          — an MTTKRP dispatch engine at call
      time (e.g. ``engine.unfused_pallas``, ``engine.xla_scan``)
    - ``checkpoint_write``       — raise during the checkpoint save
    - ``checkpoint_torn``        — consumed (not raised): the writer
      truncates the bytes it just wrote, simulating a torn write
    - ``tuner.measure``          — one autotuner candidate measurement
      (tune.py)
    - ``cpd.sweep``              — poison (not raise): corrupt one ALS
      sweep's outputs with non-finite values, exercising the
      numerical-health sentinel (cpd.py / parallel/common.py)
    - ``serve.submit`` / ``serve.journal_write`` / ``serve.job_run``
      — the serve daemon's submission, durable-journal and supervised-
      job hooks (serve.py, docs/serve.md)

Per-job scoping (docs/serve.md)
    :func:`scoped` arms a schedule in a contextvars overlay shadowing
    the global registry for the sites it names — the serve daemon
    wraps each supervised job in one, so a job spec's declared faults
    fire inside that job's thread only.

Fault kinds map to canned exceptions whose messages exercise specific
:func:`splatt_tpu.resilience.classify_failure` branches:

    ========== ==================================== ===============
    kind       message signature                    classifies as
    ========== ==================================== ===============
    http500    ``... HTTP code 500``                transient
    internal   ``INTERNAL: ...``                    transient
    unavailable ``UNAVAILABLE: ...``                transient
    timeout    ``TimeoutError``                     transient
    oom        ``RESOURCE_EXHAUSTED: ...``          resource
    mosaic     ``Mosaic ...``                       deterministic
    runtime    generic runtime failure              unknown
    ========== ==================================== ===============

Two kinds do not raise at all:

    - ``nan`` / ``inf`` — claimed only by :func:`poison`, which
      multiplies the value it guards by NaN/Inf (a silent numerical
      blowup, the sentinel's quarry);
    - ``slow``          — claimed by :func:`maybe_fail`, which SLEEPS
      ``delay`` seconds instead of raising, so the deadline watchdog
      (:func:`splatt_tpu.resilience.deadline`) fires for real.

The registry is process-local and the checks are O(1) dict lookups on
cold paths only (probes, dispatch resolution, checkpoint IO, one check
per sweep) — never inside a kernel.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import random
import threading
import time
from typing import Dict, Optional, Tuple, Union

_FAULTS_ENV = "SPLATT_FAULTS"

#: times value meaning "every eligible call"
ALWAYS = -1

#: kinds whose firing RAISES a canned exception from maybe_fail
RAISING_KINDS = ("http500", "internal", "unavailable", "timeout",
                 "oom", "mosaic", "runtime")
#: kinds claimed only by poison(): corrupt a value instead of raising
POISON_KINDS = ("nan", "inf")
#: kinds claimed by maybe_fail() that sleep instead of raising — the
#: way to make a real call blow a real deadline
DELAY_KINDS = ("slow",)

#: default sleep of the ``slow`` kind (overridable per spec: delay=s)
SLOW_DELAY_S = 1.0

#: The declared fault sites of the production code, site → doc.  A
#: trailing ``.*`` marks a dynamic family (the production call passes
#: an f-string with that prefix).  This registry is load-bearing, not
#: documentation-only: `splint` rule SPL006 checks that every site
#: string the production code passes to :func:`maybe_fail` /
#: :func:`consume` / :func:`poison` is declared here, that every
#: declared site is still called somewhere, and that every declared
#: site is exercised by at least one test — so a renamed hook cannot
#: silently orphan the resilience path it was built to exercise.
#: (Tests may arm ad-hoc sites to test the harness itself; those need
#: no declaration.)
SITES = {
    "probe_compile": "the capability-probe compile "
                     "(ops/pallas_kernels.py)",
    "engine.*": "an MTTKRP dispatch engine at call time, e.g. "
                "engine.unfused_pallas / engine.xla_scan (ops/mttkrp.py); "
                "poison-armed specs corrupt the engine's OUTPUT "
                "instead of raising",
    "checkpoint_write": "raise during the checkpoint save (cpd.py)",
    "checkpoint_torn": "consumed (not raised): the writer truncates "
                       "the bytes it just wrote, simulating a torn "
                       "write (cpd.py)",
    "format.encode": "the compact-format v2 encode of one blocked "
                     "layout (blocked.py build_layout/reencode_layout); "
                     "a raised fault must degrade the build classified "
                     "to the v1 i32 encoding (format_fallback event), "
                     "never fail it",
    "format.dense": "the dense tile-layout build of one mode "
                    "(blocked.py build_layout/from_coo/reencode_layout, "
                    "docs/dense.md); a raised fault must degrade the "
                    "build classified to the sparse blocked encoding "
                    "(format_fallback event with site=dense), never "
                    "fail it",
    "format.decode": "native stream consumption of a compact layout "
                     "at MTTKRP dispatch (ops/mttkrp.py "
                     "mttkrp_blocked, docs/format.md); a raised fault "
                     "must degrade the dispatch classified to the "
                     "materialized global-i32 v1 path "
                     "(blocked.decode_to_v1, format_fallback event "
                     "with site=decode) — slower bytes, never a "
                     "failed run",
    "layout.pack": "the balanced fiber packing of one blocked layout "
                   "(blocked.py build_layout, docs/layout-balance.md); "
                   "a raised fault must degrade the build classified "
                   "to the fixed slicing (packing_fallback event), "
                   "never fail it",
    "reorder.apply": "the reorder permutation compute + apply "
                     "(reorder.py apply_reorder); a raised fault must "
                     "degrade the run classified to identity order "
                     "(reorder_fallback event), never fail it",
    "comm.ring_exchange": "the ring row-exchange of a distributed "
                          "sweep (parallel/ring_kernels.py: the async "
                          "remote-copy kernels and their ppermute "
                          "fallback); a raised fault surfaces at the "
                          "sweep's first invocation and must degrade "
                          "CLASSIFIED down the comm chain — "
                          "async_ring -> ring -> all2all "
                          "(comm_fallback events, docs/ring.md) — "
                          "never kill the run",
    "tuner.measure": "one autotuner candidate measurement — warm + "
                     "timed MTTKRP runs of a forced engine (tune.py); "
                     "a crashing measurement must degrade dispatch to "
                     "the heuristic chain, never fail the run",
    "cpd.sweep": "poisoned (not raised): corrupt one ALS sweep's "
                 "factor output with non-finite values, exercising "
                 "the numerical-health sentinel and its rollback "
                 "(cpd.py, parallel/common.py)",
    "serve.submit": "one job submission into the serve daemon's "
                    "queue (serve.py); a raised fault must reject "
                    "that submission, classified — never kill the "
                    "daemon",
    "serve.journal_write": "one durable journal append (serve.py); a "
                           "failure while journaling a submission "
                           "rejects the job (durability cannot be "
                           "promised), terminal-record failures "
                           "degrade to warn-and-continue",
    "serve.job_run": "the start of one supervised job (serve.py); a "
                     "raising kind marks the job failed/degraded, "
                     "'slow' holds the job open (blowing a per-job "
                     "deadline, or pinning it for kill-and-restart "
                     "soaks)",
    "fleet.lease_acquire": "one atomic job-lease acquisition (fleet.py "
                           "FleetMember.acquire — the flock + atomic-"
                           "rename claim of docs/fleet.md); a raised "
                           "fault drops the claim classified (the job "
                           "stays claimable and the fleet scan "
                           "re-surfaces it), never kills the worker",
    "fleet.heartbeat": "one membership heartbeat + held-lease renewal "
                       "sweep (fleet.py FleetMember.beat); a raised "
                       "fault degrades classified — a missed beat "
                       "makes the replica look dead sooner, so peers "
                       "adopt its jobs after the lease window, which "
                       "is the documented failure mode",
    "fleet.adopt": "one dead-peer job takeover (fleet.py "
                   "FleetMember.adopt: expired-lease steal with a gen "
                   "bump); a raised fault leaves the job for the next "
                   "scan pass, classified — adoption is retried, "
                   "never lost",
    "trace.export": "the Chrome trace-event JSON export "
                    "(trace.write_chrome_trace); a raised fault must "
                    "degrade classified to a trace_written ok=False "
                    "event — losing the trace must never lose the run "
                    "it observed (docs/observability.md)",
    "trace.flight": "one flight-recorder ring flush (trace.py "
                    "_flight_flush: the bounded per-replica black box "
                    "of docs/observability.md); a raised fault must "
                    "disarm the recorder and degrade classified to a "
                    "flight_degraded event — the trace.export "
                    "discipline: losing the black box must never lose "
                    "the run it records",
    "serve.batch": "one coalesced batch dispatch (serve.py "
                   "_run_batch, docs/batched.md); a raised fault must "
                   "degrade the batch CLASSIFIED to per-tensor "
                   "dispatch of its members (batch_degraded event) — "
                   "every member still reaches its own terminal "
                   "record, never a lost job",
    "cpd.update": "the warm incremental-update pre-pass of a served "
                  "model (cpd.py refresh_touched_rows, consumed by "
                  "serve.py _run_update; docs/batched.md); a raised "
                  "fault must degrade the update CLASSIFIED to the "
                  "full-refit repair path (refit_scheduled event) — "
                  "an update can cost extra sweeps, never the model",
    "cpd.batch.sweep": "poisoned (not raised): corrupt SLOT 0 of a "
                       "batched ALS sweep's last factor output with "
                       "non-finite values (cpd.py "
                       "_cpd_als_batched_traced) — the per-slot "
                       "isolation drill: slot 0 must roll back ALONE "
                       "while every batch neighbor stays bit-clean "
                       "(docs/batched.md)",
    "predict.read": "the direct generation-fenced model read of one "
                    "predict (predict.py load_model_generation, "
                    "docs/predict.md); a raised fault must REFUSE "
                    "that predict classified (predict_degraded "
                    "event) — a refusal, never garbage",
    "predict.cache": "one hot-factor cache lookup on the predict "
                     "lane (predict.py HotFactorCache.get); a raised "
                     "fault must degrade that predict classified to "
                     "the direct generation-fenced read "
                     "(predict_degraded event with a served answer) "
                     "— slower bytes, never a wrong generation",
    "model.generation": "the generation-stamp advance of one model "
                        "commit (predict.py advance_generation, "
                        "called from serve.py's update/fit commits); "
                        "a raised fault must ABORT that commit "
                        "classified — the stamp never advances, so "
                        "readers keep serving the previous "
                        "generation (docs/predict.md)",
    "ingest.read": "one chunk read from the raw record stream "
                   "(ingest.py IngestState.read_chunks, docs/"
                   "ingest.md); a raised fault must ABORT the run "
                   "classified with every committed chunk intact — "
                   "a re-run resumes from the journal watermark and "
                   "re-reads from the recorded byte offset, losing "
                   "and duplicating nothing",
    "ingest.vocab": "the vocab-delta publish of one chunk commit "
                    "(ingest.py IngestState.publish_vocab); a raised "
                    "fault must ABORT that chunk BEFORE its journal "
                    "append — the watermark never moves, so the "
                    "vocab can never land ahead of or behind the "
                    "data (docs/ingest.md fence order)",
    "ingest.commit": "the journal-append watermark fence of one "
                     "chunk commit (ingest.py "
                     "IngestState.append_journal); a raised fault "
                     "leaves published segment/vocab debris but NO "
                     "journal record — the chunk re-commits "
                     "bit-identically on resume, the exactly-once "
                     "invariant's load-bearing window",
}


def _canned(kind: str, site: str) -> Exception:
    if kind == "http500":
        return RuntimeError(
            f"XLA:TPU compile failed: HTTP code 500 from the compile "
            f"service (injected fault at {site})")
    if kind == "internal":
        return RuntimeError(
            f"INTERNAL: injected transient service failure at {site}")
    if kind == "unavailable":
        return RuntimeError(
            f"UNAVAILABLE: injected service failure at {site}")
    if kind == "timeout":
        return TimeoutError(f"injected deadline expiry at {site}")
    if kind == "oom":
        return RuntimeError(
            f"RESOURCE_EXHAUSTED: injected out-of-memory at {site} "
            f"(attempting to allocate 128.00G)")
    if kind == "mosaic":
        return RuntimeError(
            f"Mosaic failed to compile the injected kernel at {site}")
    if kind == "runtime":
        return RuntimeError(f"injected engine runtime failure at {site}")
    raise ValueError(f"unknown fault kind {kind!r}")


def _validate_kind(kind: str) -> None:
    """Arm-time validation of every kind, raising or not."""
    if kind in POISON_KINDS or kind in DELAY_KINDS:
        return
    _canned(kind, "validate")  # raises ValueError on unknown kinds


@dataclasses.dataclass
class FaultSpec:
    """One armed fault: what to do and the schedule deciding when.

    `times` bounds total firings (ALWAYS = unbounded); `iter_at`, `p`
    (+ `seed`), and `after` decide per-call ELIGIBILITY — see the
    module docstring's chaos-schedule grammar.
    """

    kind: str
    times: int = 1          # remaining trigger count; ALWAYS = unbounded
    exc: Optional[Exception] = None   # overrides the canned exception
    fired: int = 0          # how often it actually triggered
    iter_at: Optional[int] = None     # fire on the N-th call only
    p: Optional[float] = None         # per-call Bernoulli probability
    seed: Optional[int] = None        # seeds the Bernoulli draw
    after: Optional[float] = None     # eligible after N seconds armed
    delay: Optional[float] = None     # 'slow' kind: sleep length
    calls: int = 0          # calls observed at the site since arming
    armed_ts: float = dataclasses.field(default_factory=time.monotonic)
    _rng: Optional[random.Random] = dataclasses.field(
        default=None, repr=False, compare=False)

    def rng(self) -> random.Random:
        if self._rng is None:
            self._rng = random.Random(self.seed)
        return self._rng


_LOCK = threading.Lock()
_ACTIVE: Dict[str, FaultSpec] = {}
_env_loaded = False

#: per-context fault overlay (docs/serve.md): a serve job's declared
#: schedule shadows the global registry for the sites it names, so one
#: tenant's chaos drill fires inside that job only — sites the overlay
#: does not name fall through to the global/env-armed registry.
_SCOPED: contextvars.ContextVar = contextvars.ContextVar(
    "splatt_faults_scope", default=None)


def _lookup_locked(site: str) -> Optional[FaultSpec]:
    """The spec governing `site` in this context: the scoped overlay's
    when it names the site, else the global registry's."""
    overlay = _SCOPED.get()
    if overlay is not None and site in overlay:
        return overlay[site]
    return _ACTIVE.get(site)


@contextlib.contextmanager
def scoped(schedule: Union[str, Dict[str, FaultSpec], None]):
    """Arm a per-context fault schedule (same grammar as SPLATT_FAULTS
    / :func:`parse_schedule`) overlaying the global registry for the
    duration of the block.  The serve daemon wraps each supervised job
    in one of these so a job spec's declared faults fire inside that
    job's thread only — per-tenant chaos without cross-tenant blast
    radius.  Yields the {site: FaultSpec} dict; callers read each
    spec's ``fired`` counter afterwards for evidence matching."""
    if schedule is None:
        specs: Dict[str, FaultSpec] = {}
    elif isinstance(schedule, str):
        specs = parse_schedule(schedule)
    else:
        specs = dict(schedule)
    token = _SCOPED.set(specs)
    try:
        yield specs
    finally:
        _SCOPED.reset(token)


def parse_spec(item: str) -> Tuple[str, FaultSpec]:
    """Parse one ``site[:kind][:modifier]...`` spec → (site, FaultSpec).

    Raises ValueError/TypeError on malformation — callers decide
    whether that is fatal (:func:`parse_schedule` from code) or
    warn-and-ignore (the env loader).
    """
    parts = [p.strip() for p in item.split(":")]
    if len(parts) < 2 or not parts[0]:
        raise ValueError("want site:kind[:modifier]... or "
                         "site:modifier=value")
    site = parts[0]
    rest = parts[1:]
    kind = "runtime"
    if rest and "=" not in rest[0] and rest[0] != "*" \
            and not rest[0].isdigit():
        kind = rest[0]
        rest = rest[1:]
    _validate_kind(kind)
    spec = FaultSpec(kind=kind)
    for mod in rest:
        if mod == "*":
            spec.times = ALWAYS
        elif mod.isdigit():
            spec.times = int(mod)
        elif "=" in mod:
            key, _, val = mod.partition("=")
            key = key.strip()
            val = val.strip()
            if key == "iter":
                spec.iter_at = int(val)
                if spec.iter_at < 1:
                    raise ValueError("iter= is 1-based")
            elif key == "p":
                spec.p = float(val)
                if not 0.0 <= spec.p <= 1.0:
                    raise ValueError("p= must lie in [0, 1]")
            elif key == "seed":
                spec.seed = int(val)
            elif key == "after":
                spec.after = float(val)
            elif key == "delay":
                spec.delay = float(val)
            elif key == "times":
                spec.times = ALWAYS if val == "*" else int(val)
            else:
                raise ValueError(f"unknown schedule modifier {key!r}")
        else:
            raise ValueError(f"unparseable modifier {mod!r}")
    return site, spec


def format_spec(site: str, spec: FaultSpec) -> str:
    """Inverse of :func:`parse_spec` (round-trip: parse(format(s)) == s
    for every schedule field)."""
    parts = [site, spec.kind]
    if spec.iter_at is not None:
        parts.append(f"iter={spec.iter_at}")
    if spec.p is not None:
        parts.append(f"p={spec.p:g}")
    if spec.seed is not None:
        parts.append(f"seed={spec.seed}")
    if spec.after is not None:
        parts.append(f"after={spec.after:g}")
    if spec.delay is not None:
        parts.append(f"delay={spec.delay:g}")
    if spec.times == ALWAYS:
        parts.append("*")
    elif spec.times != 1:
        parts.append(str(spec.times))
    return ":".join(parts)


def parse_schedule(text: str) -> Dict[str, FaultSpec]:
    """Parse a comma-separated chaos schedule → {site: FaultSpec}.
    Strict: a malformed entry raises (the env loader has its own
    warn-and-ignore wrapper — a typo in an interactive chaos run should
    fail loudly, a typo in a production env var should not kill the
    run)."""
    out: Dict[str, FaultSpec] = {}
    for item in text.split(","):
        item = item.strip()
        if not item:
            continue
        site, spec = parse_spec(item)
        out[site] = spec
    return out


def format_schedule(schedule: Dict[str, FaultSpec]) -> str:
    """Inverse of :func:`parse_schedule`."""
    return ",".join(format_spec(site, spec)
                    for site, spec in schedule.items())


def arm(site: str, spec: FaultSpec) -> None:
    """Arm `spec` at `site` until :func:`reset` (chaos harness; tests
    preferring scoped arming use :func:`inject`)."""
    with _LOCK:
        _load_env_locked()
        _ACTIVE[site] = spec


def _load_env_locked() -> None:
    global _env_loaded
    if _env_loaded:
        return
    _env_loaded = True
    from splatt_tpu.utils.env import read_env

    raw = read_env(_FAULTS_ENV)
    for item in raw.split(","):
        item = item.strip()
        if not item:
            continue
        # every malformation is warn-and-ignore: a typo in a fault spec
        # must not kill the production run at some random hook site
        try:
            site, spec = parse_spec(item)
        except (ValueError, TypeError) as e:
            import sys

            print(f"splatt-tpu: bad {_FAULTS_ENV} entry {item!r} "
                  f"({e}); ignored", file=sys.stderr)
            continue
        _ACTIVE[site] = spec


def _eligible_locked(spec: FaultSpec) -> bool:
    """Whether THIS call (already counted) satisfies the schedule."""
    if spec.iter_at is not None and spec.calls != spec.iter_at:
        return False
    if spec.after is not None \
            and time.monotonic() - spec.armed_ts < spec.after:
        return False
    if spec.p is not None and not spec.rng().random() < spec.p:
        return False
    return True


def _take(site: str, kinds: Optional[tuple] = None) -> Optional[FaultSpec]:
    """Claim one firing of the fault armed at `site`, if any.  `kinds`
    restricts which fault kinds this hook may claim, so a poison-armed
    spec is never consumed (and wasted) by a raise-shaped hook at the
    same site."""
    with _LOCK:
        _load_env_locked()
        spec = _lookup_locked(site)
        if spec is None:
            return None
        if kinds is not None and spec.kind not in kinds:
            return None
        spec.calls += 1
        if spec.times == 0:
            return None
        if not _eligible_locked(spec):
            return None
        if spec.times != ALWAYS:
            spec.times -= 1
        spec.fired += 1
        return spec


def maybe_fail(site: str) -> None:
    """Production hook: raise the armed fault for `site`, if any —
    or SLEEP, for the ``slow`` kind, so a wrapping deadline watchdog
    fires for real.  A no-op (one dict lookup) when nothing is armed."""
    spec = _take(site, kinds=RAISING_KINDS + DELAY_KINDS)
    if spec is None:
        return
    if spec.kind in DELAY_KINDS:
        time.sleep(spec.delay if spec.delay is not None else SLOW_DELAY_S)
        return
    raise spec.exc if spec.exc is not None else _canned(spec.kind, site)


def poison(site: str, value):
    """Production hook for non-finite injection: when a ``nan``/``inf``
    fault is armed (and scheduled) at `site`, return `value` multiplied
    by NaN/Inf — the silent numerical blowup the health sentinel
    exists to catch; otherwise return `value` unchanged.  Works on any
    array-like with scalar broadcasting (jax arrays included; under a
    jit trace the corruption is baked into the traced program, flushed
    by the sweep rebuild a rollback performs)."""
    spec = _take(site, kinds=POISON_KINDS)
    if spec is None:
        return value
    return value * float("nan" if spec.kind == "nan" else "inf")


def consume(site: str) -> bool:
    """Production hook for non-raising faults (e.g. torn writes): True
    when a fault was armed at `site` (and claims one firing)."""
    return _take(site) is not None


def active(site: str) -> bool:
    """Whether a fault is currently armed at `site` (no claim) — the
    scoped overlay included."""
    with _LOCK:
        _load_env_locked()
        spec = _lookup_locked(site)
        return spec is not None and spec.times != 0


def fired(site: Optional[str] = None):
    """How often armed faults actually triggered: a count for one
    `site`, or {site: count} for every armed site (the chaos harness
    matches run-report events against what actually fired)."""
    with _LOCK:
        _load_env_locked()
        overlay = _SCOPED.get() or {}
        if site is not None:
            spec = _lookup_locked(site)
            return spec.fired if spec is not None else 0
        merged = dict(_ACTIVE)
        merged.update(overlay)  # overlay shadows, as in _lookup_locked
        return {s: spec.fired for s, spec in merged.items()}


@contextlib.contextmanager
def inject(site: str, kind: str = "runtime", times: int = 1,
           exc: Optional[Exception] = None,
           iter_at: Optional[int] = None, p: Optional[float] = None,
           seed: Optional[int] = None, after: Optional[float] = None,
           delay: Optional[float] = None):
    """Arm a fault at `site` for the duration of the block (tests).
    `times` bounds how many calls trigger (ALWAYS = every call); `exc`
    substitutes a custom exception for the canned one; `iter_at` / `p`
    (+ `seed`) / `after` / `delay` are the chaos-schedule fields (see
    the module docstring)."""
    if exc is None:
        _validate_kind(kind)  # validate early
    spec = FaultSpec(kind=kind, times=times, exc=exc, iter_at=iter_at,
                     p=p, seed=seed, after=after, delay=delay)
    with _LOCK:
        _load_env_locked()
        prev = _ACTIVE.get(site)
        _ACTIVE[site] = spec
    try:
        yield spec
    finally:
        with _LOCK:
            if prev is None:
                _ACTIVE.pop(site, None)
            else:
                _ACTIVE[site] = prev


def reset() -> None:
    """Disarm everything and forget the env parse (tests)."""
    global _env_loaded
    with _LOCK:
        _ACTIVE.clear()
        _env_loaded = False
