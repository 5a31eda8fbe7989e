"""splatt-tune: empirical autotuner for MTTKRP engine plans.

The blocked format's speed comes from picking the right execution plan
per tensor — BENCH_r05 measured a 33x spread between dispatch paths on
the same tensor — yet the port used to hardcode the plan: one
``nnz_block`` (4096 + clamp), one ``scan_target``, an engine chain
ordered by static heuristics.  GenTen's performance-portable MTTKRP and
the load-balanced GPU MTTKRP line of work (PAPERS.md) both show the
winning kernel configuration depends on the nnz distribution, the rank
and the device: it must be *measured*, not guessed.  This module is
that measurement layer.

For a given (shape regime, rank, dtype) — the device kind lives in the
cache environment key — :func:`tune` times candidate plans per mode:

    engine (from :func:`splatt_tpu.ops.mttkrp.engine_chain`)
      x nnz_block in NNZ_BLOCKS
      x scan_target ladder (xla_scan engine only)

with short warm+timed runs, and persists each mode's winner in a
versioned on-disk **plan cache** next to the capability-probe cache.
The cache shares the probe cache's environment key (jax version, device
kind, ``_kernel_src_hash`` — editing a kernel source invalidates every
cached plan) and TTL (``SPLATT_PROBE_CACHE_TTL_S``), and applies the
same resilience verdict handling: engines demoted by the resilience
registry are never candidates, transient timing failures are retried in
place via :func:`resilience.retry_transient`, and deterministic or
resource failures are recorded as **negative entries** so a later tune
does not re-pay the failing compile.

Dispatch integration: :func:`splatt_tpu.ops.mttkrp.mttkrp_blocked`
consults :func:`cached_plan` first (the new head of dispatch) and falls
back to the heuristic chain when no applicable plan exists or autotune
is off (``Options.autotune`` / ``SPLATT_AUTOTUNE``);
:meth:`BlockedSparse.compile` consults :func:`tuned_blocks_for` so the
layouts are built at the tuned ``nnz_block`` directly.  ``splatt tune``
(cli.py) pre-tunes a tensor offline; bench.py reports a ``"tuned"``
timing next to ``"blocked"``/``"stream"``.

Plans are tuned against the mode's OWN sorted layout (the allmode-style
fast path).  A dispatch whose path or block disagrees with the stored
plan simply does not match it and keeps today's heuristics — the tuner
can make dispatch faster, never wronger.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: bump when the plan schema or the measurement methodology changes —
#: a cache written by an older tuner is re-tuned, not reinterpreted.
#: v2: plans carry the layout FORMAT (idx_width/val_storage,
#: docs/format.md) and were measured per encoding.
#: v3: plans carry the layout-BALANCE axes (fiber packing / reorder
#: recipe, docs/layout-balance.md) and the plan key gains a slice-skew
#: regime component so uniform-tuned plans never steer power-law
#: tensors.
#: v4: the delta/RLE catalog entries join the format candidates and
#: winners were measured in the in-kernel-decode era (docs/format.md)
#: — plans tuned when
#: every engine paid operand-prep decode are re-earned, not
#: reinterpreted.
#: v5: the plan key gains a mode-density regime component and the
#: dense tile-layout candidates join the matrix (docs/dense.md) —
#: plans tuned when every mode was sparse-only are re-earned on
#: dense-eligible regimes, not reinterpreted.
PLAN_CACHE_VERSION = 5

#: candidate nnz blocks (build_layout clamps small tensors; duplicate
#: effective blocks are measured once)
NNZ_BLOCKS = (1024, 2048, 4096, 8192, 16384)

#: scan_target ladder for the xla_scan engine (elements of one-hot
#: materialized per scan step); the middle rung is the static default
SCAN_TARGETS = (1 << 21, 1 << 23, 1 << 25)

#: candidate index widths when the policy is not pinned: the v1 global
#: encoding, the compact v2 local/segment encoding, the u8 segment-id
#: narrowing, and the delta/RLE catalog entries (docs/format.md) —
#: when a regime's block spans exceed uint8 (or RLE would invert
#: compression, or a delta stream cannot narrow below "auto") the
#: candidate's encode degrades and collapses into an already-measured
#: one via the seen-dedup
IDX_CANDIDATES = ("i32", "auto", "u8", "delta", "rle")

#: candidate fiber-packing policies when the knob is not pinned
#: (docs/layout-balance.md): the fixed slicing and the nnz-balanced
#: fiber packing with long-fiber splitting.  A balanced pack that
#: degrades to fixed at build time collapses into the fixed candidate
#: via the seen-dedup (measured once).
PACKING_CANDIDATES = ("fixed", "balanced")

#: candidate reorder recipes when the knob is not pinned: identity plus
#: the relabeling strategies of splatt_tpu.reorder.  "random" is
#: deliberately not a default candidate (it exists to DESTROY locality
#: — a useful control, available pinned via Options.reorder /
#: SPLATT_REORDER).  Each recipe's permutation is computed once per
#: tune call and every candidate axis is measured over the relabeled
#: tensor; the verdict is whole-tensor at compile time
#: (BlockedSparse.compile resolves a unanimous winner).
REORDER_CANDIDATES = ("identity", "graph", "hgraph", "fibsched")

_AUTOTUNE_ENV = "SPLATT_AUTOTUNE"
_CACHE_ENV = "SPLATT_TUNE_CACHE"


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One persisted dispatch decision: the measured-fastest
    (path, engine, nnz_block, scan_target, layout format) for a
    plan-cache key, plus the winning median seconds per MTTKRP call as
    evidence.  ``idx_width``/``val_storage`` name the encoding the
    winner was measured under (docs/format.md) — dispatch only applies
    a plan to a layout built at exactly that format."""

    path: str
    engine: str
    nnz_block: int
    scan_target: int
    sec: float
    idx_width: str = "i32"
    val_storage: str = "auto"
    #: layout-balance axes (docs/layout-balance.md): the fiber-packing
    #: policy and reorder recipe the winner was measured under —
    #: dispatch only applies a plan to a layout built at exactly them
    packing: str = "fixed"
    reorder: str = "identity"


#: The v5 plan-cache schema contract, in ONE declared place (splint
#: SPL027 audits the code against it in both directions):
#: ``key`` — the regime components :func:`plan_key` must fold in;
#: ``fields`` — every :class:`TunedPlan` field; ``match`` — the subset
#: dispatch must STRICT-compare against the built layout before
#: applying a plan (ops/mttkrp._tuned_plan_for); ``exempt`` — fields
#: that are evidence or applied outputs, never match predicates.
#: Growing TunedPlan/plan_key without updating this dict (and bumping
#: PLAN_CACHE_VERSION — the v2..v5 history above) is the silent
#: mis-dispatch drift class: a plan measured under one layout axis
#: steering a layout built under another.  cached_plan consults
#: ``fields`` so a foreign/partial cache entry is rejected as a
#: schema mismatch instead of half-read.
PLAN_SCHEMA = {
    "version": 5,
    "key": ("dims", "nnz", "mode", "rank", "dtype", "skew", "batch",
            "mode_density"),
    "fields": ("path", "engine", "nnz_block", "scan_target", "sec",
               "idx_width", "val_storage", "packing", "reorder"),
    "match": ("path", "nnz_block", "idx_width", "val_storage",
              "packing", "reorder"),
    "exempt": ("engine", "scan_target", "sec"),
}


@dataclasses.dataclass
class TuneResult:
    """What one :func:`tune` invocation did: the per-mode winning plans,
    how many candidate measurements actually ran (0 on a fully warm
    cache — the cache-hit contract bench and tests assert on), how many
    modes were satisfied straight from the cache, and how many
    candidates were skipped via negative entries or demotions."""

    plans: Dict[int, TunedPlan]
    measured: int = 0
    cache_hits: int = 0
    skipped: int = 0


# -- enablement -------------------------------------------------------------

def autotune_enabled(override: Optional[bool] = None) -> bool:
    """Whether dispatch consults the plan cache: an explicit
    ``Options.autotune`` wins; otherwise the SPLATT_AUTOTUNE env
    default (on unless 0/off/false/no)."""
    if override is not None:
        return bool(override)
    from splatt_tpu.utils.env import read_env

    return str(read_env(_AUTOTUNE_ENV)).lower() not in (
        "0", "off", "false", "no")


# -- plan-cache keys --------------------------------------------------------

def shape_regime(dims: Sequence[int], nnz: int) -> str:
    """Power-of-two shape regime: per-mode dim buckets + an nnz bucket.
    Tensors within 2x of each other per mode share plans — the same
    granularity at which the winning configuration actually moves."""
    db = "-".join(str(int(d).bit_length()) for d in dims)
    return f"m{len(dims)}:d{db}:z{int(max(nnz, 1)).bit_length()}"


def skew_regime(bucket: str) -> str:
    """The regime component of a slice-skew bucket
    (blocked.nnz_skew_bucket): near-uniform buckets (max/mean < 8)
    collapse to "" so uniform-tensor plan keys stay byte-identical to
    the pre-balance cache era; heavier skew keys its own regime — the
    winning layout on a zipf tensor (balanced packing, small
    seg_width) is a different animal from the uniform winner
    (docs/layout-balance.md)."""
    return "" if bucket in ("", "k0", "k1", "k2", "k3") else bucket


def skew_of(tt, mode: int) -> str:
    """The slice-skew bucket of one mode of a COO tensor — what
    build_layout stamps into ModeLayout.skew (permutation-invariant:
    relabeling shuffles the histogram, not its multiset)."""
    from splatt_tpu.blocked import nnz_skew_bucket

    return nnz_skew_bucket(tt.mode_histogram(mode))


def plan_key(dims: Sequence[int], nnz: int, mode: int, rank: int,
             dtype, skew: str = "", batch: int = 1,
             mode_density: str = "") -> str:
    """The cache key of one tuned dispatch site.  Device kind and
    kernel-source hash live in the environment key (shared with the
    probe cache), so this only carries the workload shape — plus the
    mode's slice-skew regime (:func:`skew_regime`; "" for
    near-uniform, keeping legacy keys byte-identical), the mode's
    density regime (blocked.mode_density_bucket, docs/dense.md; "" for
    genuinely sparse modes, keeping legacy keys byte-identical — a
    plan tuned on a near-dense mode never steers a sparse one) and,
    for the batched fleet engine (docs/batched.md), a power-of-two
    batch-size bucket: a plan measured under one vmapped batch never
    steers single-tensor dispatch (or the reverse) — ``batch=1``
    (every pre-batch caller) keeps legacy keys byte-identical."""
    import jax.numpy as jnp

    sk = skew_regime(skew)
    md = str(mode_density or "")
    bt = f":bk{int(batch).bit_length()}" if int(batch) > 1 else ""
    return (f"{shape_regime(dims, nnz)}:mode{mode}:r{int(rank)}"
            f":{jnp.dtype(dtype).name}" + (f":{sk}" if sk else "")
            + (f":{md}" if md else "") + bt)


def _negative_key(key: str, engine: str, block: int, scan_target: int,
                  fmt: str = "i32-auto") -> str:
    return f"neg:{key}:{engine}:b{block}:s{scan_target}:{fmt}"


# -- on-disk plan cache -----------------------------------------------------
#
# Shares machinery with the capability-probe cache
# (ops/pallas_kernels.py): the same environment key — jax version |
# device kind | _kernel_src_hash, so editing any kernel source
# invalidates every cached plan — the same TTL
# (SPLATT_PROBE_CACHE_TTL_S), and the same locked atomic
# read-modify-write so concurrent tuners do not drop each other's
# plans.  Cache IO is best-effort by the same contract: a broken cache
# degrades to re-tuning (and ultimately to the heuristic chain), never
# to a failed dispatch.

#: process-wide plan-cache path override (beats the env var): the
#: chaos harness points measurements at a throwaway file so a soak run
#: cannot dirty the real cache with plans measured under injected
#: faults.  None = env/default resolution.
_cache_path_override: Optional[str] = None


def set_cache_path(path: Optional[str]) -> None:
    """Override the plan-cache file for this process (None restores
    the env/default resolution).  Clears the in-process memo so stale
    entries from the previous file cannot leak across."""
    global _cache_path_override
    _cache_path_override = str(path) if path is not None else None
    reset_memo()


def cache_path():
    """The plan-cache file: the process override, else
    $SPLATT_TUNE_CACHE, else tune_cache.json next to the probe cache."""
    import pathlib

    from splatt_tpu.ops.pallas_kernels import _cache_path
    from splatt_tpu.utils.env import read_env

    if _cache_path_override:
        return pathlib.Path(_cache_path_override)
    p = read_env(_CACHE_ENV)
    if p:
        return pathlib.Path(p)
    return _cache_path().with_name("tune_cache.json")


def _cache_io_error(op: str, exc) -> None:
    """Route a plan-cache IO failure through the failure taxonomy into
    the run report (same contract as the probe cache's helper)."""
    from splatt_tpu import resilience

    resilience.run_report().add(
        "tune_cache_io_error", op=op,
        failure_class=resilience.classify_failure(exc).value,
        error=resilience.failure_message(exc)[:200])


#: in-process memo of resolved cache entries, keyed
#: (cache file, env key, entry key) -> entry dict | False (negative).
#: Dispatch consults the plan once per (mode, sweep) — the memo keeps
#: that a dict lookup instead of a JSON parse per MTTKRP.  Guarded by
#: a lock: concurrent serve jobs share this memo (warm plans are the
#: point of multi-tenancy — docs/serve.md), and a reset racing a
#: write-through must not resurrect an entry from a replaced cache
#: file.
#: under SPLATT_LOCKCHECK the memo is an owner-assertion proxy
#: (utils/lockcheck.py — the SPL014 dynamic cross-check); otherwise
#: both pass through as a plain dict and Lock
from splatt_tpu.utils import lockcheck as _lockcheck

_MEM_LOCK = _lockcheck.guard_lock(threading.Lock())
_MEM: dict = _lockcheck.guard({}, _MEM_LOCK, "tune._MEM")

#: lookup-miss sentinel (None is a legitimate memoized value)
_MISS = object()


def reset_memo() -> None:
    """Forget memoized cache entries (tests; a re-tune in-process)."""
    with _MEM_LOCK:
        _MEM.clear()


def _load_file() -> Optional[dict]:
    from splatt_tpu.ops.pallas_kernels import _json_cache_load

    # the shared read helper owns the degradation contract: missing
    # file -> None, unreadable/corrupt -> reported through the taxonomy
    # (as tune_cache_io_error here) and degraded to a re-tune — a
    # broken cache must never break dispatch
    data = _json_cache_load(cache_path(), on_error=_cache_io_error)
    if not isinstance(data, dict) \
            or data.get("version") != PLAN_CACHE_VERSION:
        # a different schema generation: re-tune rather than reinterpret
        return None
    return data


def _entry_get(key: str) -> Optional[dict]:
    """Resolve one cache entry (plan or negative) with TTL expiry,
    memoized per (file, environment)."""
    from splatt_tpu.ops.pallas_kernels import (_cache_env_key,
                                               probe_cache_ttl)

    memo_key = (str(cache_path()), _cache_env_key(), key)
    with _MEM_LOCK:
        hit = _MEM.get(memo_key, _MISS)
    if hit is not _MISS:
        return hit if hit is not False else None
    entry = None
    data = _load_file()
    if data is not None:
        try:
            entry = data.get("envs", {}).get(_cache_env_key(), {}).get(key)
            if entry is not None:
                ttl = probe_cache_ttl()
                if ttl > 0 and time.time() - float(entry.get("ts", 0)) > ttl:
                    entry = None  # expired: re-earn the plan
        except (AttributeError, TypeError, ValueError) as e:
            # malformed entry (hand-edited file, schema drift): an
            # unusable plan, not a dispatch failure — report and re-tune
            _cache_io_error("load", e)
            entry = None
    with _MEM_LOCK:
        # never clobber a concurrent write-through: a sibling job's
        # _entry_store may have landed between our file read and here,
        # and overwriting its fresh entry with our (older-read) miss
        # would negative-cache a persisted plan for the process life
        cur = _MEM.get(memo_key, _MISS)
        if cur is _MISS:
            _MEM[memo_key] = entry if entry is not None else False
        else:
            entry = cur if cur is not False else None
    return entry


def _entry_store(key: str, value: dict) -> None:
    """Persist one entry (locked atomic read-modify-write shared with
    the probe cache); write-through to the in-process memo."""
    from splatt_tpu.ops.pallas_kernels import (_cache_env_key,
                                               _json_cache_update)

    entry = dict(value, ts=time.time())
    env_key = _cache_env_key()

    def mutate(data):
        if data.get("version") != PLAN_CACHE_VERSION:
            # new or foreign-generation file: (re)start this schema
            data.clear()
            data["version"] = PLAN_CACHE_VERSION
        data.setdefault("envs", {}).setdefault(env_key, {})[key] = entry
        return data

    _json_cache_update(cache_path(), mutate, on_error=_cache_io_error)
    with _MEM_LOCK:
        _MEM[(str(cache_path()), env_key, key)] = entry


def cached_plan(dims: Sequence[int], nnz: int, mode: int, rank: int,
                dtype, skew: str = "",
                mode_density: str = "") -> Optional[TunedPlan]:
    """The persisted winning plan for this dispatch site, or None
    (never tuned, expired, negative-only, or unreadable cache)."""
    entry = _entry_get(plan_key(dims, nnz, mode, rank, dtype, skew=skew,
                                mode_density=mode_density))
    if not entry or "plan" not in entry:
        return None
    p = entry["plan"]
    unknown = set(p) - set(PLAN_SCHEMA["fields"])
    if unknown:
        # field drift without a version bump (a foreign-schema writer):
        # reject the entry classified instead of half-reading it
        _cache_io_error("load", ValueError(
            f"plan entry carries undeclared fields {sorted(unknown)}"))
        return None
    try:
        return TunedPlan(path=str(p["path"]), engine=str(p["engine"]),
                         nnz_block=int(p["nnz_block"]),
                         scan_target=int(p["scan_target"]),
                         sec=float(p.get("sec", 0.0)),
                         idx_width=str(p.get("idx_width", "i32")),
                         val_storage=str(p.get("val_storage", "auto")),
                         packing=str(p.get("packing", "fixed")),
                         reorder=str(p.get("reorder", "identity")))
    except (KeyError, TypeError, ValueError) as e:
        _cache_io_error("load", e)
        return None


def tuned_build_for(tt, rank: int, dtype) -> Dict[int, TunedPlan]:
    """Per-mode cached plans — what :meth:`BlockedSparse.compile`
    builds layouts with (winning ``nnz_block`` AND encoding:
    idx_width/val_storage, docs/format.md, AND the layout-balance axes:
    packing/reorder, docs/layout-balance.md), so the layout is built
    once at the tuned configuration instead of rebuilt when the plan
    disagrees with the default.  Takes the COO tensor (not just
    dims/nnz): the plan key's skew component needs the mode
    histograms."""
    from splatt_tpu.blocked import mode_density_bucket

    out = {}
    for m in range(tt.nmodes):
        plan = cached_plan(tt.dims, tt.nnz, m, rank, dtype,
                           skew=skew_of(tt, m),
                           mode_density=mode_density_bucket(
                               tt.dims, m, tt.nnz))
        if plan is not None:
            out[m] = plan
    return out


def tuned_blocks_for(tt, rank: int, dtype) -> Dict[int, int]:
    """Per-mode tuned nnz_block for every mode with a cached plan
    (the block-only view of :func:`tuned_build_for`)."""
    return {m: p.nnz_block
            for m, p in tuned_build_for(tt, rank, dtype).items()}


def batched_block_for(dims: Sequence[int], nnz: int, mode: int,
                      rank: Optional[int], dtype, k: int,
                      autotune: Optional[bool] = None) -> Optional[int]:
    """The tuned ``nnz_block`` for a BATCHED dispatch of `k` same-regime
    tensors (docs/batched.md), or None (untuned — the caller falls back
    to the options default).

    Consults the batch-axis plan key first (a verdict measured under
    vmapped batching), then the single-tensor key for the same site
    (a reasonable prior: the batch axis multiplies work per block but
    does not change the block's internal shape).  The batched engine
    consumes only the block size today; the full candidate walk stays
    single-tensor (``splatt tune``)."""
    if rank is None or not autotune_enabled(autotune):
        return None
    entry = _entry_get(plan_key(dims, nnz, mode, rank, dtype, batch=k))
    if entry and "plan" in entry:
        try:
            return int(entry["plan"]["nnz_block"])
        except (KeyError, TypeError, ValueError) as e:
            _cache_io_error("load", e)
    plan = cached_plan(dims, nnz, mode, rank, dtype)
    return plan.nnz_block if plan is not None else None


# -- measurement ------------------------------------------------------------

def _measure_candidate(layout, factors, mode: int, path: str, impl: str,
                       engine: str, scan_target: int,
                       warm: int = 1, reps: int = 2) -> float:
    """Median seconds of one forced-engine MTTKRP over `layout` after
    `warm` warm-up calls (compile excluded).  Module-level so tests can
    substitute the timing body without touching the candidate walk."""
    from splatt_tpu import resilience
    from splatt_tpu.ops.mttkrp import _mttkrp_blocked_jit
    from splatt_tpu.utils import faults
    from splatt_tpu.utils.env import host_fence

    def call():
        return _mttkrp_blocked_jit(layout, factors, mode, path, impl,
                                   scan_target, engine)

    # deadline watchdog (docs/guarded-als.md): one pathological
    # candidate's compile must not wedge the whole tune; a blown
    # deadline classifies TIMEOUT — skipped this session, never
    # persisted as a negative entry (slow today may be fine tomorrow)
    from splatt_tpu import trace

    with trace.span("tune.measure", mode=int(mode), path=path,
                    engine=engine, block=int(layout.block),
                    scan_target=int(scan_target)):
        with resilience.deadline("tuner.measure"):
            faults.maybe_fail("tuner.measure")
            for _ in range(max(warm, 1)):
                host_fence(call())
            times = []
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                host_fence(call())
                times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _tune_impl(opts) -> str:
    """The jit engine family candidates are measured under.  The native
    host engine sits before the blocked jit dispatch (plans do not
    govern it), and interpret mode's timings are meaningless — both
    coerce to the XLA family."""
    from splatt_tpu.ops.mttkrp import choose_impl

    impl = choose_impl(opts)
    if impl in ("native", "pallas_interpret"):
        return "xla"
    return impl


def _format_candidates(opts, dtype) -> List[Tuple[str, str]]:
    """(idx_width, val_storage) format candidates (docs/format.md).

    A pinned knob (an explicit ``Options.idx_width``/``val_storage``
    or an explicitly-set SPLATT_IDX_WIDTH/SPLATT_VAL_STORAGE) is
    measured alone; unpinned knobs span the candidate matrix — both
    index encodings, and bf16 value storage next to the compute dtype
    when computing in f32 (the only dtype a bf16 narrowing is a
    *format* choice for rather than a numerics change the caller
    already made).  The cheapest measured format wins per regime; the
    bit-parity (u16/seg) and fit-parity (bf16) test suites are what
    keep "cheapest" and "correct" the same set."""
    import jax.numpy as jnp

    from splatt_tpu.utils.env import env_is_set, read_env

    if opts.idx_width is not None:
        idx = (opts.idx_width,)
    elif env_is_set("SPLATT_IDX_WIDTH"):
        idx = (str(read_env("SPLATT_IDX_WIDTH")),)
    else:
        idx = IDX_CANDIDATES
    if opts.val_storage is not None:
        val = (opts.val_storage,)
    elif env_is_set("SPLATT_VAL_STORAGE"):
        val = (str(read_env("SPLATT_VAL_STORAGE")),)
    elif jnp.dtype(dtype) == jnp.dtype("float32"):
        val = ("auto", "bf16")
    else:
        val = ("auto",)
    return [(i, v) for i in idx for v in val]


def _packing_candidates(opts) -> Tuple[str, ...]:
    """Fiber-packing candidates: a pinned knob (explicit
    ``Options.fiber_packing`` or an explicitly-set
    SPLATT_FIBER_PACKING) is measured alone; unpinned spans both
    policies (docs/layout-balance.md).  Resolution goes through
    config.packing_pinned so a typo'd policy fails with its clear
    message up front, not deep inside a mid-tune build."""
    from splatt_tpu.config import packing_pinned

    pinned = packing_pinned(opts)
    return (pinned,) if pinned is not None else PACKING_CANDIDATES


def _reorder_candidates(opts) -> Tuple[str, ...]:
    """Reorder-recipe candidates: a pinned knob (``Options.reorder`` /
    a set SPLATT_REORDER) is measured alone; unpinned spans
    :data:`REORDER_CANDIDATES`."""
    from splatt_tpu.config import resolve_reorder

    pinned = resolve_reorder(opts)
    return (pinned,) if pinned is not None else REORDER_CANDIDATES


def _candidates(layout, factors, mode: int, path: str, impl: str,
                scan_targets: Sequence[int],
                default_scan: int) -> List[Tuple[str, int]]:
    """(engine, scan_target) candidates for one layout: every live
    engine_chain entry (demoted engines are pruned there — they are
    never candidates), with the scan ladder applied only to the
    xla_scan engine (the only consumer of scan_target)."""
    from splatt_tpu.ops.mttkrp import engine_chain

    out = []
    for engine in engine_chain(layout, factors, mode, path, impl):
        if engine == "xla_scan":
            out.extend((engine, int(st)) for st in scan_targets)
        else:
            out.append((engine, int(default_scan)))
    return out


def tune(tt, rank: int, opts=None, modes: Optional[Sequence[int]] = None,
         blocks: Optional[Sequence[int]] = None,
         scan_targets: Optional[Sequence[int]] = None,
         formats: Optional[Sequence[Tuple[str, str]]] = None,
         packings: Optional[Sequence[str]] = None,
         reorders: Optional[Sequence[str]] = None,
         warm: int = 1, reps: int = 2, force: bool = False) -> TuneResult:
    """Tune the MTTKRP plan for each mode of `tt` at `rank` and persist
    the winners in the plan cache.

    The candidate matrix is reorder x packing x engine x nnz_block x
    scan_target x FORMAT (docs/format.md, docs/layout-balance.md): each
    (idx_width, val_storage) pair from :func:`_format_candidates` (or
    an explicit `formats`) is measured against the same sorted build —
    the v2/bf16 re-encodings are derived without re-sorting — so the
    cheapest *correct* encoding wins empirically per regime.
    bf16-storage candidates are measured with bf16 factors (the
    configuration that actually dispatches), and a winner whose storage
    narrows the compute dtype is stored under BOTH the requested
    dtype's key (for compile-time layout building) and the storage
    dtype's key (for dispatch-time steering, where the factors already
    carry the narrow dtype).

    Layout-balance axes: each reorder recipe's permutation is computed
    ONCE per tune call (a failed recipe degrades classified via
    apply_reorder and is skipped); each (block, packing) pair is one
    sorted build, with a balanced pack that degraded to fixed
    collapsing into the fixed candidate via the seen-dedup.  Plans
    record the recipe, not the permutation — BlockedSparse.compile
    recomputes it deterministically (reorder.REORDER_SEED) and
    resolves a whole-tensor verdict.

    Already-cached (unexpired) plans short-circuit their mode entirely
    — a warm cache runs ZERO measurements (``result.measured == 0``),
    which is what makes pre-tuning with ``splatt tune`` pay off.  Pass
    ``force=True`` to re-measure anyway.

    Failure handling follows the resilience taxonomy: transient timing
    failures retry in place with backoff, deterministic/resource
    failures persist as negative entries (skipped by later tunes),
    unknown failures skip the candidate for this session only, and a
    measurement that blows the deadline watchdog (TIMEOUT,
    docs/guarded-als.md) is skipped this session but never persisted —
    a wedged service today must not blacklist a healthy candidate
    forever.  A mode where every candidate fails gets NO plan —
    dispatch keeps the heuristic chain, recorded as a
    ``tuner_degraded`` run-report event.
    """
    import jax.numpy as jnp

    from splatt_tpu import resilience, trace
    from splatt_tpu.blocked import build_layout, reencode_layout
    from splatt_tpu.config import (LayoutFormat, Verbosity, default_opts,
                                   resolve_dtype, resolve_storage_dtype)
    from splatt_tpu.cpd import init_factors
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, choose_path
    from splatt_tpu.utils.env import read_env_int

    opts = (opts or default_opts()).validate()
    dtype = resolve_dtype(opts, tt.vals.dtype)
    impl = _tune_impl(opts)
    default_scan = read_env_int("SPLATT_SCAN_TARGET_ELEMS") or _SCAN_TARGET
    blocks = tuple(blocks) if blocks else NNZ_BLOCKS
    scan_targets = tuple(scan_targets) if scan_targets else SCAN_TARGETS
    formats = (list(formats) if formats
               else _format_candidates(opts, dtype))
    packings = tuple(packings) if packings else _packing_candidates(opts)
    reorders = tuple(reorders) if reorders else _reorder_candidates(opts)
    modes = range(tt.nmodes) if modes is None else modes
    loud = opts.verbosity >= Verbosity.LOW
    # one relabeled tensor per recipe, computed once (a recipe whose
    # permutation fails degrades classified inside apply_reorder — its
    # candidates are skipped, identity keeps the floor).  Shapes, nnz
    # and per-mode skew are permutation-invariant, so every recipe
    # shares the same plan key and factor operands.
    from splatt_tpu.reorder import apply_reorder

    tensors = None

    def reorder_tensors():
        # lazy, built on the FIRST cache miss only: a fully-warm tune()
        # must stay free (result.measured == 0 AND no O(nnz) permutation
        # builds or relabeled index copies) — serve's Nth same-regime
        # job and the bench tuned path rely on that contract.
        nonlocal tensors
        if tensors is None:
            tensors = {}
            for how in reorders:
                if how == "identity":
                    tensors[how] = tt
                else:
                    tt_r, rperm = apply_reorder(tt, how)
                    if rperm is not None:
                        tensors[how] = tt_r
                    elif loud:
                        print(f"  tune: reorder recipe {how!r} failed "
                              f"(classified); skipping its candidates")
        return tensors
    # plan-independent factor operands: the timing only needs shapes
    # and a realistic dtype, not the caller's actual factors.  Narrow-
    # storage candidates measure with matching narrow factors (memoized
    # casts — the real dispatch they stand for runs that way).
    factors = init_factors(tt.dims, rank, seed=0, dtype=dtype)
    facs_by_dtype = {jnp.dtype(dtype): factors}

    def factors_for(storage):
        sd = jnp.dtype(storage)
        if sd not in facs_by_dtype:
            facs_by_dtype[sd] = [f.astype(sd) for f in factors]
        return facs_by_dtype[sd]

    from splatt_tpu.blocked import mode_density_bucket

    result = TuneResult(plans={})
    for m in modes:
        skew = skew_of(tt, m)
        md = mode_density_bucket(tt.dims, m, tt.nnz)
        key = plan_key(tt.dims, tt.nnz, m, rank, dtype, skew=skew,
                       mode_density=md)
        if not force:
            plan = cached_plan(tt.dims, tt.nnz, m, rank, dtype, skew=skew,
                               mode_density=md)
            # always-on metrics (docs/observability.md): the serve
            # fleet's warm-cache payoff as a Prometheus series
            trace.metric_inc("splatt_tune_cache_total",
                             outcome="hit" if plan is not None
                             else "miss")
            if plan is not None:
                result.cache_hits += 1
                result.plans[m] = plan
                if loud:
                    print(f"  tune mode {m}: plan cache hit "
                          f"({plan.engine} b{plan.nnz_block} "
                          f"s{plan.scan_target} "
                          f"{plan.idx_width}/{plan.val_storage} "
                          f"{plan.packing}/{plan.reorder}) — "
                          f"skipping measurement")
                continue
        best: Optional[TunedPlan] = None
        seen = set()
        for how, tt_how in reorder_tensors().items():
            for req_block in blocks:
                for pack in packings:
                    base_layout = build_layout(
                        tt_how, m, block=int(req_block),
                        val_dtype=np.dtype(dtype),
                        mode_order=opts.mode_order,
                        mode_order_custom=opts.mode_order_custom,
                        packing=pack, reorder_label=how,
                        record_stats=False, dense=False)
                    path = choose_path(base_layout, m, opts)
                    for iw, vs in formats:
                        storage = resolve_storage_dtype(vs, dtype)
                        if (iw, vs) == ("i32", "auto"):
                            layout = base_layout
                        else:
                            # derive the candidate encoding from the one
                            # sorted build (a failed v2 encode degrades
                            # classified to v1 inside reencode_layout)
                            layout = reencode_layout(
                                base_layout, LayoutFormat(idx=iw, val=vs),
                                val_dtype=(None if jnp.dtype(storage) ==
                                           jnp.dtype(dtype) else storage))
                        cand_key = (layout.block, layout.idx_width,
                                    layout.val_storage, layout.packing,
                                    how)
                        if cand_key in seen:
                            continue  # clamp/fallback collapsed this one
                        seen.add(cand_key)
                        fac = factors_for(storage)
                        fmt_tag = (f"{layout.idx_width}-"
                                   f"{layout.val_storage}-"
                                   f"{layout.packing}-{how}")
                        for engine, st in _candidates(layout, fac, m,
                                                      path, impl,
                                                      scan_targets,
                                                      default_scan):
                            neg = _entry_get(_negative_key(
                                key, engine, layout.block, st, fmt_tag))
                            if neg is not None:
                                result.skipped += 1
                                continue

                            def attempt(layout=layout, fac=fac,
                                        path=path, engine=engine, st=st):
                                return _measure_candidate(
                                    layout, fac, m, path, impl, engine,
                                    st, warm=warm, reps=reps)

                            try:
                                sec = resilience.retry_transient(
                                    attempt, label=f"tuner.{engine}")
                            except Exception as e:
                                cls = resilience.classify_failure(e)
                                if cls in (
                                        resilience.FailureClass
                                        .DETERMINISTIC,
                                        resilience.FailureClass.RESOURCE):
                                    # proven: never re-pay this
                                    # candidate's compile
                                    _entry_store(
                                        _negative_key(key, engine,
                                                      layout.block, st,
                                                      fmt_tag),
                                        {"state": cls.value,
                                         "error":
                                         resilience
                                         .failure_message(e)[:200]})
                                resilience.run_report().add(
                                    "tuner_negative", key=key,
                                    engine=engine, block=layout.block,
                                    scan_target=st, fmt=fmt_tag,
                                    failure_class=cls.value,
                                    error=resilience
                                    .failure_message(e)[:200])
                                result.skipped += 1
                                continue
                            result.measured += 1
                            if loud:
                                print(f"  tune mode {m}: {path}/{engine} "
                                      f"b{layout.block} s{st} {fmt_tag}: "
                                      f"{sec:.4f}s")
                            if best is None or sec < best.sec:
                                best = TunedPlan(
                                    path=path, engine=engine,
                                    nnz_block=layout.block,
                                    scan_target=st, sec=sec,
                                    idx_width=layout.idx_width,
                                    val_storage=layout.val_storage,
                                    packing=layout.packing,
                                    reorder=how)
        # dense tile-layout candidates (docs/dense.md): measured
        # beside the sparse matrix whenever the policy allows and the
        # mode's geometry passes the density verdict.  One dense build
        # per value storage — the tile layout has no index-width or
        # packing axis, and a relabeling permutes cells without
        # changing density, so dense is measured under identity
        # reorder only.  A failed build (the format.dense fault site)
        # degrades classified to "no dense candidates", never a
        # failed tune.
        from splatt_tpu.blocked import build_dense_layout, \
            dense_mode_verdict
        from splatt_tpu.config import (resolve_dense,
                                       resolve_dense_threshold)
        from splatt_tpu.utils import faults

        pol = resolve_dense(opts)
        if pol != "off" and dense_mode_verdict(
                tt.dims, m, tt.nnz,
                threshold=resolve_dense_threshold(opts),
                force=(pol == "on")):
            for vs in dict.fromkeys(v for _, v in formats):
                storage = resolve_storage_dtype(vs, dtype)
                try:
                    faults.maybe_fail("format.dense")
                    dlay = build_dense_layout(
                        tt, m, val_dtype=np.dtype(storage))
                except Exception as e:
                    cls = resilience.classify_failure(e)
                    resilience.run_report().add(
                        "format_fallback", mode=m, site="dense",
                        idx_width="dense", failure_class=cls.value,
                        error=resilience.failure_message(e)[:200])
                    continue
                fac = factors_for(storage)
                fmt_tag = f"dense-{dlay.val_storage}-fixed-identity"
                for engine, st in _candidates(dlay, fac, m, "dense",
                                              impl, scan_targets,
                                              default_scan):
                    neg = _entry_get(_negative_key(
                        key, engine, dlay.block, st, fmt_tag))
                    if neg is not None:
                        result.skipped += 1
                        continue

                    def attempt_dense(dlay=dlay, fac=fac, engine=engine,
                                      st=st):
                        return _measure_candidate(
                            dlay, fac, m, "dense", impl, engine, st,
                            warm=warm, reps=reps)

                    try:
                        sec = resilience.retry_transient(
                            attempt_dense, label=f"tuner.{engine}")
                    except Exception as e:
                        cls = resilience.classify_failure(e)
                        if cls in (resilience.FailureClass.DETERMINISTIC,
                                   resilience.FailureClass.RESOURCE):
                            _entry_store(
                                _negative_key(key, engine, dlay.block,
                                              st, fmt_tag),
                                {"state": cls.value,
                                 "error":
                                 resilience.failure_message(e)[:200]})
                        resilience.run_report().add(
                            "tuner_negative", key=key, engine=engine,
                            block=dlay.block, scan_target=st,
                            fmt=fmt_tag, failure_class=cls.value,
                            error=resilience.failure_message(e)[:200])
                        result.skipped += 1
                        continue
                    result.measured += 1
                    if loud:
                        print(f"  tune mode {m}: dense/{engine} "
                              f"t{dlay.tile} {fmt_tag}: {sec:.4f}s")
                    if best is None or sec < best.sec:
                        best = TunedPlan(
                            path="dense", engine=engine,
                            nnz_block=dlay.tile, scan_target=st,
                            sec=sec, idx_width="dense",
                            val_storage=dlay.val_storage,
                            packing="fixed", reorder="identity")
        if best is None:
            # every candidate failed or was skipped: no plan — dispatch
            # keeps the heuristic chain (observable, not silent)
            resilience.run_report().add("tuner_degraded", mode=m, key=key)
            if loud:
                print(f"  tune mode {m}: no candidate measurable; "
                      f"dispatch keeps the heuristic chain")
            continue
        _entry_store(key, {"plan": dataclasses.asdict(best)})
        if best.path == "dense" and skew_regime(skew):
            # a dense layout has no nnz stream, so dispatch keys its
            # lookup with an empty skew bucket — alias the winner
            # there so a skewed-regime dense plan still steers
            # (the storage-dtype alias idiom below)
            _entry_store(plan_key(tt.dims, tt.nnz, m, rank, dtype,
                                  mode_density=md),
                         {"plan": dataclasses.asdict(best)})
        storage = resolve_storage_dtype(best.val_storage, dtype)
        if jnp.dtype(storage) != jnp.dtype(dtype):
            # a storage-narrowing winner also steers dispatch, where
            # the factors already carry the narrow dtype — alias the
            # plan under that key so the steering is not lost
            _entry_store(plan_key(tt.dims, tt.nnz, m, rank, storage,
                                  skew=skew, mode_density=md),
                         {"plan": dataclasses.asdict(best)})
        result.plans[m] = best
        if loud:
            print(f"  tune mode {m}: winner {best.path}/{best.engine} "
                  f"b{best.nnz_block} s{best.scan_target} "
                  f"{best.idx_width}/{best.val_storage} "
                  f"{best.packing}/{best.reorder} "
                  f"({best.sec:.4f}s)")
    return result
