"""Run-time options and compile-time-style configuration.

The reference keeps a flat ``double[SPLATT_OPTION_NOPTIONS]`` options array
(include/splatt/types_config.h:103-123) populated by ``splatt_default_opts``
(src/opts.c:10-47).  Here the same knobs live in a typed dataclass; enums
mirror the reference's option enums.

TPU-first mapping notes:
- ``BlockAlloc`` ≙ ``SPLATT_CSF_{ONEMODE,TWOMODE,ALLMODE}``
  (include/splatt/types_config.h:168-173): how many sorted nnz layouts are
  precomputed — one shared layout, two (smallest + largest mode), or one per
  mode.
- ``priv_threshold`` ≙ ``SPLATT_OPTION_PRIVTHRESH`` (src/opts.c:26): modes
  whose dim is ≤ ``priv_threshold * nnz`` use the full-width one-hot
  reduction (no scatter at all — the analog of per-thread privatized
  accumulators reduced at the end).
- ``Decomposition``/``CommPattern`` ≙ the MPI decomposition/comm enums
  (include/splatt/types_config.h:179-201).  ALL2ALL row exchanges map to
  ``all_gather`` / ``psum_scatter`` over a mesh axis; POINT2POINT maps
  to a ``ppermute`` ring (memory-lean; splatt_tpu.parallel.ring).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import numpy as np

# ≙ SPLATT_MAX_NMODES (include/splatt/constants.h:14-16)
MAX_NMODES = 8


class BlockAlloc(enum.Enum):
    """How many per-mode sorted layouts to precompute (≙ csf allocation)."""

    ONEMODE = "onemode"    # one layout (sorted for the smallest mode)
    TWOMODE = "twomode"    # smallest mode + largest mode layouts
    ALLMODE = "allmode"    # one layout per mode


class ModeOrder(enum.Enum):
    """Secondary mode-ordering policy for a layout (≙ csf_find_mode_order,
    src/csf.h:12-19, src/csf.c:694-726).

    In the blocked design the output mode is *always* the primary sort
    key (that is what makes the sorted one-hot reduction work), so the
    policy orders the remaining modes — which controls gather locality
    for the other factors.  Consequently SMALLFIRST here equals the
    reference's SORTED_MINUSONE (target first, rest ascending); the
    reference's SMALLFIRST/BIGFIRST placements of the target mid-tree
    have no analog (root/internal/leaf traversal collapsed by design).
    """

    SMALLFIRST = "smallfirst"            # rest ascending by dim (default)
    BIGFIRST = "bigfirst"                # rest descending by dim
    INORDER_MINUSONE = "inorder_minusone"  # rest in natural order
    SORTED_MINUSONE = "sorted_minusone"  # alias of SMALLFIRST here
    CUSTOM = "custom"                    # opts.mode_order_custom


class Decomposition(enum.Enum):
    """Distributed decomposition (≙ types_config.h:179-190)."""

    COARSE = "coarse"   # 1-D per mode
    MEDIUM = "medium"   # n-D cartesian grid (default)
    FINE = "fine"       # nonzero-level partition


class CommPattern(enum.Enum):
    """Row-exchange pattern (≙ types_config.h:197-201).

    ALL2ALL (default): all_gather + psum_scatter — fastest when factors
    fit in HBM.  POINT2POINT: the ppermute ring variant
    (splatt_tpu.parallel.ring) — factor blocks travel the ICI ring and
    no device ever materializes a full factor, O(dim/ndev) peak memory
    per factor (the ring-attention trade for huge modes).  ASYNC_RING:
    the same ring dataflow driven by Pallas ``make_async_remote_copy``
    DMAs (splatt_tpu.parallel.ring_kernels, docs/ring.md) — block s+1
    streams from the left neighbor while the local partial MTTKRP
    consumes block s, hiding the exchange behind compute; off-TPU it
    falls back to the ppermute hops (same math bit-for-bit), and a
    failure degrades classified to POINT2POINT then ALL2ALL
    (``comm_fallback``).
    """

    ALL2ALL = "all2all"
    POINT2POINT = "point2point"
    ASYNC_RING = "async_ring"


def resolve_comm_pattern(opts: "Options") -> CommPattern:
    """Resolve the comm strategy for a distributed run: an explicit
    ``Options.comm_pattern`` wins, else the ``SPLATT_COMM`` env default,
    else ALL2ALL — the same explicit-beats-env layering as the format
    knobs (:func:`layout_format`)."""
    from splatt_tpu.utils.env import read_env

    if opts.comm_pattern is not None:
        return opts.comm_pattern
    env = str(read_env("SPLATT_COMM") or "").strip().lower()
    if env:
        try:
            return CommPattern(env)
        except ValueError:
            raise ValueError(
                f"SPLATT_COMM must be one of "
                f"{[c.value for c in CommPattern]}, got {env!r}")
    return CommPattern.ALL2ALL


class Verbosity(enum.IntEnum):
    """≙ SPLATT_VERBOSITY_{NONE,LOW,HIGH,MAX} (types_config.h:143-149)."""

    NONE = 0
    LOW = 1
    HIGH = 2
    MAX = 3


# -- compact blocked format v2 (docs/format.md) -----------------------------
#
# The reference makes index width a build-time config (splatt_idx_t,
# include/splatt/types_config.h:38-43).  Here it is a per-layout
# *policy* the autotuner can choose per shape regime: "i32" keeps the
# v1 global-int32 encoding, "auto"/"u16" switch to the v2 compact
# encoding (per-block LOCAL indices at the narrowest width that fits,
# plus int32 per-block base offsets; the sorted mode's row stream
# becomes segment ids against the block's run start).  Value storage is
# the companion knob: "bf16" stores nonzero values (and hence the
# factors the CPD driver derives its dtype from) in bfloat16 with f32
# accumulation — the MXU-native mixed pattern.

#: legal index-width policies (SPLATT_IDX_WIDTH / Options.idx_width).
#: "u8" narrows the SORTED mode's segment-id stream to uint8 (legal
#: when every block's sorted-mode extent fits 255 — a block span that
#: does not is an encode failure, degraded classified to v1); the
#: other modes encode at the "auto" u16/i32 widths.
#: "delta" stores the GATHER modes' local streams as within-block
#: first-order differences at the narrowest signed width that fits
#: (i8 on smooth index runs — decode is one per-block cumulative sum,
#: exact over integers); the sorted mode keeps its "auto" segment ids.
#: "rle" replaces the sorted mode's per-nnz segment stream with a
#: per-block (seg_width,) run-length COUNT vector (the bitmap/RLE
#: hybrid for dense-ish blocks: seg_width counts instead of block
#: entries); a layout whose seg_width exceeds its block is an encode
#: failure, degraded classified to v1 — compression must never invert.
IDX_WIDTHS = ("i32", "auto", "u16", "u8", "delta", "rle")

#: legal decode-placement policies (SPLATT_DECODE): "kernel" lets
#: dispatch consume the compact streams natively (the per-chunk scan
#: decode — achieved HBM bytes ≈ encoded bytes, docs/format.md); "prep" forces operand-prep decode (the
#: pre-format-v2 dataflow: every engine widens to global i32 before
#: the kernel) — the A/B lever for the decode_overhead bench model.
DECODES = ("kernel", "prep")


def resolve_decode() -> str:
    """Resolve the decode-placement policy (docs/format.md): the
    SPLATT_DECODE env default is "kernel" (native stream consumption);
    "prep" forces operand-prep decode — dispatch materializes the
    global-i32 form up front (blocked.decode_to_v1) so EVERY engine
    runs the pre-format-v2 dataflow.  Centralized here like the format knobs so a typo'd policy fails
    with one clear message."""
    from splatt_tpu.utils.env import read_env

    pol = str(read_env("SPLATT_DECODE"))
    if pol not in DECODES:
        raise ValueError(
            f"SPLATT_DECODE must be one of {DECODES}, got {pol!r}")
    return pol

#: legal value-storage policies (SPLATT_VAL_STORAGE /
#: Options.val_storage); "auto" = the resolved compute dtype
VAL_STORAGES = ("auto", "f32", "bf16")

#: legal fiber-packing policies (SPLATT_FIBER_PACKING /
#: Options.fiber_packing, docs/layout-balance.md): "fixed" slices the
#: sorted stream every nnz_block nonzeros regardless of where fibers
#: fall (the original policy); "balanced" bin-packs fibers into blocks
#: by nnz weight with long-fiber splitting, bounding each block's
#: output-row span so one straggler block cannot inflate seg_width —
#: and with it the one-hot contraction cost — for every block
#: (≙ the chains-on-chains partitioner, src/thread_partition.c:156-195)
PACKINGS = ("fixed", "balanced")

#: legal reorder policies (SPLATT_REORDER / Options.reorder,
#: docs/layout-balance.md): "identity" keeps original index labels;
#: the rest are the relabeling strategies of splatt_tpu.reorder
#: (≙ splatt_perm_type, src/reorder.h:15-22).  Resolution is
#: whole-tensor: one permutation relabels every mode before the
#: layouts are built, and the CPD driver restores original row order
#: on output via Permutation.undo.
REORDERS = ("identity", "random", "graph", "hgraph", "fibsched")


def resolve_packing(opts: "Options") -> str:
    """Resolve the fiber-packing policy for a run: the explicit
    Options field wins, else the SPLATT_FIBER_PACKING env default
    ("fixed" — the conservative original policy)."""
    from splatt_tpu.utils.env import read_env

    pol = (opts.fiber_packing if opts.fiber_packing is not None
           else str(read_env("SPLATT_FIBER_PACKING")))
    if pol not in PACKINGS:
        raise ValueError(
            f"fiber_packing must be one of {PACKINGS}, got {pol!r}")
    return pol


def packing_pinned(opts: "Options") -> Optional[str]:
    """The EXPLICITLY pinned fiber-packing policy — a set
    ``Options.fiber_packing`` or an explicitly-set SPLATT_FIBER_PACKING
    env — validated through :func:`resolve_packing`; None when the user
    left the knob to the tuner.  Pinned beats any cached tuned verdict
    (the val_storage precedent): the tuner measures a pinned policy
    alone, and the builder drops stale plans that disagree."""
    from splatt_tpu.utils.env import env_is_set

    if opts.fiber_packing is None and not env_is_set("SPLATT_FIBER_PACKING"):
        return None
    return resolve_packing(opts)


def resolve_reorder(opts: "Options") -> Optional[str]:
    """Resolve the PINNED reorder policy: the explicit Options field
    wins, else a non-empty SPLATT_REORDER env value; None means
    "unpinned" — BlockedSparse.compile then consults the autotuner's
    unanimous verdict (docs/layout-balance.md), defaulting to
    identity."""
    from splatt_tpu.utils.env import read_env

    how = opts.reorder
    if how is None:
        env = str(read_env("SPLATT_REORDER") or "").strip().lower()
        how = env or None
    if how is not None and how not in REORDERS:
        raise ValueError(
            f"reorder must be one of {REORDERS}, got {how!r}")
    return how


#: legal dense-mode policies (SPLATT_DENSE / Options.dense,
#: docs/dense.md): "off" keeps every mode on the sparse blocked
#: encodings (the conservative default — existing workloads see no
#: change); "auto" lets build/dispatch switch a mode to the dense tile
#: layout when its padded fiber density crosses the threshold; "on" is
#: "auto" with the verdict forced for every mode that is FEASIBLE to
#: tile (the padding-blowup guard still applies — forcing a 42x
#: materialization through a 3-wide inner mode is never useful).
DENSE_POLICIES = ("off", "auto", "on")

#: default padded-density threshold for the dense-mode verdict
#: (SPLATT_DENSE_THRESHOLD / Options.dense_threshold, docs/dense.md):
#: a mode whose nnz fill of the PADDED tile space meets this fraction
#: stops paying index traffic and is stored as dense value tiles.
DENSE_THRESHOLD_DEFAULT = 0.05


def resolve_dense(opts: "Options") -> str:
    """Resolve the dense-mode policy (docs/dense.md): the explicit
    Options field wins, else the SPLATT_DENSE env default ("off" — the
    conservative choice: dense tiling is opt-in, like every format
    knob whose wrong guess costs memory)."""
    from splatt_tpu.utils.env import read_env

    pol = (opts.dense if opts.dense is not None
           else str(read_env("SPLATT_DENSE")))
    if pol not in DENSE_POLICIES:
        raise ValueError(
            f"dense must be one of {DENSE_POLICIES}, got {pol!r}")
    return pol


def resolve_dense_threshold(opts: "Options") -> float:
    """Resolve the dense-mode padded-density threshold: the explicit
    Options field wins, else SPLATT_DENSE_THRESHOLD (default
    :data:`DENSE_THRESHOLD_DEFAULT`)."""
    from splatt_tpu.utils.env import read_env_float

    thr = (opts.dense_threshold if opts.dense_threshold is not None
           else float(read_env_float("SPLATT_DENSE_THRESHOLD")))
    if not 0.0 < thr <= 1.0:
        raise ValueError(
            f"dense_threshold must lie in (0, 1], got {thr!r}")
    return thr


@dataclasses.dataclass(frozen=True)
class LayoutFormat:
    """One blocked-layout encoding request: index width x value
    storage.  ``idx`` "i32" is the v1 global encoding; "auto" encodes
    v2 local indices at the narrowest width that fits each mode's
    per-block extent (uint16 where possible, int32 otherwise); "u16"
    additionally *requires* every mode to fit uint16 (a mode that does
    not is an encode failure, degraded classified to v1).  ``val``
    picks the stored value dtype ("auto" = compute dtype)."""

    idx: str = "i32"
    val: str = "auto"

    def validate(self) -> "LayoutFormat":
        if self.idx not in IDX_WIDTHS:
            raise ValueError(
                f"idx_width must be one of {IDX_WIDTHS}, got {self.idx!r}")
        if self.val not in VAL_STORAGES:
            raise ValueError(
                f"val_storage must be one of {VAL_STORAGES}, "
                f"got {self.val!r}")
        return self

    @property
    def v2(self) -> bool:
        return self.idx != "i32"


def layout_format(opts: "Options") -> LayoutFormat:
    """Resolve the layout format for a run: explicit Options fields
    win, else the SPLATT_IDX_WIDTH / SPLATT_VAL_STORAGE env defaults
    (both conservative: v1 i32 indices, compute-dtype values)."""
    from splatt_tpu.utils.env import read_env

    idx = opts.idx_width if opts.idx_width is not None \
        else str(read_env("SPLATT_IDX_WIDTH"))
    val = opts.val_storage if opts.val_storage is not None \
        else str(read_env("SPLATT_VAL_STORAGE"))
    return LayoutFormat(idx=idx, val=val).validate()


def resolve_storage_dtype(val_storage: str, compute_dtype):
    """The on-device dtype layout values are STORED at: "auto" keeps
    the resolved compute dtype, "f32"/"bf16" pin it.  Centralized here
    (the config module owns dtype policy) so storage narrowing is one
    decision, not a per-callsite literal."""
    import jax.numpy as jnp

    if val_storage == "bf16":
        return jnp.dtype(jnp.bfloat16)
    if val_storage == "f32":
        return jnp.dtype(jnp.float32)
    return jnp.dtype(compute_dtype)


def fit_dtype():
    """The λ/fit bookkeeping dtype of the ALS drivers: solve/normalize
    emit f32 even under bf16 storage (the engines' f32-accumulation
    contract), so λ, fit and the batched drivers' per-slot reg vectors
    live in f32 — one policy decision, owned here (docs/batched.md)."""
    import jax.numpy as jnp

    return jnp.dtype(jnp.float32)


def host_acc_dtype():
    """Host-side accumulator dtype for fit deltas and Frobenius norms:
    f64, matching BlockedSparse.frobsq's full-precision contract."""
    return np.dtype(np.float64)


def host_staging_dtype(dtype):
    """A numpy-representable staging dtype that round-trips `dtype`
    exactly (numpy has no bfloat16 — bf16 device arrays stage through
    f32, an exact widening)."""
    import jax.numpy as jnp

    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.bfloat16):
        return np.dtype(np.float32)
    return np.dtype(d)


#: the narrow storage floats: stored low-precision, accumulated wide
#: (docs/format.md bf16 value storage; :func:`acc_dtype`)
NARROW_DTYPES = ("bfloat16", "float16")


def is_narrow(dtype) -> bool:
    """True when `dtype` is a narrow storage float (bf16/f16) whose
    reductions must accumulate wide (:func:`acc_dtype`)."""
    import jax.numpy as jnp

    return jnp.dtype(dtype).name in NARROW_DTYPES


def acc_dtype(dtype):
    """THE accumulation-dtype policy: reductions over narrow storage
    floats (bf16/f16) accumulate in f32 — the MXU-native mixed pattern
    (low-precision reads, full-precision accumulation) — and every
    other dtype accumulates in itself.  The engine-side ``_acc_dtype``
    helpers delegate here so storage narrowing stays ONE decision;
    splint SPL024 recognizes reductions routed through this helper
    (or pinned via ``preferred_element_type``) as carrying the
    discipline."""
    import jax.numpy as jnp

    if is_narrow(dtype):
        return jnp.dtype(jnp.float32)
    return jnp.dtype(dtype)


def tile_packing(dtype):
    """Native TPU ``(sublane, lane)`` tile packing for `dtype`:
    (8, 128) for 4-byte types, (16, 128) for the 2-byte floats
    (bf16/f16), (32, 128) for 1-byte.  The minor dim is always 128
    lanes; the sublane count scales inversely with itemsize, so one
    packed register tile always spans the same bytes.  Kernel rank/row
    padding must align to THIS (splint SPL025): a dtype-blind pad to 8
    sublanes under-packs bf16 tiles 2x."""
    import jax.numpy as jnp

    itemsize = max(1, jnp.dtype(dtype).itemsize)
    return (8 * max(1, 4 // itemsize), 128)


@dataclasses.dataclass
class Options:
    """Run-time options (≙ splatt_default_opts, src/opts.c:10-47).

    Defaults mirror the reference: tol 1e-5, 50 iterations, TWOMODE
    allocation, privatization threshold 0.02, MEDIUM decomposition,
    ALL2ALL communication, time-based seed.
    """

    # CPD
    tolerance: float = 1e-5
    max_iterations: int = 50
    regularization: float = 0.0
    # Check convergence (and fetch the fit to host) every k iterations.
    # The fit is computed on device every sweep regardless; k > 1 only
    # batches the host synchronization — each fetch is a device-to-host
    # sync that stalls the next sweep's dispatch.  k=1 is the
    # reference semantics (src/cpd.c:357-370).
    fit_check_every: int = 1
    # RNG: None ≙ seed-from-time (src/opts.c RANDSEED default)
    random_seed: Optional[int] = None
    verbosity: Verbosity = Verbosity.LOW

    # Blocked format (≙ CSF_ALLOC / TILE / TILELEVEL)
    block_alloc: BlockAlloc = BlockAlloc.TWOMODE
    nnz_block: int = 4096          # nnz per block (≙ dense-tile granularity)
    # Secondary mode ordering within a layout (≙ csf_find_mode_order);
    # CUSTOM reads mode_order_custom, a permutation of all modes whose
    # relative order of the non-output modes is used.
    mode_order: ModeOrder = ModeOrder.SMALLFIRST
    mode_order_custom: Optional[tuple] = None
    # ≙ SPLATT_OPTION_PRIVTHRESH: a mode is "privatized" (full-width
    # one-hot reduction, no scatter) when its dim ≤ priv_threshold * nnz
    # — i.e. short relative to the nonzero count — and ≤ priv_cap.
    priv_threshold: float = 0.02
    priv_cap: int = 4096           # absolute max width for the one-hot
                                   # full-replica (privatized) reduction
    onehot_cap: int = 1024         # max block row-span for the sorted
                                   # one-hot path before falling back to
                                   # a sorted scatter
    # One-hot reduction engine: None = auto (Pallas kernel on TPU,
    # scanned-XLA einsum elsewhere); True forces Pallas (interpret mode
    # off-TPU); False forces the XLA engine.
    use_pallas: Optional[bool] = None
    # Runtime engine fallback (splatt_tpu.resilience): a failure of the
    # selected MTTKRP engine demotes it and the next engine in the
    # ordered chain runs, instead of the failure killing cpd_als.
    # None = env default (SPLATT_ENGINE_FALLBACK, on unless disabled);
    # False = fail loudly (differential tests chasing a kernel bug want
    # the crash, not the silent rescue).
    engine_fallback: Optional[bool] = None
    # Empirical autotuner (splatt_tpu.tune, docs/autotune.md): when on,
    # MTTKRP dispatch consults the persisted plan cache (measured
    # winning engine / nnz_block / scan_target) before the heuristic
    # engine chain, and BlockedSparse.compile builds layouts at the
    # tuned block.  None = env default (SPLATT_AUTOTUNE, on unless
    # disabled); False forces the static heuristics.  Consulting is
    # cheap; the measurements themselves only run via `splatt tune`,
    # bench.py, or an explicit tune.tune() call.
    autotune: Optional[bool] = None
    # Donate the factor/gram buffers to the jitted ALS sweep
    # (jax donate_argnums): XLA aliases outputs onto the input buffers,
    # so a sweep stops round-tripping per-iteration copies of every
    # factor.  The sweep then CONSUMES its inputs — cpd_als holds a
    # host snapshot (refreshed at fit-check iterations) and
    # re-materializes from it when an engine rescue needs the pre-sweep
    # state back.  None = on; False keeps copying semantics (a caller
    # timing against the old behavior, or holding references to the
    # arrays it passed in).
    donate_sweep: Optional[bool] = None

    # Compact blocked format v2 (docs/format.md): index-width and
    # value-storage policy for the blocked layouts.  None = env default
    # (SPLATT_IDX_WIDTH / SPLATT_VAL_STORAGE, both conservative); the
    # autotuner measures the format candidates and BlockedSparse.compile
    # builds layouts at the winning encoding per mode.
    idx_width: Optional[str] = None      # "i32" | "auto" | "u16"
    val_storage: Optional[str] = None    # "auto" | "f32" | "bf16"

    # Load-balanced layouts (docs/layout-balance.md): fiber-packing
    # policy for the blocked layouts (None = env default
    # SPLATT_FIBER_PACKING, "fixed") and the index-relabeling reorder
    # applied before layout build (None = unpinned: SPLATT_REORDER if
    # set, else the autotuner's unanimous verdict, else identity).
    # Both are autotuner candidate axes.
    fiber_packing: Optional[str] = None  # "fixed" | "balanced"
    reorder: Optional[str] = None        # "identity" | "random" |
                                         # "graph" | "hgraph" | "fibsched"

    # Dense-mode tile layouts (docs/dense.md): a mode whose padded
    # fiber density crosses the threshold stores dense (tile, span)
    # value tiles with NO index streams and dispatches through the
    # dense matmul engines instead of the sparse blocked chain.
    # None = env defaults (SPLATT_DENSE "off" / SPLATT_DENSE_THRESHOLD
    # 0.05); any dense build failure degrades classified to the sparse
    # encoding (format_fallback site=dense), never fails the run.
    dense: Optional[str] = None           # "off" | "auto" | "on"
    dense_threshold: Optional[float] = None

    # Distributed
    decomposition: Decomposition = Decomposition.MEDIUM
    # Row-exchange strategy for the FINE decomposition.  None = env
    # default (SPLATT_COMM, else ALL2ALL) via resolve_comm_pattern —
    # the distributed drivers resolve it once at entry.
    comm_pattern: Optional[CommPattern] = None

    # Structured span tracing (splatt_tpu/trace.py,
    # docs/observability.md): None = env default (SPLATT_TRACE, off);
    # True records host-side spans (cpd → sweep → guard, dispatch,
    # comm) for the Chrome-trace exporter; False pins tracing off for
    # this run even when the process enables it.  Point-event metrics
    # are always on regardless — only span recording is gated.
    trace: Optional[bool] = None

    # Numerics: device compute dtype. None = auto (float32, upgraded to
    # float64 when host data is f64 and x64 is enabled).  An explicit
    # dtype — including an explicit float32 — is respected as-is, so a
    # deliberate f32 run on f64 inputs does not silently double
    # memory/compute.  Host COO stays float64.
    val_dtype: Optional[np.dtype] = None

    def validate(self) -> "Options":
        """Sanity-check option values once, centrally (≙ the reference's
        argp-level validation); returns self for chaining."""
        if self.tolerance < 0:
            raise ValueError(f"tolerance must be >= 0, got {self.tolerance}")
        if self.max_iterations < 0:
            raise ValueError(
                f"max_iterations must be >= 0, got {self.max_iterations}")
        if self.regularization < 0:
            raise ValueError(
                f"regularization must be >= 0, got {self.regularization}")
        if self.fit_check_every < 1:
            raise ValueError(
                f"fit_check_every must be >= 1, got {self.fit_check_every}")
        if self.nnz_block < 1:
            raise ValueError(f"nnz_block must be >= 1, got {self.nnz_block}")
        if not 0 <= self.priv_threshold:
            raise ValueError(
                f"priv_threshold must be >= 0, got {self.priv_threshold}")
        if self.idx_width is not None and self.idx_width not in IDX_WIDTHS:
            raise ValueError(
                f"idx_width must be one of {IDX_WIDTHS}, "
                f"got {self.idx_width!r}")
        if (self.val_storage is not None
                and self.val_storage not in VAL_STORAGES):
            raise ValueError(
                f"val_storage must be one of {VAL_STORAGES}, "
                f"got {self.val_storage!r}")
        if (self.fiber_packing is not None
                and self.fiber_packing not in PACKINGS):
            raise ValueError(
                f"fiber_packing must be one of {PACKINGS}, "
                f"got {self.fiber_packing!r}")
        if self.reorder is not None and self.reorder not in REORDERS:
            raise ValueError(
                f"reorder must be one of {REORDERS}, got {self.reorder!r}")
        if self.dense is not None and self.dense not in DENSE_POLICIES:
            raise ValueError(
                f"dense must be one of {DENSE_POLICIES}, got {self.dense!r}")
        if (self.dense_threshold is not None
                and not 0.0 < self.dense_threshold <= 1.0):
            raise ValueError(
                f"dense_threshold must lie in (0, 1], "
                f"got {self.dense_threshold!r}")
        import jax.numpy as jnp

        if (self.val_dtype is not None
                and not jnp.issubdtype(jnp.dtype(self.val_dtype),
                                       jnp.floating)):
            raise ValueError(
                f"val_dtype must be a floating dtype, got {self.val_dtype}")
        return self

    def seed(self) -> int:
        """Resolve (and pin) the RNG seed.

        A time-based seed is sampled once and stored so every consumer —
        stats header, factor init, reruns — sees the same value (the
        reference stores the time seed into the opts array once,
        src/opts.c).
        """
        if self.random_seed is None:
            import time

            self.random_seed = int(time.time()) & 0x7FFFFFFF
        return int(self.random_seed)


def default_opts() -> Options:
    """≙ splatt_default_opts() (src/opts.c:10-47)."""
    return Options()


_warned_f64 = False


def resolve_dtype(opts: Options, data_dtype=None):
    """Resolve the device compute dtype once, centrally.

    Rules: ``val_dtype=None`` (the default) means auto — float32,
    upgraded to float64 when the host data is f64 and x64 is enabled.
    Any explicit dtype (including explicit float32) is respected as-is.
    float64 without x64 degrades to float32 with ONE clear warning
    instead of a truncation warning at every array construction site.
    """
    import warnings

    import jax

    if opts.val_dtype is None:
        d = np.dtype(np.float32)
        if (data_dtype is not None and np.dtype(data_dtype) == np.float64
                and jax.config.jax_enable_x64):
            d = np.dtype(np.float64)
    else:
        d = np.dtype(opts.val_dtype)
    if d == np.float64 and not jax.config.jax_enable_x64:
        global _warned_f64
        if not _warned_f64:
            warnings.warn(
                "float64 requested but jax x64 is disabled; computing in "
                "float32 (set JAX_ENABLE_X64=1 or "
                "jax.config.update('jax_enable_x64', True) for double)",
                stacklevel=2)
            _warned_f64 = True
        d = np.dtype(np.float32)
    import jax.numpy as jnp

    return jnp.dtype(d)
