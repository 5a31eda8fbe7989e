"""Blocked sparse format — the TPU-native answer to CSF (≙ src/csf.c).

Design (SURVEY §7): CSF's pointer-tree (variable-length fibers,
data-dependent traversal) is hostile to XLA.  The TPU equivalent of
"CSF + chains-on-chains partitioning + cache tiling" is a blocked/padded
layout:

- nonzeros are **sorted by the output mode** (≙ tt_sort + csf mode
  permutation), then segmented into **fixed-size nnz blocks** — equal work
  per block *by construction*, which is exactly what the reference's
  chains-on-chains partitioner (src/thread_partition.c:156-195) achieves
  dynamically for threads;
- each block records the first output row it touches (``row_start``) and
  the layout records the maximum row-span any block covers (``seg_width``)
  — together these let MTTKRP reduce each block with a small one-hot
  matmul on the MXU instead of a scatter (the locked/privatized/tiled
  trichotomy of src/mttkrp.c:104-236 collapses into this);
- indices are padded to a whole number of blocks with a sentinel row
  (= dim) and zero values, keeping every shape static for XLA.

The reference's ONEMODE/TWOMODE/ALLMODE allocation policy
(include/splatt/types_config.h:168-173, src/csf.c:770-814) survives as
"how many sorted layouts do we precompute": a layout sorted for mode k is
the fast path for output mode k and a generic (scatter) path otherwise —
mirroring CSF's root vs. internal/leaf mode traversals.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from splatt_tpu.config import (BlockAlloc, LayoutFormat, Options, Verbosity,
                               default_opts, layout_format, resolve_dtype,
                               resolve_storage_dtype)
from splatt_tpu.coo import SparseTensor
from splatt_tpu.utils.env import ceil_to as _ceil_to

#: short dtype names for format descriptions ("mode0=u16/seg/bf16")
_DTYPE_SHORT = {"float32": "f32", "float64": "f64", "bfloat16": "bf16",
                "float16": "f16"}

#: short integer-dtype names for achieved index widths (signed widths
#: appear under the "delta" encoding)
_IDX_SHORT = {"uint8": "u8", "uint16": "u16", "int8": "i8",
              "int16": "i16", "int32": "i32", "int64": "i64"}


# -- stream-consumer interface ----------------------------------------------
#
# THE single decode vocabulary of the blocked format (docs/format.md):
# every engine — the XLA scatter/segment paths, the scanned-XLA chunk
# decode, the Pallas operand prep, and the ring kernels' index
# widening — consumes a layout's encoded streams through these
# helpers, so a new encoding lands in exactly one place and bit parity
# across engines is by construction.  All are
# pure jnp, shape-polymorphic over leading batch dims, trace-safe and
# donation-safe, and legal inside Pallas kernel bodies (they operate
# on values, not refs).

#: per-mode stream-encoding kinds:
#:   "glob"  — the stream holds global i32 ids (v1; base is None)
#:   "loc"   — narrow local ids; global = local + base[block]
#:   "seg"   — the sorted mode's within-block segment ids (base is
#:             row_start); global = seg + base[block]
#:   "delta" — within-block first-order differences of "loc"; decode
#:             is an exact integer cumulative sum, then + base
#:   "rle"   — per-block (seg_width,) run-length counts replacing the
#:             sorted mode's per-nnz stream; decode expands counts to
#:             nondecreasing segment ids
STREAM_ENCODINGS = ("glob", "loc", "seg", "delta", "rle")


class ModeStreams(NamedTuple):
    """The stream-consumer view of one :class:`ModeLayout`: the raw
    encoded per-mode index streams, their per-block bases (None for
    v1) and the per-mode encoding kinds — what
    :func:`stream_encodings` derives from the layout's static
    ``idx_width`` policy, so consumers dispatch on static strings, not
    array dtypes."""

    streams: tuple                 # per-mode encoded index arrays
    bases: Optional[tuple]         # per-mode (nblocks,) i32, or None
    encs: Tuple[str, ...]          # per-mode STREAM_ENCODINGS kind


def stream_encodings(idx_width: str, mode: int,
                     nmodes: int) -> Tuple[str, ...]:
    """Per-mode stream-encoding kinds for a layout built under
    ``idx_width`` (static — derived from static metadata only)."""
    if idx_width == "i32":
        return ("glob",) * nmodes
    out = []
    for k in range(nmodes):
        if k == mode:
            out.append("rle" if idx_width == "rle" else "seg")
        else:
            out.append("delta" if idx_width == "delta" else "loc")
    return tuple(out)


def widen_ids(arr: jax.Array) -> jax.Array:
    """Widen a stored index stream to the i32 the compute consumes —
    the one sanctioned narrowing boundary (ring kernels and engines
    share it, so a future narrow shard stream flows through the same
    interface)."""
    return arr.astype(jnp.int32)


def decode_gather_ids(arr: jax.Array, base, enc: str) -> jax.Array:
    """Decode one gather-mode chunk ``(..., B)`` to GLOBAL i32 ids.

    `base` must already be broadcastable against the widened stream
    (callers shape it: ``(..., 1)`` per-block columns in the scan
    engine); pass None for
    "glob".  "delta" decodes with an exact integer cumulative sum
    along the block axis — the chunk axis boundary IS the block
    boundary, so chunked consumers need no carry."""
    if enc == "glob":
        return widen_ids(arr)
    ids = widen_ids(arr)
    if enc == "delta":
        ids = jnp.cumsum(ids, axis=-1)
    return ids + base


def rle_expand(counts: jax.Array, block: int) -> jax.Array:
    """Expand per-block run-length counts ``(..., S)`` into the
    nondecreasing within-block segment ids ``(..., block)`` they
    encode: entry j's id is the number of run ENDS at or before j.
    Exact over integers, and monotone by construction — the
    ``indices_are_sorted`` scatter hint stays truthful."""
    ends = jnp.cumsum(widen_ids(counts), axis=-1)        # (..., S)
    iota = jnp.arange(block, dtype=jnp.int32)
    return (iota >= ends[..., None]).astype(jnp.int32).sum(axis=-2)


def decode_segment_ids(arr: jax.Array, enc: str, block: int,
                       row_start=None) -> jax.Array:
    """Decode the sorted mode's chunk to within-block LOCAL segment
    ids ``(..., block)``: "seg" widens the stored ids, "rle" expands
    the count vector, "glob" subtracts the block run start
    (`row_start`, shaped broadcastable like `base` above)."""
    if enc == "rle":
        return rle_expand(arr, block)
    if enc == "glob":
        return widen_ids(arr) - row_start
    return widen_ids(arr)


def decode_global_ids(arr: jax.Array, base, enc: str,
                      block: int) -> jax.Array:
    """Decode one encoded chunk of ANY kind to GLOBAL i32 ids — what a
    consumer gathering a mode it is not sorted by needs (e.g. the
    privatized path reading the sorted mode's segment/RLE stream as a
    gather stream).  "glob" ignores `base`."""
    if enc in ("seg", "rle"):
        return decode_segment_ids(arr, enc, block) + base
    return decode_gather_ids(arr, base, enc)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ModeLayout:
    """One sorted+blocked copy of the nonzeros (≙ one splatt_csf).

    Two encodings share this container (docs/format.md):

    v1 ("i32" index width — the original format):
      inds: (nmodes, nnz_pad) int32 GLOBAL coordinates, sorted by
        ``mode``; pad entries hold ``dim`` for ``mode`` and 0 elsewhere.
      base: None.

    v2 (compact — "auto"/"u16" index width, ≙ the reference's
    configurable splatt_idx_t done per block + CSF fiber compression):
      inds: a TUPLE of per-mode (nnz_pad,) arrays of LOCAL within-block
        indices, each at the narrowest width that fits that mode's
        maximum per-block extent (uint16 when it allows, int32
        otherwise).  The sorted mode's stream holds segment ids against
        the block's run start (row_start) — the output-row coordinate
        is no longer repeated per nonzero at full width.
      base: matching tuple of per-mode (nblocks,) int32 block base
        offsets; ``global = local + base[block]``.  For the sorted
        mode ``base == row_start``.

    Shared:
      vals: (nnz_pad,) values, zero-padded — stored at ``val_storage``
        ("bf16" stores bfloat16, decoded at gather and accumulated in
        f32 via the engines' _acc_dtype path).
      row_start: (nblocks,) int32 — first output row each block touches
        (``dim`` for all-padding blocks).

    Static metadata:
      mode: the output mode this layout is sorted for.
      dim: dims[mode].
      block: nnz per block (B).
      seg_width: S — max output-row span of any block, rounded up to a
        multiple of 8 (f32 sublane); the one-hot reduce is (S×B)@(B×R).
      nnz: true nonzero count (before padding).
      idx_width / val_storage: the REQUESTED format policy this layout
        was built under — what the autotuner's plan matching compares,
        so a plan measured for one encoding never steers another.
    """

    inds: jax.Array
    vals: jax.Array
    row_start: jax.Array
    mode: int = dataclasses.field(metadata=dict(static=True))
    dim: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))
    seg_width: int = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True))
    base: Optional[Tuple[jax.Array, ...]] = None
    idx_width: str = dataclasses.field(default="i32",
                                       metadata=dict(static=True))
    val_storage: str = dataclasses.field(default="auto",
                                         metadata=dict(static=True))
    #: (nblocks,) int32 REAL nonzeros per block, or None for the fixed
    #: packing (real entries are then the first ``nnz`` positions).
    #: Balanced packing (docs/layout-balance.md) pads mid-stream blocks,
    #: so the real-entry mask is per-block, not a prefix.
    block_nnz: Optional[jax.Array] = None
    #: fiber-packing policy this layout was built under ("fixed" |
    #: "balanced") — part of the autotuner plan match, like idx_width
    packing: str = dataclasses.field(default="fixed",
                                     metadata=dict(static=True))
    #: reorder recipe the tensor was relabeled with before this build
    #: ("identity" when none, docs/layout-balance.md) — plan matching
    #: and the demotion scope key both carry it
    reorder: str = dataclasses.field(default="identity",
                                     metadata=dict(static=True))
    #: slice-skew bucket of the sorted mode (nnz_skew_bucket), part of
    #: the autotuner's regime key so plans tuned on uniform inputs
    #: never steer power-law ones ("" = unclassified legacy layout)
    skew: str = dataclasses.field(default="",
                                  metadata=dict(static=True))
    #: mode-density bucket (mode_density_bucket, docs/dense.md) — the
    #: dense-mode analog of `skew` in the autotuner's regime key, so a
    #: plan tuned where dense tiling was a candidate never steers a
    #: genuinely sparse regime ("" = sparse/legacy: keys unchanged)
    density_bucket: str = dataclasses.field(default="",
                                            metadata=dict(static=True))

    @property
    def nnz_pad(self) -> int:
        return int(self.vals.shape[0])

    @property
    def nblocks(self) -> int:
        return int(self.row_start.shape[0])

    @property
    def nmodes(self) -> int:
        # len() covers both the v1 (nmodes, nnz_pad) array and the v2
        # per-mode tuple
        return len(self.inds)

    @property
    def encoding(self) -> str:
        """"v1" (global i32) or "v2" (local narrow + base)."""
        return "v1" if self.base is None else "v2"

    # -- trace-safe decode (the engines' view of the format) ---------------
    #
    # All pure jnp through the module-level stream-consumer helpers
    # (decode_gather_ids / decode_segment_ids): callable inside jitted
    # sweeps (no host sync — SPL003) and under donation (the layout
    # itself is never donated).

    def stream_encs(self) -> Tuple[str, ...]:
        """Per-mode :data:`STREAM_ENCODINGS` kinds (static)."""
        return stream_encodings(self.idx_width if self.base is not None
                                else "i32", self.mode, self.nmodes)

    def mode_ids(self, k: int) -> jax.Array:
        """(nnz_pad,) int32 GLOBAL ids of mode `k` — v1 returns the
        stored stream; the compact encodings decode per block on the
        fly (an XLA elementwise temp fused into the consuming gather,
        not a stored rematerialization)."""
        enc = self.stream_encs()[k]
        if enc == "glob":
            return decode_gather_ids(self.inds[k], None, enc)
        return decode_global_ids(
            self.inds[k].reshape(self.nblocks, -1),
            self.base[k][:, None], enc, self.block).reshape(-1)

    def blocked_locals(self) -> jax.Array:
        """(nblocks, block) int32 within-block ids of the SORTED mode
        — what the one-hot engines contract against.  v2 stores these
        directly (the segment/RLE encodings), so the per-nnz
        subtraction of the v1 path disappears from the hot loop."""
        enc = self.stream_encs()[self.mode]
        return decode_segment_ids(
            self.inds[self.mode].reshape(self.nblocks, -1), enc,
            self.block, row_start=(self.row_start[:, None]
                                   if enc == "glob" else None))

    def mode_streams(self) -> ModeStreams:
        """The :class:`ModeStreams` stream-consumer view — raw encoded
        per-mode index arrays, bases and encoding kinds — for engines
        that decode per scan chunk (ops/mttkrp._scan_fused) instead of
        whole-array."""
        return ModeStreams(
            streams=tuple(self.inds[k] for k in range(self.nmodes)),
            bases=None if self.base is None else tuple(self.base),
            encs=self.stream_encs())

    def real_mask(self) -> np.ndarray:
        """(nblocks, block) bool HOST mask of real (non-pad) entries —
        the fixed packing's reals are the first ``nnz`` positions, the
        balanced packing's are each block's first ``block_nnz[b]``
        slots.  Host-side (encode/stats); the engines never need it
        (pads are additive identities by construction)."""
        nb, B = self.nblocks, self.block
        if self.block_nnz is None:
            real = np.zeros(nb * B, dtype=bool)
            real[:self.nnz] = True
            return real.reshape(nb, B)
        return real_mask_from_counts(B, self.block_nnz)

    def idx_widths(self) -> List[str]:
        """Per-mode stored index width ("u8"/"u16"/"i8"/"i16"/"i32") —
        the ACHIEVED encoding, next to the requested ``idx_width``
        policy (signed widths appear under "delta").  Read from dtypes
        alone: indexing the stacked v1 array would run a device slice
        per mode."""
        dtypes = ([self.inds.dtype] * self.nmodes
                  if hasattr(self.inds, "dtype")
                  else [a.dtype for a in self.inds])
        return [_IDX_SHORT.get(jnp.dtype(d).name, "i32") for d in dtypes]

    def format_desc(self) -> str:
        """Compact achieved-format summary, e.g. ``u16/seg/bf16`` (v2)
        or ``i32/glob/f32`` (v1): index width(s) / mode-row encoding /
        stored value dtype.  The delta/RLE catalog entries name their
        encoding in the middle field (``dlt``/``rle``)."""
        widths = sorted(set(self.idx_widths()))
        idx = widths[0] if len(widths) == 1 else "+".join(widths)
        if self.base is None:
            enc = "glob"
        else:
            enc = {"delta": "dlt", "rle": "rle"}.get(self.idx_width, "seg")
        val = _DTYPE_SHORT.get(jnp.dtype(self.vals.dtype).name,
                               jnp.dtype(self.vals.dtype).name)
        return f"{idx}/{enc}/{val}"

    def storage_bytes(self) -> int:
        """≙ csf_storage (src/csf.c:729-767) — ENCODED bytes: what the
        stored streams actually occupy (narrow v2 indices, per-block
        bases, bf16 values), so bench's bytes/iteration model reflects
        the real format, not a fixed i32/f32 assumption."""
        if self.base is None:
            idx = self.inds.size * self.inds.dtype.itemsize
        else:
            idx = sum(a.size * a.dtype.itemsize for a in self.inds)
            idx += sum(b.size * b.dtype.itemsize for b in self.base)
        if self.block_nnz is not None:
            idx += self.block_nnz.size * self.block_nnz.dtype.itemsize
        return (idx + self.vals.size * self.vals.dtype.itemsize
                + self.row_start.size * self.row_start.dtype.itemsize)

    def __repr__(self) -> str:
        # the EFFECTIVE block and the achieved encoding are
        # load-bearing (build_layout clamps the requested block and may
        # degrade a failed v2 encode to v1), so surface both instead of
        # the dataclass default repr dumping whole device arrays —
        # demotion/tune log lines must distinguish v1 from v2 plans
        extra = "" if self.packing == "fixed" else f", pack={self.packing}"
        if self.reorder != "identity":
            extra += f", reorder={self.reorder}"
        return (f"ModeLayout(mode={self.mode}, dim={self.dim}, "
                f"block={self.block}, seg_width={self.seg_width}, "
                f"nnz={self.nnz}, nnz_pad={self.nnz_pad}, "
                f"nblocks={self.nblocks}, enc={self.encoding}"
                f"[{self.format_desc()}]{extra})")


def secondary_order(dims, mode: int, policy: "ModeOrder" = None,
                    custom=None) -> List[int]:
    """Order of the non-output modes within a layout
    (≙ csf_find_mode_order, src/csf.c:694-726; see ModeOrder for the
    mapping — the output mode is always the primary key here)."""
    from splatt_tpu.config import ModeOrder

    policy = policy or ModeOrder.SMALLFIRST
    others = [m for m in range(len(dims)) if m != mode]
    if policy in (ModeOrder.SMALLFIRST, ModeOrder.SORTED_MINUSONE):
        return sorted(others, key=lambda m: (dims[m], m))
    if policy is ModeOrder.BIGFIRST:
        return sorted(others, key=lambda m: (-dims[m], m))
    if policy is ModeOrder.INORDER_MINUSONE:
        return others
    if policy is ModeOrder.CUSTOM:
        if custom is None:
            raise ValueError("ModeOrder.CUSTOM requires mode_order_custom")
        seq = [m for m in custom if m != mode]
        if sorted(seq) != others:
            raise ValueError(
                f"mode_order_custom {custom!r} is not a permutation "
                f"covering all non-output modes for mode {mode}")
        return seq
    raise ValueError(f"unknown mode order {policy!r}")


def real_mask_from_counts(block: int, counts) -> np.ndarray:
    """(nblocks, block) bool mask of real (non-pad) entries from
    per-block real counts — THE pad contract of the balanced packing
    (each block's reals are its first ``counts[b]`` slots,
    docs/layout-balance.md), defined once so the encoder, the
    build-time stats and :meth:`ModeLayout.real_mask` can never
    disagree about which slots are padding."""
    counts = np.asarray(counts, dtype=np.int64)
    return np.arange(block, dtype=np.int64)[None, :] < counts[:, None]


def nnz_skew_bucket(hist: np.ndarray) -> str:
    """Power-of-two bucket of a mode's slice skew: ``k<n>`` where n =
    bit_length of the max/mean nnz-per-nonempty-slice ratio.  k0/k1 ≈
    uniform, k4+ ≈ power-law.  Coarse on purpose — it extends the
    autotuner's shape regime (tune.shape_regime) so a plan measured on
    a uniform tensor never steers a zipf one, without fragmenting the
    cache per tensor."""
    hist = np.asarray(hist, dtype=np.int64)
    hist = hist[hist > 0]
    if hist.size == 0:
        return "k0"
    # integer counts: numpy's mean over int64 accumulates at f64
    ratio = float(hist.max()) / float(hist.mean())
    return f"k{int(max(ratio, 1.0)).bit_length()}"


def plan_balanced_blocks(rows: np.ndarray, block: int, dim: int,
                         span_caps: Optional[Sequence] = None):
    """nnz-balanced fiber packing of a sorted row stream into fixed-size
    blocks (docs/layout-balance.md).

    The fixed policy cuts the sorted stream every `block` nonzeros, so
    a block landing on a run of tiny fibers can span thousands of
    output rows — and ``seg_width`` (a layout-wide max) then inflates
    the one-hot contraction for EVERY block.  This planner instead cuts
    at fiber boundaries under two caps — the nnz budget ``block`` and a
    row-span cap — padding underfull blocks, and SPLITS any fiber
    hotter than the budget across consecutive blocks (span 1 each); the
    split partials are summed by the same block-level segmented
    reduction that already combines straddling fibers, so no new
    combine step exists (≙ chains-on-chains partitioning +
    p_find_layer_boundaries of the reference; the nnz-balanced binning
    of the GPU load-balancing line, PAPERS.md arXiv 1904.03329).

    The span cap is chosen empirically from a cost model: total one-hot
    work ∝ nblocks(W) x seg_width(W); candidates are powers of two
    (plus uncapped pure-budget packing), cheapest wins.

    Args: rows — (nnz,) nondecreasing sorted-mode row ids; block — nnz
    budget B per block; dim — the mode's dimension.  Returns (starts,
    counts, seg_span): per-block start positions into the sorted
    stream, per-block real-nnz counts (<= B), and the max achieved
    row span.
    """
    nnz = int(rows.shape[0])
    B = int(block)
    if nnz == 0:
        return (np.zeros(1, dtype=np.int64), np.zeros(1, dtype=np.int64), 1)
    rows = np.asarray(rows, dtype=np.int64)
    starts_f = np.flatnonzero(
        np.concatenate([[True], rows[1:] != rows[:-1]]))
    run_rows = rows[starts_f]                       # row id per fiber
    bounds = np.concatenate([starts_f, [nnz]])      # (nfibers + 1,)
    nruns = int(run_rows.shape[0])

    def simulate(W, materialize=False, max_blocks=None):
        # Cut rule: a block fills to its full B budget — splitting the
        # straddling fiber, which adds NO rows to either block — unless
        # the span cap closes it first at a fiber boundary.  W=None is
        # therefore exactly the fixed slicing (the balance baseline);
        # tighter caps trade padding (only where runs of distinct tiny
        # fibers hit the cap) for span.  `max_blocks` aborts a cap the
        # MIN_FILL floor will discard anyway (fill can no longer reach
        # it) — without this, a tight cap over ~1-nnz-per-row data
        # walks a Python loop step per ~W nonzeros at full-tensor
        # scale just to produce a plan the floor rejects.
        pos = 0
        nb = 0
        max_span = 1
        out_starts = [] if materialize else None
        out_counts = [] if materialize else None
        while pos < nnz:
            row0 = int(rows[pos])
            # furthest position the span cap allows: the start of the
            # first fiber whose row falls outside [row0, row0 + W)
            if W is None:
                e_span = nnz
            else:
                rj = int(np.searchsorted(run_rows, row0 + W, side="left"))
                e_span = int(bounds[rj]) if rj < nruns else nnz
            # e_span > pos always: the fiber at pos has row row0 < row0+W
            end = min(pos + B, e_span)
            nb += 1
            if max_blocks is not None and nb > max_blocks:
                return None, None  # infeasible: fill cannot reach the floor
            max_span = max(max_span, int(rows[end - 1]) - row0 + 1)
            if materialize:
                out_starts.append(pos)
                out_counts.append(end - pos)
            pos = end
        if materialize:
            return (np.asarray(out_starts, dtype=np.int64),
                    np.asarray(out_counts, dtype=np.int64), max_span)
        return nb, max_span

    if span_caps is None:
        fixed_span = int(rows[-1]) - int(rows[0]) + 1
        # None (pure fiber-aligned budget packing, fewest blocks) first
        # and caps descending: on a cost TIE the fewer-block plan wins
        # — same one-hot MACs, less index/value padding traffic
        caps, W = [None], 8
        while W < min(fixed_span, dim if dim > 0 else 1):
            caps.insert(1, W)
            W *= 2
    else:
        caps = list(span_caps)
    # Feasibility floor: blocks must stay >= MIN_FILL full — the
    # balance CONTRACT (max/mean real nnz per block <= ~1.1, since
    # max = B and mean = fill x B) and the bytes bound (padding
    # inflates every stream by < 1/MIN_FILL).  A span cap so tight
    # that runs of 1-nnz fibers leave blocks mostly padding is
    # infeasible, however small its one-hot work looks — the padded
    # gather/Hadamard lanes and the inflated streams would eat the
    # win.  Within the feasible caps, minimize the one-hot work:
    # blocks x (padded span + a per-block overhead pricing the B-wide
    # pad-lane traffic).
    MIN_FILL = 0.91
    # a cap producing more blocks than this can never meet the floor;
    # W=None is exempt (fewest blocks possible — it IS the fallback)
    feasible_nb = int(nnz / (MIN_FILL * B)) + 1
    best_cap, best_cost, best_fill_cap, best_fill = None, None, None, -1.0
    for W in caps:
        nb, span = simulate(
            W, max_blocks=None if W is None else feasible_nb)
        if nb is None:
            continue  # aborted: provably under the fill floor
        fill = nnz / float(nb * B)
        if fill > best_fill:
            best_fill_cap, best_fill = W, fill
        if fill < MIN_FILL:
            continue
        cost = nb * (_ceil_to(min(span, dim if dim > 0 else 1), 8) + 8)
        if best_cost is None or cost < best_cost:
            best_cap, best_cost = W, cost
    if best_cost is None:
        # no cap meets the fill floor (pathological fiber sizes, or a
        # block budget dwarfing the tensor): take the fullest plan —
        # balance degrades toward the fixed slicing, never below it
        best_cap = best_fill_cap
    return simulate(best_cap, materialize=True)


def _delta_width(delta: np.ndarray):
    """Narrowest signed numpy dtype holding every within-block delta
    (the "delta" catalog entry's achieved width): i8 on smooth index
    runs, i16/i32 as the jump range grows — fiber-boundary resets are
    large negative deltas, so the worst jump sets the width."""
    lo = int(delta.min()) if delta.size else 0
    hi = int(delta.max()) if delta.size else 0
    for width in (np.int8, np.int16):
        info = np.iinfo(width)
        if info.min <= lo and hi <= info.max:
            return width
    return np.int32


def _encode_rle(loc: np.ndarray, seg_width: int, block: int) -> np.ndarray:
    """Run-length encode the sorted mode's (nblocks, block) segment ids
    into per-block (seg_width,) COUNT vectors — the bitmap/RLE hybrid
    for dense-ish blocks (docs/format.md): seg_width counts replace
    block per-nnz entries.  Exactness contract: the ids are
    nondecreasing within each block (the sort + pad-clamp guarantee),
    so the counts' expansion (:func:`rle_expand`) reproduces them
    bit-for-bit; a violation — or a seg_width that would INVERT the
    compression (S > block) — is an encode error the callers degrade
    classified to v1."""
    nb = loc.shape[0]
    if seg_width > block:
        raise ValueError(
            f"idx_width=rle would invert compression: seg_width "
            f"{seg_width} exceeds the block size {block}; use "
            f"idx_width=auto for wide-span layouts")
    if loc.size and np.any(np.diff(loc, axis=1) < 0):
        raise ValueError(
            "idx_width=rle requires nondecreasing within-block segment "
            "ids; the sorted-mode stream is not monotone")
    offs = loc.astype(np.int64) + np.arange(nb, dtype=np.int64)[:, None] \
        * seg_width
    counts = np.bincount(offs.ravel(),
                         minlength=nb * seg_width).reshape(nb, seg_width)
    width = np.uint16 if block <= np.iinfo(np.uint16).max else np.int32
    return counts.astype(width)


def _encode_v2(inds: np.ndarray, row_start: np.ndarray, mode: int,
               block: int, nnz: int, fmt: LayoutFormat,
               real: Optional[np.ndarray] = None,
               seg_width: Optional[int] = None):
    """Encode sorted+padded GLOBAL (nmodes, nnz_pad) int32 coordinates
    into the v2 compact streams: per-mode LOCAL within-block indices at
    the narrowest width that fits (uint16 when the mode's maximum
    per-block extent allows, int32 otherwise — with ``fmt.idx ==
    "u16"`` a non-fitting mode is an encode error) plus per-block int32
    base offsets.  The sorted mode's base IS its run start, so its
    stream holds segment ids (docs/format.md).

    ``fmt.idx == "u8"`` additionally narrows the SORTED mode's
    segment-id stream to uint8 (ROADMAP open item 2: block spans are
    ≤16 at production density, so the per-nnz row coordinate shrinks to
    ONE byte); a block whose span exceeds 255 is an encode error,
    degraded classified to v1 by the callers — the other modes keep the
    "auto" u16/i32 widths (their extents are block-offset ranges, not
    segment spans).

    ``fmt.idx == "delta"`` stores the GATHER modes' local streams as
    within-block first-order differences at the narrowest signed width
    that fits (:func:`_delta_width`; decode is one exact per-block
    cumulative sum — :func:`decode_gather_ids`), the sorted mode
    keeping its "auto" segment ids.  ``fmt.idx == "rle"`` replaces the
    sorted mode's per-nnz segment stream with per-block
    ``(seg_width,)`` run-length counts (:func:`_encode_rle`; decode is
    :func:`rle_expand`), the gather modes keeping "auto" widths —
    `seg_width` is required for it.

    Pad entries decode to harmless rows (their values are zero): the
    sorted mode's pads clamp to the block's last real segment id —
    keeping the decoded stream nondecreasing for the
    ``indices_are_sorted`` scatter hint — and other modes' pads decode
    to the block base.
    """
    nmodes, nnz_pad = inds.shape
    nb = nnz_pad // block
    u8_max = int(np.iinfo(np.uint8).max)
    u16_max = int(np.iinfo(np.uint16).max)
    if real is None:
        # fixed packing: real entries are the stream prefix.  Balanced
        # layouts pad mid-stream blocks, so callers pass the per-block
        # mask (ModeLayout.real_mask) instead.
        real = np.zeros(nnz_pad, dtype=bool)
        real[:nnz] = True
        real = real.reshape(nb, block)
    else:
        real = np.asarray(real, dtype=bool).reshape(nb, block)
    any_pad = not real.all()
    locs, bases = [], []
    for k in range(nmodes):
        rows = inds[k].reshape(nb, block)
        if k == mode:
            base = row_start.astype(np.int32).copy()
        else:
            masked = np.where(real, rows, np.iinfo(np.int32).max)
            base = masked.min(axis=1)
            base[base == np.iinfo(np.int32).max] = 0
            base = base.astype(np.int32)
        loc = rows - base[:, None]
        if any_pad:
            if k == mode:
                # clamp pads to the block's max real segment id (0 for
                # all-pad blocks, whose base is already the sentinel)
                maxloc = np.where(real, loc, 0).max(axis=1)
                loc = np.where(real, loc, maxloc[:, None])
            else:
                loc = np.where(real, loc, 0)
        if k == mode and fmt.idx == "rle":
            if seg_width is None:
                raise ValueError("idx_width=rle requires the layout's "
                                 "seg_width at encode time")
            locs.append(_encode_rle(loc, int(seg_width), block))
            bases.append(base)
            continue
        if k != mode and fmt.idx == "delta":
            delta = np.diff(loc, axis=1, prepend=0)
            locs.append(delta.reshape(-1).astype(_delta_width(delta)))
            bases.append(base)
            continue
        extent = int(loc.max()) if loc.size else 0
        if fmt.idx == "u16" and extent > u16_max:
            raise ValueError(
                f"idx_width=u16 requested but mode {k}'s maximum "
                f"per-block extent {extent} exceeds uint16; use "
                f"idx_width=auto (which falls back to int32 per mode)")
        if fmt.idx == "u8" and k == mode and extent > u8_max:
            raise ValueError(
                f"idx_width=u8 requested but the sorted mode's maximum "
                f"block span {extent} exceeds uint8; use idx_width=auto "
                f"(u16/i32 segment ids)")
        if fmt.idx == "u8" and k == mode:
            width = np.uint8
        else:
            width = np.uint16 if extent <= u16_max else np.int32
        locs.append(loc.reshape(-1).astype(width))
        bases.append(base)
    return locs, bases


def _pack_balanced(sinds: np.ndarray, svals: np.ndarray, mode: int,
                   block: int, dim: int, val_dtype):
    """Materialize the balanced packing of an already-sorted nonzero
    stream: padded (nmodes, nblocks*block) global int32 indices, vals,
    row_start and per-block real counts (docs/layout-balance.md).

    Pad slots are additive identities placed to keep every engine
    contract truthful: the sorted mode's pads repeat the block's LAST
    real row (the global stream stays nondecreasing for
    ``indices_are_sorted``, and the one-hot matches a lane whose value
    is zero), other modes' pads point at row 0 with value 0.
    """
    nmodes, nnz = sinds.shape
    starts, counts, span = plan_balanced_blocks(sinds[mode], block, dim)
    nb = int(starts.shape[0])
    offs = np.arange(block, dtype=np.int64)[None, :]
    sel = starts[:, None] + offs                      # (nb, B) positions
    valid = offs < counts[:, None]
    take = np.clip(np.where(valid, sel, 0), 0, max(nnz - 1, 0)).reshape(-1)
    mask = valid.reshape(-1)
    inds = sinds[:, take].astype(np.int32)
    last_row = sinds[mode][starts + counts - 1]       # (nb,) last real row
    for k in range(nmodes):
        pad_val = np.repeat(last_row, block) if k == mode else 0
        inds[k] = np.where(mask, inds[k], pad_val)
    vals = np.where(mask, svals[take], 0).astype(np.dtype(val_dtype))
    row_start = sinds[mode][starts].astype(np.int32)
    return inds, vals, row_start, counts.astype(np.int32), span


def _record_imbalance(mode: int, packing: str, block: int, seg_width: int,
                      hist: np.ndarray, counts: np.ndarray,
                      spans: np.ndarray, nnz: int, verbose: bool) -> None:
    """One ``layout_imbalance`` run-report event per layout build: the
    achieved balance of the layout (max/mean real nnz per block and
    row span per block), the input's slice skew, and the one-hot work
    amplification (padded MACs per real nonzero) — the quantities the
    balanced packing exists to improve, made observable next to the
    plan (``splatt cpd --json`` / bench carry them)."""
    from splatt_tpu import resilience

    from splatt_tpu.utils.env import max_mean_ratio as max_mean

    hist = hist[hist > 0]
    counts = np.asarray(counts)
    spans = np.asarray(spans)
    work_amp = (len(counts) * seg_width * block / max(nnz, 1))
    resilience.run_report().add(
        "layout_imbalance", mode=mode, packing=packing, block=block,
        seg_width=seg_width, nblocks=len(counts),
        slice_max_mean=max_mean(hist),
        block_nnz_max_mean=max_mean(counts),
        span_max_mean=max_mean(spans),
        work_amp=round(work_amp, 2))
    if verbose:
        print(f"  layout mode{mode} [{packing}]: block nnz max/mean "
              f"{max_mean(counts)}, span max/mean {max_mean(spans)}, "
              f"seg_width {seg_width}, one-hot work x{work_amp:.1f}/nnz")


def build_layout(tt: SparseTensor, mode: int, block: int = 4096,
                 val_dtype=np.float32, mode_order=None,  # splint: ignore[SPL005] signature default mirroring the reference's val_t; callers override via Options.val_dtype
                 mode_order_custom=None, verbose: bool = False,
                 fmt: Optional[LayoutFormat] = None,
                 packing: str = "fixed",
                 reorder_label: str = "identity",
                 record_stats: bool = True,
                 dense: Optional[bool] = None):
    """Sort, block and pad the tensor for output mode `mode`.

    ≙ csf_alloc's sort + fiber build (src/csf.c:613-726); the secondary
    mode ordering follows `mode_order` (default SMALLFIRST,
    ≙ csf_find_mode_order).  The block a caller (or the autotuner)
    requests may be clamped to the tensor size; the override is
    recorded in the run report (and printed when `verbose`) and the
    effective block is what :class:`ModeLayout` reports.

    `fmt` picks the encoding (docs/format.md): the default v1 global
    int32, or the compact v2 local-index/segment encoding.  A v2
    encode that fails (the ``format.encode`` fault site, or a forced
    u16 that does not fit) degrades CLASSIFIED to v1 — recorded as a
    ``format_fallback`` run-report event, never a failed build.

    `packing` picks the block-cut policy (docs/layout-balance.md):
    "fixed" slices the sorted stream every `block` nonzeros; "balanced"
    bin-packs fibers by nnz weight with long-fiber splitting, bounding
    each block's row span.  A failed balanced pack (the ``layout.pack``
    fault site) degrades CLASSIFIED to the fixed slicing
    (``packing_fallback`` event) — never a failed build.
    `reorder_label` stamps the relabeling recipe the caller applied
    before this build (plan matching and demotion scoping carry it).

    `dense` picks the dense tile layout (docs/dense.md): True forces
    it, False forbids it, None consults the SPLATT_DENSE policy and
    the per-mode density verdict.  A dense build that fails (the
    ``format.dense`` fault site, infeasible geometry, a blowup past
    the cap) degrades CLASSIFIED to this sparse build — recorded as a
    ``format_fallback`` event with ``site="dense"``, never a failed
    build — so the return type is ModeLayout unless the dense tiling
    actually lands (then :class:`DenseModeLayout`).
    """
    nmodes, nnz = tt.nmodes, tt.nnz
    from splatt_tpu.utils.env import check_int32_dims

    check_int32_dims(tt.dims)
    fmt = (fmt or LayoutFormat()).validate()
    if packing not in ("fixed", "balanced"):
        raise ValueError(f"unknown packing {packing!r}")

    if dense is None:
        from splatt_tpu.config import (Options, resolve_dense,
                                       resolve_dense_threshold)
        pol = resolve_dense(Options())
        dense = (pol != "off" and dense_mode_verdict(
            tt.dims, mode, nnz, resolve_dense_threshold(Options()),
            force=(pol == "on")))
    if dense:
        from splatt_tpu import resilience
        from splatt_tpu.utils import faults

        try:
            faults.maybe_fail("format.dense")
            return build_dense_layout(tt, mode, val_dtype=val_dtype,
                                      reorder_label=reorder_label,
                                      verbose=verbose)
        except Exception as e:
            # a failed dense tiling must degrade the BUILD, not kill
            # it: classify, report, fall through to the sparse build
            # every engine can always consume
            cls = resilience.classify_failure(e)
            resilience.run_report().add(
                "format_fallback", mode=mode, site="dense",
                idx_width="dense", failure_class=cls.value,
                error=resilience.failure_message(e)[:200])
            if verbose:
                print(f"  layout mode{mode}: dense tiling failed "
                      f"({cls.value}); falling back to the sparse "
                      f"encoding")
    others = secondary_order(tt.dims, mode, mode_order, mode_order_custom)
    order = [mode] + others
    perm = tt.sort_order(order)
    dim = tt.dims[mode]
    hist = tt.mode_histogram(mode)
    skew = nnz_skew_bucket(hist)

    # Don't let the block dwarf a small tensor: clamp to the padded nnz
    # count (kept a multiple of 128 for lane alignment).
    requested = int(block)
    block = max(128, min(block, _ceil_to(max(nnz, 1), 128)))
    if block != requested:
        # a silent override of a caller-requested block made the
        # effective plan unobservable (ISSUE 3 satellite): record it —
        # with the requested format, so clamp/demotion/tune log lines
        # distinguish v1 from v2 plans
        from splatt_tpu import resilience

        resilience.run_report().add("block_clamp", mode=mode,
                                    requested=requested, effective=block,
                                    nnz=nnz, idx_width=fmt.idx,
                                    val_storage=fmt.val)
        if verbose:
            print(f"  layout mode{mode} [{fmt.idx}/{fmt.val}]: requested "
                  f"nnz_block {requested} clamped to {block} (nnz={nnz})")

    block_nnz = None
    if packing == "balanced" and nnz > 0:
        from splatt_tpu import resilience
        from splatt_tpu.utils import faults

        try:
            faults.maybe_fail("layout.pack")
            sinds = tt.inds[:, perm].astype(np.int64)
            svals = np.asarray(tt.vals)[perm]
            inds, vals, row_start, block_nnz, span = _pack_balanced(
                sinds, svals, mode, block, dim, val_dtype)
            nblocks = int(row_start.shape[0])
        except Exception as e:
            # a failed balanced pack must degrade the BUILD, not kill
            # it: classify, report, fall back to the fixed slicing
            cls = resilience.classify_failure(e)
            resilience.run_report().add(
                "packing_fallback", mode=mode, failure_class=cls.value,
                error=resilience.failure_message(e)[:200])
            if verbose:
                print(f"  layout mode{mode}: balanced packing failed "
                      f"({cls.value}); falling back to fixed slicing")
            packing, block_nnz = "fixed", None
    elif packing == "balanced":
        packing = "fixed"  # empty tensor: nothing to balance

    if block_nnz is None:
        nnz_pad = max(block, _ceil_to(nnz, block))
        nblocks = nnz_pad // block
        inds = np.zeros((nmodes, nnz_pad), dtype=np.int32)
        inds[:, :nnz] = tt.inds[:, perm]
        inds[mode, nnz:] = dim  # sentinel row for padding
        vals = np.zeros(nnz_pad, dtype=np.dtype(val_dtype))
        vals[:nnz] = tt.vals[perm]
        rows = inds[mode].reshape(nblocks, block)
        row_start = rows[:, 0].astype(np.int32)
        span = int((rows[:, -1] - rows[:, 0]).max()) + 1 if nnz else 1
    # Padding sentinels in the last real block can inflate its span; the
    # one-hot simply never matches those lanes (vals are zero anyway), so
    # clamp to the widest span a block of real rows can have.
    seg_width = _ceil_to(min(span, dim if dim > 0 else 1), 8)

    if record_stats:
        # the autotuner's candidate builds skip this (record_stats=
        # False): dozens of throwaway layouts per tune would bury the
        # production builds' balance evidence in the run report
        rows_b = inds[mode].reshape(nblocks, block)
        counts_b = (np.asarray(block_nnz) if block_nnz is not None
                    else np.minimum(np.maximum(
                        nnz - block * np.arange(nblocks), 0), block))
        # spans over REAL entries only (each block's reals are its
        # prefix under both packings): pad sentinels carry row id
        # `dim`, which would inflate the reported span by orders of
        # magnitude on a tensor occupying a small prefix of its index
        # space — imbalance() masks the same way, and the two
        # advertised-as-identical stats must agree
        realm = real_mask_from_counts(block, counts_b)
        hi = np.where(realm, rows_b, -1).max(axis=1)
        lo = np.where(realm, rows_b, dim).min(axis=1)
        spans_b = np.where(counts_b > 0, hi - lo + 1, 1)
        _record_imbalance(mode, packing, block, seg_width, hist, counts_b,
                          np.minimum(spans_b, dim if dim > 0 else 1), nnz,
                          verbose)

    statics = dict(mode=mode, dim=dim, block=block, seg_width=seg_width,
                   nnz=nnz, packing=packing, reorder=reorder_label,
                   skew=skew,
                   density_bucket=mode_density_bucket(tt.dims, mode, nnz))
    bnz = None if block_nnz is None else jnp.asarray(block_nnz)
    if fmt.v2:
        from splatt_tpu import resilience
        from splatt_tpu.utils import faults

        try:
            faults.maybe_fail("format.encode")
            real = None
            if block_nnz is not None:
                real = real_mask_from_counts(block, block_nnz)
            locs, bases = _encode_v2(inds, row_start, mode, block, nnz,
                                     fmt, real=real, seg_width=seg_width)
            return ModeLayout(
                inds=tuple(jnp.asarray(l) for l in locs),
                vals=jnp.asarray(vals),
                row_start=jnp.asarray(row_start),
                base=tuple(jnp.asarray(b) for b in bases),
                idx_width=fmt.idx, val_storage=fmt.val,
                block_nnz=bnz, **statics)
        except Exception as e:
            # a failed v2 encode must degrade the BUILD, not kill it:
            # classify, report, and fall through to the v1 encoding the
            # engines can always consume
            cls = resilience.classify_failure(e)
            resilience.run_report().add(
                "format_fallback", mode=mode, idx_width=fmt.idx,
                failure_class=cls.value,
                error=resilience.failure_message(e)[:200])
            if verbose:
                print(f"  layout mode{mode}: v2 ({fmt.idx}) encode failed "
                      f"({cls.value}); falling back to the v1 i32 "
                      f"encoding")

    return ModeLayout(
        inds=jnp.asarray(inds),
        vals=jnp.asarray(vals),
        row_start=jnp.asarray(row_start),
        idx_width="i32",
        val_storage=fmt.val,
        block_nnz=bnz,
        **statics,
    )


def reencode_layout(layout: ModeLayout, fmt: LayoutFormat,
                    val_dtype=None, dense: bool = False,
                    dims: Optional[Sequence[int]] = None):
    """Re-encode an existing v1 layout under `fmt` (and optionally a
    new stored value dtype) WITHOUT re-sorting — the autotuner derives
    its format candidates from one sorted build per (mode, block)
    instead of paying the host sort per candidate.  Same degradation
    contract as :func:`build_layout`: a failed v2 encode (the
    ``format.encode`` fault site) returns the v1 layout, classified
    into the run report.

    `dense` re-encodes to the dense tile layout instead (docs/dense.md;
    requires `dims`, the full tensor extents a single-mode layout does
    not store) — a failed dense tiling (the ``format.dense`` fault
    site) degrades to the `fmt` re-encode under the same classified
    ``format_fallback`` contract, with ``site="dense"``."""
    fmt = fmt.validate()
    if layout.encoding != "v1":
        raise ValueError("reencode_layout expects a v1 source layout")
    if dense:
        from splatt_tpu import resilience
        from splatt_tpu.utils import faults

        if dims is None:
            raise ValueError("dense re-encode needs the tensor dims")
        try:
            faults.maybe_fail("format.dense")
            return densify_layout(layout, dims, val_dtype=val_dtype)
        except Exception as e:
            cls = resilience.classify_failure(e)
            resilience.run_report().add(
                "format_fallback", mode=layout.mode, site="dense",
                idx_width="dense", failure_class=cls.value,
                error=resilience.failure_message(e)[:200])
    vals = (layout.vals if val_dtype is None
            else layout.vals.astype(val_dtype))
    if not fmt.v2:
        return dataclasses.replace(layout, vals=vals, idx_width="i32",
                                   val_storage=fmt.val)
    from splatt_tpu import resilience
    from splatt_tpu.utils import faults

    try:
        faults.maybe_fail("format.encode")
        locs, bases = _encode_v2(np.asarray(layout.inds),
                                 np.asarray(layout.row_start),
                                 layout.mode, layout.block, layout.nnz,
                                 fmt, real=layout.real_mask(),
                                 seg_width=layout.seg_width)
        return dataclasses.replace(
            layout, vals=vals,
            inds=tuple(jnp.asarray(l) for l in locs),
            base=tuple(jnp.asarray(b) for b in bases),
            idx_width=fmt.idx, val_storage=fmt.val)
    except Exception as e:
        cls = resilience.classify_failure(e)
        resilience.run_report().add(
            "format_fallback", mode=layout.mode, idx_width=fmt.idx,
            failure_class=cls.value,
            error=resilience.failure_message(e)[:200])
        return dataclasses.replace(layout, vals=vals, idx_width="i32",
                                   val_storage=fmt.val)


def decode_to_v1(layout: ModeLayout) -> ModeLayout:
    """Materialize a compact layout's GLOBAL-i32 v1 form — the
    degrade target of the ``format.decode`` fault site: when native
    stream consumption fails at dispatch, the run continues on the v1
    path every engine can always consume (slower bytes, never a failed
    run).  Pure device compute through :meth:`ModeLayout.mode_ids`
    (the same stream-consumer decode the engines run), so the result
    is bit-identical to the in-kernel decode by construction."""
    if layout.encoding == "v1":
        return layout
    inds = jnp.stack([layout.mode_ids(k) for k in range(layout.nmodes)])
    return dataclasses.replace(layout, inds=inds, base=None,
                               idx_width="i32")


# -- dense-mode tile layout (docs/dense.md) ----------------------------------
#
# A mode whose fiber density crosses the threshold stops paying index
# traffic entirely: its unfolding X_(m) is stored as dense (tile, span)
# value tiles — NO index streams at all — and MTTKRP becomes the matmul
# X_(m) @ KR(other factors), the one shape the MXU is built for
# (GenTen's dense-MTTKRP line, PAPERS.md).  Column c of the unfolding
# linearizes the non-output modes row-major in ascending mode order
# with the LAST one fastest; the inner mode's extent is padded to the
# 128-lane boundary (pad columns hold zero values and the KR operand
# is zero there by construction — see dense_operands in ops/mttkrp.py),
# so the tiles feed the MXU without any re-layout.

#: the feasibility floor: a dense tiling whose PADDED cells exceed this
#: multiple of nnz is refused even under dense="on" — materializing a
#: 64x blowup through a skinny inner mode is never a win
DENSE_BLOWUP_CAP = 64


class DenseGeometry(NamedTuple):
    """Tile geometry of one mode's dense unfolding — derived
    deterministically from (dims, mode), never stored, so the layout's
    static metadata stays minimal and build/dispatch cannot disagree.
    """

    others: Tuple[int, ...]   # non-output modes, ascending
    inner: int                # fastest-varying (last) other mode
    n_outer: int              # prod of the remaining other dims (>= 1)
    inner_pad: int            # dims[inner] padded to the 128-lane tile
    tile: int                 # output rows per tile (8-sublane multiple)
    ntiles: int               # row tiles (ntiles * tile >= dim)
    span: int                 # columns per tile = n_outer * inner_pad
    cells: int                # padded cells = ntiles * tile * span


def dense_tile_geometry(dims: Sequence[int],
                        mode: int) -> Optional[DenseGeometry]:
    """The (tile, span) geometry of mode `mode`'s dense unfolding, or
    None when the mode cannot be tiled (fewer than two modes, or an
    empty dim)."""
    dims = tuple(int(d) for d in dims)
    others = tuple(k for k in range(len(dims)) if k != mode)
    if not others or min(dims, default=0) < 1:
        return None
    inner = others[-1]
    n_outer = 1
    for k in others[:-1]:
        n_outer *= dims[k]
    inner_pad = _ceil_to(dims[inner], 128)
    dim = dims[mode]
    tile = min(_ceil_to(dim, 8), 256)
    ntiles = -(-dim // tile)
    span = n_outer * inner_pad
    return DenseGeometry(others=others, inner=inner, n_outer=n_outer,
                         inner_pad=inner_pad, tile=tile, ntiles=ntiles,
                         span=span, cells=ntiles * tile * span)


def mode_density(dims: Sequence[int], mode: int, nnz: int) -> float:
    """True per-mode density: nnz / (prod of other dims x dim) — the
    fill fraction of the mode's unfolding (docs/dense.md)."""
    total = 1
    for d in dims:
        total *= max(int(d), 1)
    return float(nnz) / float(max(total, 1))


def padded_mode_density(dims: Sequence[int], mode: int,
                        nnz: int) -> float:
    """Density over the PADDED tile space — what the dense verdict is
    judged on: a mode whose inner dim pads 3 -> 128 looks 42x sparser
    here than :func:`mode_density` says, which is exactly the blowup
    the tiling would pay."""
    geo = dense_tile_geometry(dims, mode)
    if geo is None:
        return 0.0
    return float(nnz) / float(max(geo.cells, 1))


def mode_density_bucket(dims: Sequence[int], mode: int, nnz: int) -> str:
    """Power-of-two bucket of a mode's padded density: ``dn<n>`` where
    n = bit_length of 1/density — dn1 means more than half full, dn5 ≈
    the 5% regime.  "" below ~3% (or infeasible geometry): sparse modes
    keep their legacy plan keys byte-identical, the nnz_skew_bucket
    convention (tune.plan_key carries this next to the skew bucket)."""
    pd = padded_mode_density(dims, mode, nnz)
    if pd <= 1.0 / 32.0:
        return ""
    return f"dn{int(1.0 / pd).bit_length()}"


def dense_mode_verdict(dims: Sequence[int], mode: int, nnz: int,
                       threshold: float, force: bool = False) -> bool:
    """Whether mode `mode` should be stored as dense tiles: the padded
    density meets `threshold`, and the geometry is feasible (two+
    modes, padded cells within :data:`DENSE_BLOWUP_CAP` x nnz).
    `force` (the dense="on" policy) skips the threshold but keeps the
    feasibility floor."""
    geo = dense_tile_geometry(dims, mode)
    if geo is None or nnz < 1:
        return False
    if geo.cells > DENSE_BLOWUP_CAP * nnz:
        return False
    return force or padded_mode_density(dims, mode, nnz) >= threshold


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseModeLayout:
    """The dense tile layout of one mode (docs/dense.md): the mode's
    unfolding as (ntiles, tile, span) value tiles plus a (span,) pad
    mask — no index streams at all, so the encoded-bytes model carries
    ZERO index bytes for this mode.

    tiles: (ntiles, tile, span) values at the resolved storage dtype
      (bf16-capable, f32 accumulation in the engines); pad rows/columns
      hold zero.
    mask: (span,) bool — True at REAL unfolding columns (False at the
      inner mode's 128-lane pad columns).  The engines never read it
      on the hot path (the KR operand is zero at pad columns because
      the inner factor is zero-padded); stats/tests recover real
      entries through it.

    The static metadata mirrors :class:`ModeLayout`'s plan-matching
    surface (block/idx_width/val_storage/packing/reorder properties)
    so the autotuner's strict match and the per-shape demotion keys
    treat dense plans uniformly — idx_width reads "dense", block is
    the row tile.
    """

    tiles: jax.Array
    mask: jax.Array
    mode: int = dataclasses.field(metadata=dict(static=True))
    dims: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True))
    val_storage: str = dataclasses.field(default="auto",
                                         metadata=dict(static=True))
    reorder: str = dataclasses.field(default="identity",
                                     metadata=dict(static=True))
    density_bucket: str = dataclasses.field(default="",
                                            metadata=dict(static=True))

    @property
    def dim(self) -> int:
        return int(self.dims[self.mode])

    @property
    def nmodes(self) -> int:
        return len(self.dims)

    @property
    def geometry(self) -> DenseGeometry:
        return dense_tile_geometry(self.dims, self.mode)

    @property
    def tile(self) -> int:
        return int(self.tiles.shape[1])

    @property
    def ntiles(self) -> int:
        return int(self.tiles.shape[0])

    @property
    def span(self) -> int:
        return int(self.tiles.shape[2])

    # -- the plan-matching surface shared with ModeLayout ------------------

    @property
    def encoding(self) -> str:
        return "dense"

    @property
    def block(self) -> int:
        """The row tile plays nnz_block's role in plan matching and
        the per-shape demotion keys."""
        return self.tile

    @property
    def idx_width(self) -> str:
        return "dense"

    @property
    def packing(self) -> str:
        return "fixed"

    @property
    def skew(self) -> str:
        return ""

    def density(self) -> float:
        return mode_density(self.dims, self.mode, self.nnz)

    def index_bytes(self) -> int:
        """ZERO by construction — the point of the format."""
        return 0

    def value_bytes(self) -> int:
        return self.tiles.size * self.tiles.dtype.itemsize

    def storage_bytes(self) -> int:
        return self.value_bytes() + self.mask.size * self.mask.dtype.itemsize

    def format_desc(self) -> str:
        val = _DTYPE_SHORT.get(jnp.dtype(self.tiles.dtype).name,
                               jnp.dtype(self.tiles.dtype).name)
        return f"dense/t{self.tile}/{val}"

    def __repr__(self) -> str:
        extra = ("" if self.reorder == "identity"
                 else f", reorder={self.reorder}")
        return (f"DenseModeLayout(mode={self.mode}, dim={self.dim}, "
                f"tile={self.tile}x{self.span}, ntiles={self.ntiles}, "
                f"nnz={self.nnz}, density={self.density():.3g}{extra})")


def build_dense_layout(tt: SparseTensor, mode: int, val_dtype=None,
                       reorder_label: str = "identity",
                       verbose: bool = False) -> DenseModeLayout:
    """Materialize mode `mode`'s unfolding as dense value tiles.

    Raises on infeasible geometry or a blowup past
    :data:`DENSE_BLOWUP_CAP` — callers own the classified degrade to
    the sparse encoding (the ``format.dense`` fault site contract:
    :func:`build_layout` / :meth:`BlockedSparse.from_coo`).  Duplicate
    coordinates accumulate (np.add.at), matching the scatter-add
    semantics of every sparse engine."""
    from splatt_tpu.config import (Options, host_staging_dtype,
                                   resolve_dtype, resolve_storage_dtype)
    from splatt_tpu.utils.env import check_int32_dims

    check_int32_dims(tt.dims)
    if val_dtype is None:
        val_dtype = resolve_dtype(Options())
    geo = dense_tile_geometry(tt.dims, mode)
    if geo is None:
        raise ValueError(
            f"mode {mode} of dims {tuple(tt.dims)} cannot be dense-tiled "
            f"(need two+ nonempty modes)")
    if geo.cells > DENSE_BLOWUP_CAP * max(tt.nnz, 1):
        raise ValueError(
            f"dense tiling of mode {mode} would materialize {geo.cells} "
            f"padded cells for {tt.nnz} nonzeros (> {DENSE_BLOWUP_CAP}x "
            f"blowup); keeping the sparse encoding")
    stage = host_staging_dtype(val_dtype)
    arr = np.zeros((geo.ntiles * geo.tile, geo.n_outer, geo.inner_pad),
                   dtype=stage)
    if tt.nnz:
        inds = np.asarray(tt.inds, dtype=np.int64)
        if len(geo.others) > 1:
            outer_lin = np.ravel_multi_index(
                [inds[k] for k in geo.others[:-1]],
                [tt.dims[k] for k in geo.others[:-1]])
        else:
            outer_lin = np.zeros(tt.nnz, dtype=np.int64)
        np.add.at(arr, (inds[mode], outer_lin, inds[geo.inner]),
                  np.asarray(tt.vals, dtype=stage))
    mask = np.zeros((geo.n_outer, geo.inner_pad), dtype=bool)
    mask[:, :tt.dims[geo.inner]] = True
    lay = DenseModeLayout(
        tiles=jnp.asarray(arr.reshape(geo.ntiles, geo.tile, geo.span)
                          ).astype(jnp.dtype(val_dtype)),
        mask=jnp.asarray(mask.reshape(-1)),
        mode=mode, dims=tuple(int(d) for d in tt.dims), nnz=tt.nnz,
        val_storage=("bf16" if jnp.dtype(val_dtype)
                     == resolve_storage_dtype("bf16", val_dtype)
                     else "auto"),
        reorder=reorder_label,
        density_bucket=mode_density_bucket(tt.dims, mode, tt.nnz))
    if verbose:
        print(f"  layout mode{mode}: dense tiles {geo.ntiles}x{geo.tile}"
              f"x{geo.span} (density {lay.density():.3g}, zero index "
              f"bytes)")
    return lay


def densify_layout(layout: ModeLayout, dims: Sequence[int],
                   val_dtype=None) -> DenseModeLayout:
    """Dense re-encoding of an existing sorted layout WITHOUT re-sorting
    the COO — the :func:`reencode_layout` dense hook: real coordinates
    are recovered through the stream-consumer decode (mode_ids +
    real_mask), so the result is identical to a fresh
    :func:`build_dense_layout` of the same tensor.  `dims` supplies the
    other modes' extents (a ModeLayout only stores its own)."""
    from splatt_tpu.config import host_acc_dtype, host_staging_dtype

    real = layout.real_mask().reshape(-1)
    inds = np.stack([np.asarray(layout.mode_ids(k))
                     for k in range(layout.nmodes)])[:, real]
    stage = host_staging_dtype(layout.vals.dtype)
    vals = np.asarray(jnp.asarray(layout.vals, stage))[real]
    tt = SparseTensor(inds=inds.astype(np.int64),
                      vals=vals.astype(host_acc_dtype()),
                      dims=tuple(int(d) for d in dims))
    return build_dense_layout(
        tt, layout.mode,
        val_dtype=(val_dtype if val_dtype is not None
                   else layout.vals.dtype),
        reorder_label=layout.reorder)


@dataclasses.dataclass
class BlockedSparse:
    """A set of per-mode layouts + the mode→layout assignment.

    ≙ splatt_csf[] + the workspace mode map (splatt_mttkrp_alloc_ws,
    src/mttkrp.c:1814-1912).
    """

    layouts: List[ModeLayout]
    mode_map: Dict[int, int]          # output mode -> index into layouts
    dims: Tuple[int, ...]
    nnz: int
    opts: Options
    #: the relabeling applied before the layouts were built (None =
    #: identity; docs/layout-balance.md).  Factors computed over this
    #: BlockedSparse live in RELABELED row space — cpd_als restores
    #: original order on output via Permutation.undo_factors.
    perm: Optional[object] = None     # reorder.Permutation
    reorder: str = "identity"         # the recipe perm was computed by

    @property
    def nmodes(self) -> int:
        return len(self.dims)

    def layout_for(self, mode: int) -> ModeLayout:
        return self.layouts[self.mode_map[mode]]

    def storage_bytes(self) -> int:
        return sum(l.storage_bytes() for l in self.layouts)

    def format_summary(self) -> str:
        """One-line achieved-format summary per build mode, e.g.
        ``mode0=u16/seg/bf16 mode1=u16/seg/bf16`` — what bench and the
        CLI print so the plan a run executed is observable."""
        parts = []
        for i, lay in enumerate(self.layouts):
            parts.append(f"mode{lay.mode}={lay.format_desc()}")
        return " ".join(parts)

    def imbalance(self) -> Dict[str, dict]:
        """Per-build-mode achieved-balance stats recomputed from the
        layouts (host copies — bench-time cost): real nnz per block and
        row span per block as max/mean, plus the one-hot work
        amplification.  The same quantities ``layout_imbalance``
        events record at build time (docs/layout-balance.md)."""
        out = {}
        for lay in self.layouts:
            if getattr(lay, "encoding", "v1") == "dense":
                # dense tile layouts have no nnz stream to balance
                continue
            real = lay.real_mask()
            counts = np.count_nonzero(real, axis=1)
            # mode_ids is the stream-consumer decode shared with the
            # engines (identity for v1, local+base / RLE expansion for
            # the compact encodings) — only the sorted mode's decoded
            # stream crosses to host
            rows = np.asarray(lay.mode_ids(lay.mode)).reshape(
                lay.nblocks, lay.block).astype(np.int64)
            rows = np.where(real, rows, rows.min(axis=1, keepdims=True))
            spans = np.minimum(rows.max(axis=1) - rows.min(axis=1) + 1,
                               lay.dim if lay.dim > 0 else 1)

            from splatt_tpu.utils.env import max_mean_ratio as mm

            out[f"mode{lay.mode}"] = dict(
                packing=lay.packing, nblocks=lay.nblocks,
                seg_width=lay.seg_width,
                block_nnz_max_mean=mm(counts),
                span_max_mean=mm(spans),
                work_amp=round(lay.nblocks * lay.seg_width * lay.block
                               / max(lay.nnz, 1), 2))
        return out

    @staticmethod
    def from_coo(tt: SparseTensor, opts: Optional[Options] = None,
                 tuned_blocks: Optional[Dict[int, int]] = None,
                 tuned_formats: Optional[Dict[int, LayoutFormat]] = None,
                 tuned_packings: Optional[Dict[int, str]] = None,
                 reorder_label: str = "identity",
                 tuned_dense: Optional[Dict[int, bool]] = None
                 ) -> "BlockedSparse":
        """Compile a COO tensor into blocked layouts per the alloc policy.

        ≙ splatt_csf_alloc (src/csf.c:770-814):
        - ONEMODE: one layout, sorted for the smallest mode;
        - TWOMODE (default): smallest mode + largest mode (≙ smallest-first
          CSF + leaf-rooted CSF, src/csf.c:787-803);
        - ALLMODE: one per mode.
        Every mode maps to its own layout when one exists, else to the
        first layout (generic path).

        `tuned_blocks` (mode -> nnz_block, from the autotuner's plan
        cache) overrides ``opts.nnz_block`` per build mode — the layout
        is built once at the tuned block instead of rebuilt when the
        plan disagrees with the default.  `tuned_formats` does the same
        for the encoding (index width; docs/format.md).
        :meth:`compile` fills both in.

        Value STORAGE is resolved once for the whole tensor (every
        layout must share one dtype — the CPD driver derives its
        factor dtype from it): the explicit/env policy wins, else a
        unanimous tuned-format verdict.
        """
        from splatt_tpu.config import (resolve_dense,
                                       resolve_dense_threshold,
                                       resolve_packing)

        opts = (opts or default_opts()).validate()
        nmodes = tt.nmodes
        tuned_blocks = dict(tuned_blocks or {})
        tuned_formats = dict(tuned_formats or {})
        tuned_packings = dict(tuned_packings or {})
        tuned_dense = dict(tuned_dense or {})
        fmt_default = layout_format(opts)
        packing_default = resolve_packing(opts)
        # one storage dtype across layouts: pinned policy > unanimous
        # tuned verdict > compute dtype
        val_pol = fmt_default.val
        if val_pol == "auto" and tuned_formats:
            verdicts = {f.val for f in tuned_formats.values()}
            if len(verdicts) == 1:
                val_pol = verdicts.pop()
        # a plan whose storage verdict cannot follow the resolved
        # policy (non-unanimous modes, or a pinned knob overriding it)
        # is dropped WHOLE — building its block/idx_width at a storage
        # it was never measured with would make a configuration
        # dispatch then silently rejects (_tuned_plan_for's strict
        # match).  Observable, not silent: tuner_degraded per mode.
        dropped = [m for m, f in tuned_formats.items() if f.val != val_pol]
        if dropped:
            from splatt_tpu import resilience

            for m in sorted(dropped):
                tuned_formats.pop(m)
                tuned_blocks.pop(m, None)
                tuned_packings.pop(m, None)
                resilience.run_report().add(
                    "tuner_degraded", mode=m,
                    reason=f"tuned val_storage could not apply under "
                           f"the resolved storage policy {val_pol!r}; "
                           f"mode keeps the default format and the "
                           f"heuristic chain")
        storage = resolve_storage_dtype(val_pol,
                                        resolve_dtype(opts, tt.vals.dtype))
        # one selection rule shared with the distributed cell/shard
        # layout builders — they must never desynchronize
        from splatt_tpu.parallel.common import alloc_build_modes

        build_modes = alloc_build_modes(tt.dims, opts)

        layouts = [build_layout(
                       tt, m,
                       block=tuned_blocks.get(m, opts.nnz_block),
                       val_dtype=storage,
                       mode_order=opts.mode_order,
                       mode_order_custom=opts.mode_order_custom,
                       verbose=opts.verbosity >= Verbosity.LOW,
                       fmt=LayoutFormat(
                           idx=tuned_formats[m].idx if m in tuned_formats
                           else fmt_default.idx,
                           val=val_pol),
                       packing=tuned_packings.get(m, packing_default),
                       reorder_label=reorder_label,
                       dense=False)
                   for m in build_modes]
        mode_map = {}
        for m in range(nmodes):
            mode_map[m] = build_modes.index(m) if m in build_modes else 0
        # hybrid per-mode dispatch (docs/dense.md): a mode whose tuned
        # plan says path=="dense", or whose fiber density crosses the
        # policy threshold, gets a dense tile layout APPENDED and its
        # mode_map entry remapped — the sparse layouts above stay
        # intact, so a dense build failure degrades to an
        # already-built sparse path, never a failed compile.  A tuned
        # dense verdict wins regardless of the env policy (tuned wins,
        # the tuned_blocks precedent).
        pol = resolve_dense(opts)
        thr = resolve_dense_threshold(opts)
        for m in range(nmodes):
            want = tuned_dense.get(m)
            if want is None:
                want = (pol != "off"
                        and dense_mode_verdict(tt.dims, m, tt.nnz,
                                               threshold=thr,
                                               force=(pol == "on")))
            if not want:
                continue
            from splatt_tpu import resilience
            from splatt_tpu.utils import faults

            try:
                faults.maybe_fail("format.dense")
                dl = build_dense_layout(
                    tt, m, val_dtype=storage,
                    reorder_label=reorder_label,
                    verbose=opts.verbosity >= Verbosity.LOW)
            except Exception as e:
                cls = resilience.classify_failure(e)
                resilience.run_report().add(
                    "format_fallback", mode=m, site="dense",
                    idx_width="dense", failure_class=cls.value,
                    error=resilience.failure_message(e)[:200])
                if opts.verbosity >= Verbosity.LOW:
                    print(f"  layout mode{m}: dense tiling failed "
                          f"({cls.value}); mode keeps the sparse "
                          f"encoding")
                continue
            mode_map[m] = len(layouts)
            layouts.append(dl)
        bs = BlockedSparse(layouts=layouts, mode_map=mode_map,
                           dims=tt.dims, nnz=tt.nnz, opts=opts,
                           reorder=reorder_label)
        if (any(l.encoding in ("v2", "dense") for l in layouts)
                or val_pol != "auto"):
            # the chosen encoding is part of the executed plan: record
            # it (docs/format.md) like tuned_plan records dispatch
            from splatt_tpu import resilience

            resilience.run_report().add(
                "format_v2",
                modes={str(l.mode): l.format_desc() for l in layouts})
            if opts.verbosity >= Verbosity.LOW:
                print(f"  format: {bs.format_summary()}")
        return bs

    @staticmethod
    def compile(tt: SparseTensor, opts: Optional[Options] = None,
                rank: Optional[int] = None) -> "BlockedSparse":
        """:meth:`from_coo` + autotune: consult the tuner's plan cache
        (splatt_tpu/tune.py) for each mode's winning ``nnz_block`` AND
        encoding (index width / value storage — docs/format.md) AND
        layout-balance axes (fiber packing / reorder recipe —
        docs/layout-balance.md) and build the layouts at them directly.
        `rank` keys the plan lookup (the winning configuration is
        rank-dependent); without it, or with autotune off, this is
        plain :meth:`from_coo` under the pinned/env policies.

        Reorder resolution is WHOLE-TENSOR (one permutation relabels
        every mode — the factors are shared across the per-mode
        layouts, so per-mode recipes cannot mix): a pinned policy
        (``Options.reorder`` / SPLATT_REORDER) wins, else a unanimous
        tuned verdict, else identity; plans whose recipe cannot apply
        are dropped WHOLE with a ``tuner_degraded`` event (the
        val_storage precedent).  The permutation is computed and
        applied under the ``reorder.apply`` fault site and ANY failure
        degrades CLASSIFIED to identity order (``reorder_fallback``
        event) — a bad reorder heuristic can cost speed, never the
        run.  The resulting :class:`BlockedSparse` carries the
        :class:`Permutation` so cpd_als restores original factor row
        order on output."""
        from splatt_tpu.config import resolve_reorder

        opts = (opts or default_opts()).validate()
        tuned_blocks = {}
        tuned_formats = {}
        tuned_packings = {}
        plans = {}
        if rank is not None:
            from splatt_tpu import tune

            if tune.autotune_enabled(opts.autotune):
                plans = tune.tuned_build_for(
                    tt, rank, resolve_dtype(opts, tt.vals.dtype))
        how = resolve_reorder(opts)
        if how is None:
            verdicts = {p.reorder for p in plans.values()}
            how = verdicts.pop() if len(verdicts) == 1 else "identity"
        dropped = [m for m, p in plans.items() if p.reorder != how]
        if dropped:
            from splatt_tpu import resilience

            for m in sorted(dropped):
                plans.pop(m)
                resilience.run_report().add(
                    "tuner_degraded", mode=m,
                    reason=f"tuned reorder recipe could not apply under "
                           f"the resolved whole-tensor recipe {how!r}; "
                           f"mode keeps the default layout policy")
        # a pinned fiber-packing policy beats a cached tuned verdict
        # (same precedence val_storage and reorder enforce above):
        # plans measured under the other policy are dropped WHOLE —
        # their block/idx_width was never measured at the pinned
        # packing, and dispatch's strict match would reject them anyway
        from splatt_tpu.config import packing_pinned

        pinned_pack = packing_pinned(opts)
        if pinned_pack is not None:
            dropped_p = [m for m, p in plans.items()
                         if p.packing != pinned_pack]
            if dropped_p:
                from splatt_tpu import resilience

                for m in sorted(dropped_p):
                    plans.pop(m)
                    resilience.run_report().add(
                        "tuner_degraded", mode=m,
                        reason=f"tuned fiber packing could not apply "
                               f"under the pinned policy "
                               f"{pinned_pack!r}; mode keeps the "
                               f"default layout policy")
        perm = None
        if how != "identity":
            from splatt_tpu.reorder import apply_reorder

            tt, perm = apply_reorder(tt, how)
            if perm is None:
                # classified degrade inside apply_reorder: the recipe
                # could not apply, so plans MEASURED under it must go
                # too (dropped WHOLE, the val_storage precedent) —
                # half-building their block/format at identity order
                # would execute a configuration the tuner never
                # measured and dispatch's strict match then rejects
                failed = how
                how = "identity"
                stale = [m for m, p in plans.items()
                         if p.reorder != "identity"]
                if stale:
                    from splatt_tpu import resilience

                    for m in sorted(stale):
                        plans.pop(m)
                        resilience.run_report().add(
                            "tuner_degraded", mode=m,
                            reason=f"tuned plan was measured under "
                                   f"reorder {failed!r}, which degraded "
                                   f"to identity; mode keeps the "
                                   f"default layout policy")
        # dense-path plans (docs/dense.md) leave the sparse build
        # matrix entirely: their "idx_width" is the sentinel "dense"
        # (not a LayoutFormat), their block is the dense row tile —
        # from_coo appends a dense tile layout for those modes instead
        tuned_dense = {m: True for m, p in plans.items()
                       if p.path == "dense"}
        sparse_plans = {m: p for m, p in plans.items()
                        if p.path != "dense"}
        tuned_blocks = {m: p.nnz_block for m, p in sparse_plans.items()}
        tuned_formats = {m: LayoutFormat(idx=p.idx_width,
                                         val=p.val_storage)
                         for m, p in sparse_plans.items()}
        tuned_packings = {m: p.packing for m, p in sparse_plans.items()}
        bs = BlockedSparse.from_coo(tt, opts, tuned_blocks=tuned_blocks,
                                    tuned_formats=tuned_formats,
                                    tuned_packings=tuned_packings,
                                    reorder_label=how,
                                    tuned_dense=tuned_dense)
        bs.perm = perm
        return bs

    def frobsq(self) -> float:
        """Squared Frobenius norm (≙ csf_frobsq, src/csf.c:828-851).

        Accumulated in f64 on host so both cpd_als drivers (COO via
        coo.normsq, blocked via this) share the same ⟨X,X⟩ to full
        precision — at 77M+ nnz an f32 accumulation loses digits in the
        fit denominator.  (bf16-stored values upcast first: numpy's dot
        has no bfloat16 kernel.)
        """
        v = np.asarray(self.layouts[0].vals).astype(np.float64)  # splint: ignore[SPL005] host-side frobsq upcasts to f64 BEFORE the reduce by design
        return float(np.dot(v, v))


# -- batched fleets (docs/batched.md) ----------------------------------------
#
# The million-tenant shape: MANY small same-regime tensors, each too
# small to amortize its own compile.  K slots are padded to the
# regime's bucket shape and stacked along a leading batch axis so ONE
# jitted vmapped sweep serves all of them — per-slot semantics
# (independent fits, independent health verdicts) ride the batch axis
# as data, never as control flow.


def bucket_dims(dims: Sequence[int]) -> Tuple[int, ...]:
    """The regime's padded bucket shape: each mode padded to the
    power of two just above its :func:`splatt_tpu.tune.shape_regime`
    bucket (``1 << bit_length``), so every tensor in one regime pads
    to the SAME static shape and a later batch of that regime reuses
    the jit cache — one compile across batches, not just within one."""
    return tuple(1 << int(d).bit_length() for d in dims)


def bucket_nnz_pad(nnz: int, block: int) -> int:
    """The regime's padded nnz count: the nnz bucket (``1 <<
    bit_length``) rounded up to whole blocks — shared by every slot
    of every batch in the regime, for the same jit-reuse reason."""
    return _ceil_to(1 << int(max(nnz, 1)).bit_length(), block)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchedBlocked:
    """K same-regime tensors stacked into one static-shape batch.

    Each slot is built through :func:`build_layout` (the same sort /
    block / pad / clamp machinery every single-tensor run uses) at a
    COMMON configuration — one sort mode, one block, v1 global-i32
    index streams (per-slot narrow v2 widths would differ across
    slots and cannot stack), one value-storage dtype (bf16 supported:
    factors derive from it and accumulate f32 exactly like the
    single-tensor sweep) — then padded to the regime bucket shape and
    stacked.  Pad entries are additive identities by the same sentinel
    policy as ModeLayout: zero values, sorted-mode ids at the slot's
    true ``dim`` (a padded row), zeros elsewhere.
    """

    inds: jax.Array               # (K, nmodes, nnz_pad) int32 GLOBAL ids
    vals: jax.Array               # (K, nnz_pad) storage dtype, zero-pad
    dims: Tuple[int, ...] = dataclasses.field(
        default=(), metadata=dict(static=True))     # bucket (padded) dims
    slot_dims: Tuple[Tuple[int, ...], ...] = dataclasses.field(
        default=(), metadata=dict(static=True))     # true per-slot dims
    slot_nnz: Tuple[int, ...] = dataclasses.field(
        default=(), metadata=dict(static=True))
    sort_mode: int = dataclasses.field(default=0,
                                       metadata=dict(static=True))
    block: int = dataclasses.field(default=4096,
                                   metadata=dict(static=True))
    regime: str = dataclasses.field(default="",
                                    metadata=dict(static=True))
    val_storage: str = dataclasses.field(default="auto",
                                         metadata=dict(static=True))

    @property
    def k(self) -> int:
        return int(self.vals.shape[0])

    @property
    def nmodes(self) -> int:
        return len(self.dims)

    @property
    def nnz_pad(self) -> int:
        return int(self.vals.shape[1])

    def slot_frobsq(self) -> np.ndarray:
        """(K,) per-slot squared Frobenius norms, f64 host
        accumulation like :meth:`BlockedSparse.frobsq` (pads are zero,
        so whole-row dots equal real-entry dots)."""
        from splatt_tpu.config import host_acc_dtype

        v = np.asarray(self.vals).astype(host_acc_dtype())
        return np.einsum("kz,kz->k", v, v)

    def __repr__(self) -> str:
        return (f"BatchedBlocked(k={self.k}, dims={self.dims}, "
                f"nnz_pad={self.nnz_pad}, block={self.block}, "
                f"sort_mode={self.sort_mode}, regime={self.regime!r}, "
                f"val={jnp.dtype(self.vals.dtype).name})")


def batch_compile(tensors: Sequence[SparseTensor],
                  opts: Optional[Options] = None,
                  rank: Optional[int] = None) -> BatchedBlocked:
    """Stack K same-regime COO tensors into one :class:`BatchedBlocked`.

    Every slot must share one :func:`splatt_tpu.tune.shape_regime`
    (the coalescing precondition serve enforces before dispatching a
    batch — docs/batched.md); a mixed-regime batch raises ValueError.
    The block consults the autotuner's plan cache once for the whole
    batch (:func:`splatt_tpu.tune.batched_block_for` — the batch axis
    is part of the plan key, so a batched verdict never steers
    single-tensor dispatch and vice versa).
    """
    from splatt_tpu import tune as _tune

    if not tensors:
        raise ValueError("batch_compile needs at least one tensor")
    opts = (opts or default_opts()).validate()
    nmodes = tensors[0].nmodes
    regime = _tune.shape_regime(tensors[0].dims, tensors[0].nnz)
    for i, tt in enumerate(tensors):
        if tt.nmodes != nmodes:
            raise ValueError(
                f"batch slot {i} has {tt.nmodes} modes, slot 0 has "
                f"{nmodes} — a batch must be mode-count homogeneous")
        r = _tune.shape_regime(tt.dims, tt.nnz)
        if r != regime:
            raise ValueError(
                f"batch slot {i} is in shape regime {r}, slot 0 in "
                f"{regime} — a batch must share one regime "
                f"(docs/batched.md)")
    dims_pad = bucket_dims(tensors[0].dims)
    # one sort mode for every slot: the smallest BUCKET mode (ties to
    # the lowest index) — deterministic across slots by regime equality
    sort_mode = int(np.argmin(np.asarray(dims_pad)))
    # storage dtype: the explicit/env policy, exactly like from_coo
    # (bf16 stores bf16 and the factors/accumulation rules follow)
    fmt = layout_format(opts)
    compute = resolve_dtype(opts, tensors[0].vals.dtype)
    storage = resolve_storage_dtype(fmt.val, compute)
    block = _tune.batched_block_for(
        tensors[0].dims, tensors[0].nnz, sort_mode, rank,
        compute, len(tensors), autotune=opts.autotune)
    if block is None:
        block = opts.nnz_block
    block = max(128, min(int(block),
                         _ceil_to(max(t.nnz for t in tensors), 128)))
    nnz_pad = bucket_nnz_pad(max(t.nnz for t in tensors), block)

    from splatt_tpu.config import host_staging_dtype

    inds = np.zeros((len(tensors), nmodes, nnz_pad), dtype=np.int32)
    vals = np.zeros((len(tensors), nnz_pad),
                    dtype=host_staging_dtype(storage))
    slot_dims = []
    slot_nnz = []
    for i, tt in enumerate(tensors):
        lay = build_layout(tt, sort_mode, block=block, val_dtype=storage,
                           mode_order=opts.mode_order,
                           mode_order_custom=opts.mode_order_custom,
                           fmt=LayoutFormat(idx="i32", val=fmt.val),
                           packing="fixed", record_stats=False,
                           dense=False)
        n = lay.nnz_pad
        for m in range(nmodes):
            inds[i, m, :n] = np.asarray(lay.mode_ids(m))
        # tail padding past the slot's own blocks keeps the layout's
        # sentinel policy: sorted-mode ids at the slot's true dim
        # (dim < bucket always, so the sentinel row is in range and
        # collects only zeros), zeros elsewhere
        inds[i, sort_mode, n:] = tt.dims[sort_mode]
        vals[i, :n] = np.asarray(lay.vals, dtype=vals.dtype)
        slot_dims.append(tuple(tt.dims))
        slot_nnz.append(tt.nnz)
    return BatchedBlocked(
        inds=jnp.asarray(inds),
        vals=jnp.asarray(vals).astype(storage),
        dims=dims_pad, slot_dims=tuple(slot_dims),
        slot_nnz=tuple(slot_nnz), sort_mode=sort_mode, block=block,
        regime=regime, val_storage=fmt.val)
