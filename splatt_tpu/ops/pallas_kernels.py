"""Pallas TPU kernels for the MTTKRP hot path.

The performance-critical reduction in blocked MTTKRP is

    out[b, s, :] = Σ_j  [local[b, j] == s] · prod[b, j, :]

i.e. a per-block one-hot contraction (S×B)@(B×R) — the TPU replacement
for the reference's scattered accumulation with its mutex pool /
privatization / tile scheduling (src/mttkrp.c:104-236).  XLA executes
the same einsum but materializes the one-hot operand (nb·S·B elements)
in HBM; the Pallas kernel builds it on the fly in VMEM with a
broadcasted iota-compare and feeds the MXU directly, so HBM traffic is
just prod in + partials out.

Two variants:
- :func:`onehot_reduce_sorted`  — per-block partials (sorted layouts,
  combined by a small scatter outside);
- :func:`onehot_reduce_full`    — full-width accumulation across the
  whole grid (privatized short modes, no scatter at all).

Both take `interpret=` so the differential tests run on CPU
(≙ tests running the real kernels at 7 threads, tests/mttkrp_test.c).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from splatt_tpu.ops.mttkrp import _acc_dtype, mxu_precision
from splatt_tpu.utils.env import ceil_to

# Max blocks per grid step; the actual chunk is sized against VMEM by
# vmem_chunk() below.
_CHUNK = 8

@functools.cache
def _vmem_limit() -> int:
    """Scoped VMEM the kernels request (splatt_tpu/devices.py): the
    device's own row on a TPU, the kernels' target chip in interpret
    mode."""
    from splatt_tpu.devices import DEVICE_SPECS, KERNEL_TARGET, device_spec

    spec = device_spec() or DEVICE_SPECS[KERNEL_TARGET]
    return spec.vmem_limit


def _vmem_budget() -> int:
    return (_vmem_limit() * 24) // 25


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit())


def vmem_chunk(width: int, block: int, rank: int,
               itemsize: int = 4, budget_bytes: int = None,
               out_itemsize: int = None) -> int:
    """Blocks per grid step such that the kernel's working set —
    one-hot (C,width,block) + prod (C,block,rank) + out (C,width,rank) —
    fits the VMEM budget (_vmem_budget()//2, against the measured 128MiB
    v5e VMEM and the raised _VMEM_LIMIT compiler cap, leaving room for
    double buffering).  The out term is costed at the accumulator
    width (f32 even for bf16 inputs).  Returns 0 when even one block
    does not fit: callers must fall back to the XLA engine, which
    streams the one-hot through HBM instead.
    """
    if budget_bytes is None:
        budget_bytes = _vmem_budget() // 2
    if out_itemsize is None:
        out_itemsize = max(itemsize, 4)
    per_block = ((width * block + block * rank) * itemsize
                 + width * rank * out_itemsize)
    if per_block <= 0:
        return _CHUNK
    return min(_CHUNK, budget_bytes // per_block)


def _sorted_kernel(local_ref, prod_ref, out_ref, *, seg_width: int):
    local = local_ref[:, 0, :]                  # (C, B) int32
    prod = prod_ref[...]                        # (C, B, R)
    C, B = local.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (C, seg_width, B), 1)
    onehot = (local[:, None, :] == iota).astype(prod.dtype)
    out_ref[...] = jax.lax.dot_general(
        onehot, prod,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=out_ref.dtype,
        precision=mxu_precision(prod.dtype))


def _full_kernel(local_ref, prod_ref, out_ref, *, width: int):
    local = local_ref[:, 0, :]                  # (C, B) int32
    prod = prod_ref[...]                        # (C, B, R)
    C, B = local.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (C, width, B), 1)
    onehot = (local[:, None, :] == iota).astype(prod.dtype)
    part = jax.lax.dot_general(
        onehot, prod,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=out_ref.dtype,
        precision=mxu_precision(prod.dtype))    # (C, width, R)
    acc = jnp.sum(part, axis=0)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(pl.program_id(0) != 0)
    def _accum():
        out_ref[...] += acc


def _pad_blocks(local: jax.Array, prod: jax.Array, chunk: int):
    """Pad to whole chunks; local gains a singleton middle dim so its
    Mosaic block shape (chunk, 1, B) is legal for any chunk (the last
    two block dims must divide (8, 128) or equal the array dims)."""
    nb = local.shape[0]
    nb_pad = ceil_to(max(nb, 1), chunk)
    if nb_pad != nb:
        local = jnp.pad(local, ((0, nb_pad - nb), (0, 0)),
                        constant_values=-1)
        prod = jnp.pad(prod, ((0, nb_pad - nb), (0, 0), (0, 0)))
    return local[:, None, :], prod, nb_pad


@functools.partial(jax.jit,
                   static_argnames=("seg_width", "interpret", "chunk"))
def onehot_reduce_sorted(local: jax.Array, prod: jax.Array, seg_width: int,
                         interpret: bool = False,
                         chunk: int = _CHUNK) -> jax.Array:
    """(nb, B) local ids + (nb, B, R) partials → (nb, S, R) block partials."""
    nb = local.shape[0]
    B = local.shape[1]
    R = prod.shape[-1]
    local, prod, nb_pad = _pad_blocks(local, prod, chunk)
    grid = (nb_pad // chunk,)
    out = pl.pallas_call(
        functools.partial(_sorted_kernel, seg_width=seg_width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((chunk, B, R), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, seg_width, R), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, seg_width, R),
                                       _acc_dtype(prod.dtype)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(local, prod)
    return out[:nb]


# -- fused gather + Hadamard + reduce (transposed tables) -------------------
#
# The flagship kernel.  HBM traffic per MTTKRP is inds + vals + block
# partials — the factor tables are VMEM-resident for the whole sweep, so
# the (nnz, R) partial-product tensor of the unfused paths (3.7GB logical,
# 9.5GB after XLA's R→128 lane padding at NELL-2 scale — an HBM OOM)
# never exists anywhere.  ≙ the reference's register-blocked fiber loops
# reading factor rows in-cache (src/mttkrp.c:427-463).
#
# Two Mosaic constraints shape the design (jax 0.9.0):
# - only *same-shaped* take_along_axis gathers lower (tpu.dynamic_gather);
#   an arbitrary B-row gather from a (D, R) table must be phrased as
#   lane-wise take_along_axis on a *transposed* (R, D) table with the
#   request vector padded to D — so per-block gather cost scales with
#   max(B, D), and callers pick block ≈ max other-mode dim to amortize;
# - a (D, R) f32 table in VMEM pads R→128 lanes (14.7MB for NELL-2's
#   28818×50), while the transposed (R, D) form pads R→56 sublanes
#   (6.5MB): transposed tables are what make rank-50 f32 fit at all.
# Gathers run in 8-sublane tiles so temporaries stay ≤ (8, D).

_SUBLANE = 8


def _rank_pad(R: int, dtype) -> int:
    """Rank rows padded to the dtype's NATIVE sublane packing
    (config.tile_packing: 8 sublanes f32, 16 bf16/f16 — splint
    SPL025): the transposed factor tables and (R8, width) outputs tile
    their second-minor axis by rank, and a dtype-blind pad to 8
    under-packs narrow-dtype tiles 2x.  Always a multiple of
    ``_SUBLANE``, so the 8-row gather tiling below still divides it."""
    from splatt_tpu.config import tile_packing

    return ceil_to(int(R), tile_packing(dtype)[0])


def _tile_gather(u_t, gidx, B: int):
    """rows_t = u_t[:, idx] inside a Mosaic kernel, layout-safely.

    u_t: (R8, D) transposed factor table (VMEM-resident), R8 a multiple
    of 8, D of 128.  gidx: (ck, 8, D) int32 — the request vector
    pre-chunked into ck lane-aligned groups of D and replicated across
    8 sublanes *outside* the kernel.  Mosaic's layout inference rejects
    broadcasts/slices whose input carries a nonzero lane offset, so the
    kernel must only read whole aligned tiles: each take_along_axis here
    is the exact same-shaped (8, D) form tpu.dynamic_gather supports,
    and the only slice taken is [:, :B] at offset 0.
    """
    R8, D = u_t.shape
    ck = gidx.shape[0]
    pieces = []
    for c in range(ck):
        idx8 = gidx[c]                       # (8, D), aligned tile
        tiles = [jnp.take_along_axis(u_t[r0:r0 + _SUBLANE, :], idx8, axis=1)
                 for r0 in range(0, R8, _SUBLANE)]
        pieces.append(tiles[0] if len(tiles) == 1
                      else jnp.concatenate(tiles, axis=0))   # (R8, D)
    rows = pieces[0] if ck == 1 else jnp.concatenate(pieces, axis=1)
    return rows[:, :B]


def _fused_t_kernel(local_ref, vals_ref, *refs,
                    width: int, accumulate: bool, nother: int):
    gidx_refs = refs[:nother]
    ut_refs = refs[nother:2 * nother]
    out_ref = refs[2 * nother]
    local = local_ref[0, :, :]               # (1, B) int32
    vals = vals_ref[0, :, :]                 # (1, B)
    B = local.shape[1]
    dtype = vals.dtype
    acc = out_ref.dtype
    prod = vals                              # (1, B), broadcasts up
    for j in range(nother):
        u_t = ut_refs[j][...]                # (R8, D_j) resident in VMEM
        rows_t = _tile_gather(u_t, gidx_refs[j][0], B)     # (R8, B)
        prod = prod * rows_t
    iota = jax.lax.broadcasted_iota(jnp.int32, (width, B), 0)
    onehot = (jnp.broadcast_to(local, (width, B)) == iota).astype(dtype)
    # (R8, B) · (S, B)ᵀ on the MXU → (R8, S) transposed block partials
    part = jax.lax.dot_general(
        prod, onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
        precision=mxu_precision(dtype))
    if not accumulate:
        out_ref[...] = part[None]
        return

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = part

    @pl.when(pl.program_id(0) != 0)
    def _accum():
        out_ref[...] += part


def fused_t_vmem_ok(factors, mode: int, width: int, block: int,
                    budget_bytes: int = None) -> bool:
    """VMEM plan of the transposed-table fused kernel: every input
    factor resident as (R8, D) (R padded to 8 sublanes, D to 128
    lanes), plus per-step working set — the pre-replicated (ck, 8, D)
    index tiles, gathered rows and the accumulating (R8, B) product,
    the (S, B) one-hot, streams and partials.
    """
    if budget_bytes is None:
        budget_bytes = _vmem_budget()
    R = int(factors[0].shape[1])
    r8 = _rank_pad(R, factors[0].dtype)
    itemsize = jnp.dtype(factors[0].dtype).itemsize
    b_pad = ceil_to(block, 128)
    fac = 0
    work = 0
    for k, f in enumerate(factors):
        if k != mode:
            d = ceil_to(int(f.shape[0]), 128)
            ck = -(-b_pad // d)
            fac += r8 * d * itemsize                  # resident table
            # streamed per block -> the pipeline DOUBLE-buffers them
            # (splint SPL026's static model counts streamed specs 2x;
            # single-counting here undersold the true footprint)
            work += 2 * ck * _SUBLANE * d * 4         # replicated idx tiles
            work += r8 * ck * d * itemsize            # gathered rows
    work += (r8 * b_pad * itemsize                    # accumulating product
             + ceil_to(width, _SUBLANE) * b_pad * itemsize   # one-hot
             + r8 * ceil_to(width, 128) * 4                  # partials
             + 2 * 2 * b_pad * 4)                     # local + vals (dbuf)
    return fac + work <= budget_bytes


def _prep_t_operands(layout, factors, mode: int, accumulate: bool):
    """Shared operand prep for the transposed-table fused kernels:
    (local, vals, uts, gidxs) with the sentinel-clamp and lane-chunk
    padding contract in ONE place.

    local/vals: (nb, 1, B).  uts[j]: the (R8, d_pad) transposed,
    zero-padded factor table for the j-th non-target mode.  gidxs[j]:
    (nb, ck, 8, d_pad) gather requests — the per-block index vector
    clamped to d-1 (padding entries carry the out-of-range sentinel
    `dim`; their values are zero so the clamped row is harmless),
    padded to whole d_pad lane chunks, replicated across 8 sublanes
    (the same-shaped take_along_axis form Mosaic lowers).
    """
    nb, B = layout.nblocks, layout.block
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    R8 = _rank_pad(R, dtype)
    others = [k for k in range(layout.nmodes) if k != mode]

    # OPERAND-PREP decode through the stream-consumer interface
    # (blocked.decode_* via mode_ids/blocked_locals): identity reads
    # for v1, trace-fused decodes for the compact encodings — the
    # kernel operands below are i32/compute-dtype either way, so these
    # Mosaic kernels are format-agnostic.  The decoded i32 streams and
    # replicated request tiles DO round-trip HBM here — the traffic
    # bench's decode_overhead prices (docs/format.md)
    if accumulate:
        local = layout.mode_ids(mode).reshape(nb, B)
    else:
        local = layout.blocked_locals()
    vals = layout.vals.reshape(nb, B).astype(dtype)
    local = local[:, None, :]
    vals = vals[:, None, :]

    uts = []
    gidxs = []
    for k in others:
        d = int(factors[k].shape[0])
        d_pad = ceil_to(d, 128)
        u_t = factors[k].T
        uts.append(jnp.pad(u_t, ((0, R8 - R), (0, d_pad - d))))
        ck = -(-B // d_pad)
        idx = jnp.minimum(layout.mode_ids(k), d - 1).reshape(nb, B)
        if ck * d_pad != B:
            idx = jnp.pad(idx, ((0, 0), (0, ck * d_pad - B)))
        gidxs.append(jnp.broadcast_to(idx.reshape(nb, ck, 1, d_pad),
                                      (nb, ck, _SUBLANE, d_pad)))
    return local, vals, uts, gidxs


@functools.partial(jax.jit, static_argnames=("mode", "width", "accumulate",
                                             "interpret"))
def fused_mttkrp_t(layout, factors, mode: int, width: int,
                   accumulate: bool, interpret: bool = False) -> jax.Array:
    """Fused MTTKRP with VMEM-resident transposed factor tables.

    Output: (nb, width, R) block partials (sorted layouts), or
    (width, R) totals when `accumulate` (privatized short modes) —
    (nb, width, R)
    block partials, or (width, R) totals when `accumulate`.
    """
    nb, B = layout.nblocks, layout.block
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    R8 = _rank_pad(R, dtype)
    others = [k for k in range(layout.nmodes) if k != mode]
    grid = (nb,)

    local, vals, uts, gidxs = _prep_t_operands(layout, factors, mode,
                                               accumulate)
    ut_specs = [pl.BlockSpec(u.shape, lambda i: (0, 0)) for u in uts]
    gidx_specs = [pl.BlockSpec((1,) + g.shape[1:], lambda i: (i, 0, 0, 0))
                  for g in gidxs]

    acc = _acc_dtype(dtype)
    if accumulate:
        out_spec = pl.BlockSpec((R8, width), lambda i: (0, 0))
        out_shape = jax.ShapeDtypeStruct((R8, width), acc)
    else:
        out_spec = pl.BlockSpec((1, R8, width), lambda i: (i, 0, 0))
        out_shape = jax.ShapeDtypeStruct((nb, R8, width), acc)

    out = pl.pallas_call(
        functools.partial(_fused_t_kernel, width=width,
                          accumulate=accumulate, nother=len(others)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
            *gidx_specs,
            *ut_specs,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(local, vals, *gidxs, *uts)
    # back to the (…, width, R) contract of the untransposed kernels
    if accumulate:
        return out.T[:, :R]
    return jnp.swapaxes(out, 1, 2)[:, :, :R]


# -- sublane-tiled fused kernel (inner grid over rank tiles) ----------------
#
# Structurally different variant of fused_mttkrp_t (both lower only
# where every gathered table fits one 128-lane vreg — Mosaic's
# take_along_axis limit, tests/test_tpu_compile.py).  This variant:
#   * grid (R8/8, nb) — each instance computes ONE 8-sublane rank tile,
#     so the kernel body holds one take_along_axis per (factor, lane
#     chunk) and no concatenates at all;
#   * only an (8, D) slice of each transposed table is resident per
#     step; the table block index depends only on the rank-tile
#     coordinate, and nb is the fastest grid dimension, so Pallas
#     re-fetches each slice once per rank tile (~R8/8 · ΣD · 32 B per
#     MTTKRP — noise), not once per block;
#   * chunk products accumulate into a VMEM scratch at static
#     128-aligned lane offsets instead of concatenating tiles.
# The VMEM envelope is RANK-independent (only one 8-sublane rank tile
# is live per step) but DIM-linear: the per-step (8, d_pad) table slice
# and index tiles scale with the padded mode dim, so rank-200 configs
# fused_t's whole-table residency gate rejects are covered, while
# mode dims beyond a few hundred thousand still reject (a 10M-row mode
# ⇒ ~960 MB/step) and dispatch falls back to xla_scan.  What rescues
# the Amazon-scale configs is the multi-chip grid: each device sees
# only its grid-LOCAL dims, which shrink by the axis width.

def _fused_tg_kernel(local_ref, vals_ref, *refs,
                     width: int, accumulate: bool, nother: int):
    gidx_refs = refs[:nother]
    ut_refs = refs[nother:2 * nother]
    out_ref = refs[2 * nother]
    prod_ref = refs[2 * nother + 1]          # VMEM scratch (8, B)
    local = local_ref[0, :, :]               # (1, B) int32
    vals = vals_ref[0, :, :]                 # (1, B)
    B = local.shape[1]
    dtype = vals.dtype
    prod_ref[...] = jnp.broadcast_to(vals, (_SUBLANE, B))
    for j in range(nother):
        u_t = ut_refs[j][...]                # (8, D_j) slice of the table
        gidx = gidx_refs[j][0]               # (ck_j, 8, D_j)
        ck, _, D = gidx.shape
        for c in range(ck):
            w = min(B - c * D, D)
            if w <= 0:
                break
            tile = jnp.take_along_axis(u_t, gidx[c], axis=1)   # (8, D_j)
            if w == B and ck == 1:
                prod_ref[...] = prod_ref[...] * tile[:, :B]
            else:
                prod_ref[:, c * D:c * D + w] = (
                    prod_ref[:, c * D:c * D + w] * tile[:, :w])
    iota = jax.lax.broadcasted_iota(jnp.int32, (width, B), 0)
    onehot = (jnp.broadcast_to(local, (width, B)) == iota).astype(dtype)
    # (8, B) · (S, B)ᵀ on the MXU → (8, S) transposed partials tile
    part = jax.lax.dot_general(
        prod_ref[...], onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=out_ref.dtype,
        precision=mxu_precision(dtype))
    if not accumulate:
        out_ref[...] = part[None]
        return

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = part

    @pl.when(pl.program_id(1) != 0)
    def _accum():
        out_ref[...] += part


def fused_tg_vmem_ok(factors, mode: int, width: int, block: int,
                     budget_bytes: int = None) -> bool:
    """VMEM plan of the sublane-tiled kernel — per-step only: (8, D)
    table slices, the replicated index tiles, the (8, B) product
    scratch, one-hot and partials.  ×2 on streamed operands for double
    buffering.  RANK-independent (no whole-table footprint), but
    DIM-linear: the slice/index terms grow with each padded mode dim,
    so very large local dims (≳ a few hundred thousand rows at
    block 4096) correctly reject here and dispatch falls back."""
    if budget_bytes is None:
        budget_bytes = _vmem_budget()
    itemsize = jnp.dtype(factors[0].dtype).itemsize
    b_pad = ceil_to(block, 128)
    work = 0
    for k, f in enumerate(factors):
        if k != mode:
            d = ceil_to(int(f.shape[0]), 128)
            ck = -(-b_pad // d)
            work += 2 * _SUBLANE * d * itemsize        # table slice (dbuf)
            work += 2 * ck * _SUBLANE * d * 4          # replicated idx tiles
    work += (_SUBLANE * b_pad * itemsize               # prod scratch
             + ceil_to(width, _SUBLANE) * b_pad * itemsize   # one-hot
             + _SUBLANE * ceil_to(width, 128) * 4            # partials tile
             + 4 * b_pad * 4)                                # local + vals
    return work <= budget_bytes


@functools.partial(jax.jit, static_argnames=("mode", "width", "accumulate",
                                             "interpret"))
def fused_mttkrp_tg(layout, factors, mode: int, width: int,
                    accumulate: bool, interpret: bool = False) -> jax.Array:
    """Sublane-tiled fused MTTKRP (grid over rank tiles × blocks).

    Same contract as :func:`fused_mttkrp_t`: (nb, width, R) block
    partials, or (width, R) totals when `accumulate`.
    """
    from jax.experimental.pallas import tpu as pltpu

    nb, B = layout.nblocks, layout.block
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    R8 = _rank_pad(R, dtype)  # matches _prep_t_operands' table padding
    n_rtiles = R8 // _SUBLANE
    others = [k for k in range(layout.nmodes) if k != mode]
    grid = (n_rtiles, nb)     # nb fastest: table slices fetched per r-tile

    local, vals, uts, gidxs = _prep_t_operands(layout, factors, mode,
                                               accumulate)
    ut_specs = [pl.BlockSpec((_SUBLANE, u.shape[1]), lambda r, i: (r, 0))
                for u in uts]
    gidx_specs = [pl.BlockSpec((1,) + g.shape[1:],
                               lambda r, i: (i, 0, 0, 0)) for g in gidxs]

    acc = _acc_dtype(dtype)
    if accumulate:
        out_spec = pl.BlockSpec((_SUBLANE, width), lambda r, i: (r, 0))
        out_shape = jax.ShapeDtypeStruct((R8, width), acc)
    else:
        out_spec = pl.BlockSpec((1, _SUBLANE, width), lambda r, i: (i, r, 0))
        out_shape = jax.ShapeDtypeStruct((nb, R8, width), acc)

    out = pl.pallas_call(
        functools.partial(_fused_tg_kernel, width=width,
                          accumulate=accumulate, nother=len(others)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, B), lambda r, i: (i, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda r, i: (i, 0, 0)),
            *gidx_specs,
            *ut_specs,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_SUBLANE, B), dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(local, vals, *gidxs, *uts)
    # back to the (…, width, R) contract of the untransposed kernels
    if accumulate:
        return out.T[:, :R]
    return jnp.swapaxes(out, 1, 2)[:, :, :R]


#: outcome of each capability probe, keyed by kernel name — "ok",
#: "compile_failed", "resource", "infra", or absent if never probed.
#: "infra" means the verdict is *unproven* (a transient or unrecognized
#: failure, not a rejected kernel); "resource" means the probe ran out
#: of memory: a capacity verdict scoped to this (regime, block) shape.
#: Every verdict but "ok" is reported on stderr and in the run report.
PROBE_STATES: dict = {}


# -- persistent capability cache --------------------------------------------
#
# A capability probe costs a compile and its verdict depends only on
# (jax version, device kind, kernel sources, regime, block) — none of
# which change between the processes of one environment.  This cache
# stores proven verdicts ("ok"/"compile_failed", and the shape-scoped
# "resource") on disk; "infra" is stored for reporting but NEVER
# short-circuits a later process — an unproven verdict is retried, not
# inherited.  Every entry additionally expires after a TTL
# (SPLATT_PROBE_CACHE_TTL_S, default 14 days): drivers and libtpu drift
# under a fixed env key, so even a proven verdict is re-earned
# occasionally.

_CACHE_ENV = "SPLATT_PROBE_CACHE"
_CACHE_TTL_ENV = "SPLATT_PROBE_CACHE_TTL_S"
# the default TTL (14 days) lives in utils/env.py:ENV_VARS — the
# single registry the docs and the SPL007 check read


def probe_cache_ttl() -> float:
    """Seconds a cached verdict stays fresh (<= 0 disables expiry)."""
    from splatt_tpu.utils.env import read_env_float

    return read_env_float(_CACHE_TTL_ENV)


def _cache_path():
    import pathlib

    from splatt_tpu.utils.env import read_env

    p = read_env(_CACHE_ENV)
    if p:
        return pathlib.Path(p)
    root = pathlib.Path(__file__).resolve().parents[2]
    # a real repo-checkout marker — the bare existence of a sibling
    # "tools" dir would misfire inside site-packages
    if (root / "pyproject.toml").exists() and (root / "tools").is_dir():
        return root / "tools" / "probe_cache.json"
    return pathlib.Path.home() / ".cache" / "splatt_tpu" / "probe_cache.json"


@functools.cache
def _kernel_src_hash() -> str:
    """Hash of the sources a probe verdict depends on — this module
    plus the layout/tensor builders the probe compiles through
    (blocked.py, coo.py) and the helpers the kernels import from
    ops/mttkrp.py (_acc_dtype, onehot_precision) and utils/env.py
    (ceil_to): editing any of them changes what the probe compiles, so
    it must invalidate every cached verdict — a fixed Mosaic crash is
    re-probed instead of staying disabled behind a stale
    "compile_failed" (and a stale "ok" cannot mask a new rejection)."""
    import hashlib
    import pathlib

    h = hashlib.sha256()
    pkg = pathlib.Path(__file__).resolve().parents[1]
    try:
        for src in (pathlib.Path(__file__), pkg / "blocked.py",
                    pkg / "coo.py", pkg / "config.py",
                    pkg / "ops" / "mttkrp.py", pkg / "utils" / "env.py"):
            h.update(src.read_bytes())
        return h.hexdigest()[:12]
    # splint: ignore[SPL002] sources unreadable (zipped/frozen install):
    # the sentinel keys one shared cache namespace, a safe degradation
    except Exception:
        return "nosrc"


def _cache_env_key() -> str:
    try:
        kind = jax.devices()[0].device_kind
    # splint: ignore[SPL002] device discovery off-accelerator: the
    # cache key degrades to a shared "unknown" namespace
    except Exception:
        kind = "unknown"
    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    # splint: ignore[SPL002] optional-package version probe: absence
    # is a legitimate environment, encoded as "?" in the cache key
    except Exception:
        jl = "?"
    return f"{jax.__version__}|jaxlib{jl}|{kind}|{_kernel_src_hash()}"


def _cache_io_error(op: str, exc) -> None:
    """Report a probe-cache IO failure through the failure taxonomy.

    Cache IO stays best-effort by contract (a broken cache must never
    break dispatch), but the failure used to vanish in a bare
    ``except`` — a cache silently losing every verdict re-spends a
    probe compile per kernel per process, which is exactly the
    silent degradation the run report exists to surface."""
    from splatt_tpu import resilience

    resilience.run_report().add(
        "probe_cache_io_error", op=op,
        failure_class=resilience.classify_failure(exc).value,
        error=resilience.failure_message(exc)[:200])


def _json_cache_load(path, on_error=None):
    """The shared read side of the JSON cache protocol — used by the
    capability-probe cache here and the autotuner's plan cache
    (splatt_tpu/tune.py), and the ONLY sanctioned way to read a shared
    cache file (splint rule SPL011 flags inline ``open`` on cache
    paths): a missing file is the normal first-run path (-> None), any
    other failure is routed to `on_error(op, exc)` (classified into
    the run report) and degrades to None — a broken cache must never
    break dispatch.  Writers use :func:`_json_cache_update`; readers
    need no lock because writes are atomic replaces."""
    import json

    if on_error is None:
        on_error = _cache_io_error
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None  # first run in this environment: nothing cached yet
    except Exception as e:
        on_error("load", e)
        return None


def probe_cache_load(state_key: str):
    """Cached verdict for `state_key` in this environment, or None.
    Returns whatever was stored ("ok"/"compile_failed"/"resource"/
    "timeout"/"infra") — the CALLER decides which states are
    authoritative.  Entries older than :func:`probe_cache_ttl` are
    expired (returned as None) so every verdict, even a proven one, is
    re-earned occasionally on drifting infrastructure."""
    import time

    from splatt_tpu import trace

    data = _json_cache_load(_cache_path())
    if data is None:
        trace.metric_inc("splatt_probe_cache_total", outcome="miss")
        return None
    try:
        entry = data.get(_cache_env_key(), {}).get(state_key)
        if not entry:
            trace.metric_inc("splatt_probe_cache_total", outcome="miss")
            return None
        ttl = probe_cache_ttl()
        if ttl > 0 and time.time() - float(entry.get("ts", 0)) > ttl:
            trace.metric_inc("splatt_probe_cache_total",
                             outcome="expired")
            return None
        trace.metric_inc("splatt_probe_cache_total", outcome="hit")
        return entry["state"]
    except Exception as e:
        # a malformed entry (hand-edited file, schema drift) is an
        # unusable verdict, not a dispatch failure: report and re-probe
        _cache_io_error("load", e)
        return None


#: intra-process serialization of cache writes, complementing the
#: inter-process flock below: concurrent serve jobs (threads in ONE
#: process) tuning simultaneously must not interleave their
#: read-modify-writes.  flock on separate fds does conflict within a
#: process too, but holding a plain Lock makes the thread contract
#: independent of that platform detail and keeps the (open, flock)
#: pair itself race-free.
_JSON_CACHE_THREAD_LOCK = threading.Lock()


def _json_cache_update(path, mutate, on_error=None) -> None:
    """Locked atomic read-modify-write of a small JSON cache file —
    shared by the capability-probe cache here and the autotuner's plan
    cache (splatt_tpu/tune.py).  `mutate(data) -> data` transforms the
    loaded dict (``{}`` when absent/corrupt).  Serialized against other
    processes (flock) AND other threads of this process (concurrent
    serve jobs share the warm caches — docs/serve.md), so two writers
    never drop each other's entries.  Best-effort by contract:
    cache IO must never break dispatch, so every failure is routed to
    `on_error(op, exc)` (classified into the run report) and swallowed.
    """
    import json

    if on_error is None:
        on_error = _cache_io_error
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        # serialize concurrent read-modify-writes (two processes proving
        # different kernels must not drop each other's verdicts)
        import fcntl

        with _JSON_CACHE_THREAD_LOCK, \
                open(str(path) + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                with open(path) as f:
                    data = json.load(f)
            except FileNotFoundError:
                data = {}  # first write creates the file
            except Exception as e:
                # unreadable/corrupt cache: replaced wholesale below —
                # reported, because it drops every other entry
                on_error("store", e)
                data = {}
            data = mutate(data)
            from splatt_tpu.utils.durable import publish_json

            publish_json(path, data, indent=1, sort_keys=True)
    except Exception as e:
        # best-effort by contract (cache IO must never break dispatch):
        # degrade to an uncached probe/plan, but say so in the run report
        on_error("store", e)


def probe_cache_store(state_key: str, state: str) -> None:
    """Record a probe verdict on disk (atomic replace; best-effort —
    cache IO must never break dispatch).  Timestamps let a TPU session
    commit the file as evidence of when each verdict was proven."""
    import time

    env_key = _cache_env_key()
    entry = {"state": state, "ts": time.time()}

    def mutate(data):
        data.setdefault(env_key, {})[state_key] = entry
        return data

    _json_cache_update(_cache_path(), mutate)


#: representative probe shapes per lane-chunk regime.  "ck1": the
#: flagship NELL-like production regime — mode dims in the thousands,
#: a single lane chunk per factor (d_pad >= block), wide gathers, a
#: realistic seg_width (mode-0 indices laid out so each 4096-block
#: spans ~8 rows, like a 20M-nnz tensor's density).  "multick": small
#: mode dims against the same block, so the kernels unroll many lane
#: chunks per factor (ck up to 11) — a regime that can crash Mosaic
#: independently of the ck1 shape.  Probing per regime keeps a crash
#: in one from vetoing the other.
_PROBE_DIMS = {"ck1": (12092, 9184, 28818), "multick": (512, 384, 1024)}


def probe_regime(dims, block: int) -> str:
    """Which probe regime a (dims, block) config falls in: "multick"
    when any factor needs more than one padded lane chunk per block."""
    return ("multick"
            if any(block > ceil_to(int(d), 128) for d in dims)
            else "ck1")


def _probe_case(kernel_fn, regime: str, block: int) -> bool:
    """The probe compile itself — module-level so tests can substitute
    it without touching the thread/deadline/cache machinery around it."""
    import numpy as np

    from splatt_tpu.blocked import build_layout
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(0)
    dims = _PROBE_DIMS[regime]
    nnz = max(8192, 2 * block)
    # scale the probe's rank to the device's VMEM so a capacity
    # rejection on small-VMEM parts (v2/v3: 16 MiB) is never cached
    # as a capability rejection for the whole regime
    rank = 48 if _vmem_limit() >= (32 << 20) else 16
    if regime == "ck1":
        # NELL-like density: each block spans ~8 output rows,
        # giving the production seg_width (~8-16)
        i0 = np.minimum((np.arange(nnz, dtype=np.int64) * 8) // block,
                        dims[0] - 1)
    else:
        # small dims: random rows give the regime's natural wide
        # seg_width (~dims[0]) — the width real multick kernels
        # compile at
        i0 = rng.integers(0, dims[0], nnz)
    inds = np.stack([i0] + [rng.integers(0, d, nnz)
                            for d in dims[1:]])
    tt = SparseTensor(inds=inds.astype(np.int64),
                      vals=np.ones(nnz), dims=dims)
    lay = build_layout(tt, 0, block=block, val_dtype=np.float32,  # splint: ignore[SPL005] probes compile at the production f32 shape to keep one verdict cache
                       dense=False)
    fac = [jnp.zeros((d, rank), jnp.float32) for d in dims]  # splint: ignore[SPL005] probes compile at the production f32 shape to keep one verdict cache
    kernel_fn.lower(lay, fac, mode=0, width=lay.seg_width,
                    accumulate=False, interpret=False).compile()
    return True


def _probe_compiles(kernel_fn, name: str, regime: str = "ck1",
                    block: int = 4096, case=None) -> bool:
    """Whether `kernel_fn(layout, factors, mode, width, accumulate,
    interpret)` COMPILES for this backend at a shape representative of
    `regime` at the CALLER's block size.  Lowering alone is not
    enough: Mosaic layout inference (e.g. the "Invalid input layout"
    broadcast restriction) only runs at compile time.  And a toy shape
    is not enough either: what Mosaic accepts depends on the widths
    (its lane gather lowers only within one 128-lane vreg), so the
    block size is part of the probe key rather than fixed."""
    state_key = f"{name}:{regime}:b{block}"
    if jax.default_backend() != "tpu":
        PROBE_STATES[state_key] = "not_tpu"
        return False

    # Proven verdicts persist across processes ("resource" is proven
    # too, but scoped: the state_key already carries regime+block, so a
    # capacity rejection only gates this shape); "infra" (and a
    # "timeout" left by an older build) does not short-circuit: an
    # unproven verdict is re-probed, never inherited.  Every verdict
    # but "ok" is reported loudly — a probe must never quietly move a
    # run off its Pallas engine.
    cached = probe_cache_load(state_key)
    if cached in ("ok", "compile_failed", "resource"):
        PROBE_STATES[state_key] = cached
        if cached != "ok":
            _report_probe(state_key, cached, "cached verdict")
        return cached == "ok"

    from splatt_tpu import resilience
    from splatt_tpu.utils import faults

    # Failure taxonomy (splatt_tpu.resilience): only a recognized
    # DETERMINISTIC rejection may be persisted as "compile_failed" —
    # the cache makes any misclassification permanent for the whole
    # environment, so the persisted-negative set is a whitelist (Mosaic
    # compiler crash/rejection signatures), not a transient-error
    # blocklist.  TRANSIENT failures are retried in place with capped
    # backoff + jitter and, if they persist, recorded as "infra":
    # rejected for THIS process, re-probed by the next.  RESOURCE
    # failures (OOM/VMEM) are proven capacity verdicts scoped to this
    # (regime, block) shape.

    def attempt():
        faults.maybe_fail("probe_compile")
        # a kernel whose call signature differs from the shared probe
        # case (fused_dense: no width/accumulate) supplies its own
        # `case` callable
        if case is not None:
            return case(kernel_fn, regime, block)
        return _probe_case(kernel_fn, regime, block)

    try:
        state = ("ok" if resilience.retry_transient(attempt,
                                                    label=state_key)
                 else "compile_failed")
        err = None
    except Exception as e:
        cls = resilience.classify_failure(e)
        err = resilience.failure_message(e)[:300]
        if cls is resilience.FailureClass.DETERMINISTIC:
            state = "compile_failed"
        elif cls is resilience.FailureClass.RESOURCE:
            state = "resource"
        else:
            # transient (retries exhausted) or unknown: unproven
            state = "infra"
    PROBE_STATES[state_key] = state
    probe_cache_store(state_key, state)
    if state != "ok":
        _report_probe(state_key, state, err or "probe returned False")
    return state == "ok"


def _report_probe(state_key: str, state: str, why: str) -> None:
    """A probe verdict other than "ok": run-report event + stderr line."""
    import sys

    from splatt_tpu import resilience

    resilience.run_report().add("probe_downgrade", state_key=state_key,
                                verdict=state)
    print(f"splatt-tpu: WARNING: {state_key} capability probe: {state} "
          f"({why}); this engine is skipped", file=sys.stderr, flush=True)


@functools.cache
def fused_t_supported(regime: str = "ck1", block: int = 4096) -> bool:
    """Whether the transposed-table fused kernel compiles here (its
    lane-wise same-shape take_along_axis gather is the form Mosaic
    supports on jax 0.9.0), probed per (lane-chunk regime, block)."""
    return _probe_compiles(fused_mttkrp_t, "fused_t", regime, block)


@functools.cache
def fused_tg_supported(regime: str = "ck1", block: int = 4096) -> bool:
    """Whether the sublane-tiled fused kernel compiles here (one
    take_along_axis per factor×chunk, no concatenates, scratch-store
    accumulation — the shape Mosaic is most likely to accept), probed
    per (lane-chunk regime, block)."""
    return _probe_compiles(fused_mttkrp_tg, "fused_tg", regime, block)


@functools.partial(jax.jit,
                   static_argnames=("width", "interpret", "chunk"))
def onehot_reduce_full(local: jax.Array, prod: jax.Array, width: int,
                       interpret: bool = False,
                       chunk: int = _CHUNK) -> jax.Array:
    """(nb, B) ids + (nb, B, R) partials → (width, R) total (privatized)."""
    B = local.shape[1]
    R = prod.shape[-1]
    local, prod, nb_pad = _pad_blocks(local, prod, chunk)
    grid = (nb_pad // chunk,)
    out = pl.pallas_call(
        functools.partial(_full_kernel, width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((chunk, B, R), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((width, R), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((width, R), _acc_dtype(prod.dtype)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(local, prod)
    return out


# -- dense-mode MXU engine (docs/dense.md) ----------------------------------
#
# A DenseModeLayout's MTTKRP is X_(m) @ KR(other factors): a batched
# (tile, span) @ (span, R) matmul — the one shape the MXU is literally
# built for, with NO index streams, gathers or one-hots anywhere.  The
# kernel stages the two Khatri-Rao operands (the chained outer-factor
# product w and the lane-padded inner factor u, built ONCE by
# ops.mttkrp.dense_operands and shared with the XLA reference for bit
# parity) whole in VMEM, builds the (span, R) KR tile in registers via
# a broadcast multiply — the column space is a regular grid, so no
# arbitrary gather is ever needed (the construct Mosaic cannot lower) —
# and drives one dot_general per row tile.

def _dense_kernel(tiles_ref, w_ref, u_ref, out_ref, *, rank: int,
                  precision):
    w = w_ref[...]                           # (n_outer, R)
    u = u_ref[...]                           # (inner_pad, R)
    tiles = tiles_ref[0].astype(w.dtype)     # (tile, span)
    kr = (w[:, None, :] * u[None, :, :]).reshape(-1, rank)   # (span, R)
    out_ref[0] = jax.lax.dot_general(
        tiles, kr,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
        precision=precision)


def dense_vmem_ok(layout, factors, mode: int,
                  budget_bytes: int = None) -> bool:
    """VMEM plan of the dense MXU kernel: one (tile, span) value tile
    resident per step, both KR operands whole, the (span, R) Khatri-Rao
    product built in registers, and the (tile, R) output block at the
    accumulator width."""
    if budget_bytes is None:
        budget_bytes = _vmem_budget()
    geo = layout.geometry
    R = int(factors[0].shape[1])
    itemsize = jnp.dtype(factors[0].dtype).itemsize
    tile_bytes = layout.tile * layout.span * layout.tiles.dtype.itemsize
    work = ((geo.n_outer + geo.inner_pad) * R * itemsize      # w + u
            + layout.span * R * itemsize                      # kr
            + layout.tile * R * max(itemsize, 4))             # out block
    return tile_bytes + work <= budget_bytes


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def fused_dense(layout, factors, mode: int,
                interpret: bool = False) -> jax.Array:
    """Dense-mode MTTKRP on the MXU over a
    :class:`splatt_tpu.blocked.DenseModeLayout`.

    Interpret mode is bit-identical to :func:`ops.mttkrp.dense_mttkrp`
    by construction: both build (w, u) through the same
    ``dense_operands``, form the same (span, R) KR product, and reduce
    each output element with ONE dot_general over span at the same
    precision and accumulator dtype.  Output: (dim, R) at the
    accumulator dtype — pad rows trimmed, pad columns contributing
    exact zeros (the inner factor is zero-padded)."""
    from splatt_tpu.ops.mttkrp import dense_operands, mxu_precision

    if mode != layout.mode:
        raise ValueError("fused_dense requires the layout's own mode")
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    w, u = dense_operands(layout, factors, mode)
    ntiles, tile, span = (int(s) for s in layout.tiles.shape)
    acc = _acc_dtype(dtype)
    out = pl.pallas_call(
        functools.partial(_dense_kernel, rank=R,
                          precision=mxu_precision(dtype)),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec((1, tile, span), lambda i: (i, 0, 0)),
            pl.BlockSpec((int(w.shape[0]), R), lambda i: (0, 0)),
            pl.BlockSpec((int(u.shape[0]), R), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, R), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ntiles, tile, R), acc),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(layout.tiles, w, u)
    return out.reshape(-1, R)[:layout.dim]


def _probe_case_dense(kernel_fn, regime: str, block: int) -> bool:
    """The dense-engine probe compile — its own case because the
    kernel's call signature has no width/accumulate (the shared
    :func:`_probe_case` lowers the sparse-layout signature).  A
    synthetic near-dense mode at a production-like (tile, span); the
    Mosaic-sensitive step the probe exercises is the in-kernel
    (n_outer, inner_pad, R) -> (span, R) Khatri-Rao reshape."""
    import numpy as np

    from splatt_tpu.blocked import build_dense_layout
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(0)
    dims = (64, 32, 256)
    nnz = 65536
    rank = 48 if _vmem_limit() >= (32 << 20) else 16
    inds = np.stack([rng.integers(0, d, nnz) for d in dims])
    tt = SparseTensor(inds=inds.astype(np.int64), vals=np.ones(nnz),
                      dims=dims)
    from splatt_tpu.config import fit_dtype

    lay = build_dense_layout(tt, 0)
    fac = [jnp.zeros((d, rank), fit_dtype()) for d in dims]
    kernel_fn.lower(lay, fac, mode=0, interpret=False).compile()
    return True


@functools.cache
def fused_dense_supported(regime: str = "ck1", block: int = 4096) -> bool:
    """Whether the dense-mode MXU kernel compiles here (the in-kernel
    broadcast-multiply + (n_outer·inner_pad, R) reshape that builds the
    Khatri-Rao tile is the Mosaic-sensitive step), probed per
    (lane-chunk regime, tile) like every engine — an unlowerable form
    demotes cleanly to the ``dense_xla`` reference path."""
    return _probe_compiles(fused_dense, "fused_dense", regime, block,
                           case=_probe_case_dense)
