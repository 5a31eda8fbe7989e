"""Distributed CPD via sharding + XLA collectives (≙ src/mpi/).

The reference's medium-grained distributed ALS (mpi_cpd_als_iterate,
src/mpi/mpi_cpd.c:627-804) does, per mode per iteration:

  local MTTKRP → add own partials → reduce rows owned by me
  (MPI_Alltoallv) → solve for owned rows → normalize (λ allreduce) →
  broadcast updated rows to neighbors (Alltoallv) → Gram allreduce.

The TPU mapping (SURVEY §5/§7): nonzeros are sharded over a mesh axis
(equal-nnz shards ≙ the nnz-balanced layer boundaries of
p_find_layer_boundaries) and every factor matrix is row-sharded over the
same axis.  Inside one `shard_map`:

  - ``all_gather``     ≙ mpi_update_rows (neighbors fetch rows they need)
  - local gather-prod + segment-sum over the *global* row space
                       ≙ local MTTKRP + mpi_add_my_partials
  - ``psum_scatter``   ≙ mpi_reduce_rows (each device keeps the summed
                         rows it owns)
  - ``psum``           ≙ the Gram / λ / fit MPI_Allreduce calls
                         (src/matrix.c:445-452, :121,181; mpi_cpd.c:94)

No comm plan, no ineed lists, no greedy row assignment: ownership is the
contiguous row blocks of the sharding, and XLA schedules the collectives
over ICI.  The reference's POINT2POINT row-exchange variant
(p_reduce_rows_point2point, src/mpi/mpi_cpd.c:323-423) maps to the
ppermute ring sweep in :mod:`splatt_tpu.parallel.ring`, selected via
``opts.comm_pattern`` — same math, O(dim/ndev) peak factor memory.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from splatt_tpu.config import (CommPattern, Options, Verbosity, default_opts,
                               resolve_comm_pattern, resolve_dtype)
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import init_factors
from splatt_tpu.kruskal import KruskalTensor
from splatt_tpu.ops.mttkrp import acc_dtype
from splatt_tpu.parallel.common import (blocked_local_mttkrp, bucket_engine,
                                        bucket_scatter, comm_volume_report,
                                        fit_tail, imbalance_report,
                                        mode_update_tail,
                                        run_distributed_als)
from splatt_tpu.parallel.mesh import make_mesh, single_axis_of
from splatt_tpu.utils.env import ceil_to as _pad_to


def shard_nnz_host(tt: SparseTensor, ndev: int, val_dtype=np.float32,  # splint: ignore[SPL005] shard-builder signature default; callers override via Options.val_dtype
                   partition: Optional[np.ndarray] = None,
                   streamed: Optional[bool] = None,
                   out_dir: Optional[str] = None,
                   chunk: int = 1 << 22
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Host side of :func:`shard_nnz`: the padded (nmodes, nnz_pad)
    arrays, without the device_put.

    `streamed` (auto: when tt holds memmapped indices) runs the
    bucketing in chunked passes so host RSS stays O(chunk + bucket
    metadata); with `out_dir` the outputs are disk-backed memmaps —
    a beyond-RAM tensor shards end-to-end (≙ the reference streaming
    equal-nnz chunks from the root rank, src/mpi/mpi_io.c:587-648).
    """
    from splatt_tpu.parallel.common import (is_memmapped,
                                            streamed_bucket_scatter)
    from splatt_tpu.utils.env import check_int32_dims

    check_int32_dims(tt.dims)
    if streamed is None:
        streamed = is_memmapped(tt.inds)
    if streamed:
        if partition is None:
            csize = max(ndev, _pad_to(tt.nnz, ndev)) // ndev

            def owner_fn(ic, s):
                return np.minimum(
                    (s + np.arange(ic.shape[1], dtype=np.int64)) // csize,
                    ndev - 1)
        else:
            part = partition  # may itself be a memmap

            def owner_fn(ic, s):
                return np.asarray(part[s:s + ic.shape[1]], dtype=np.int64)

        binds, bvals, _, _ = streamed_bucket_scatter(
            tt.inds, tt.vals, owner_fn, ndev, val_dtype, chunk=chunk,
            out_dir=out_dir)
        return binds.reshape(tt.nmodes, -1), bvals.reshape(-1)
    if partition is None:
        nnz_pad = max(ndev, _pad_to(tt.nnz, ndev))
        inds = np.zeros((tt.nmodes, nnz_pad), dtype=np.int32)
        inds[:, :tt.nnz] = tt.inds
        vals = np.zeros(nnz_pad, dtype=val_dtype)
        vals[:tt.nnz] = tt.vals
        return inds, vals
    binds, bvals, _, _ = bucket_scatter(tt.inds, tt.vals,
                                        np.asarray(partition), ndev,
                                        val_dtype)
    return binds.reshape(tt.nmodes, -1), bvals.reshape(-1)


def shard_nnz(tt: SparseTensor, mesh: Mesh, axis: str = "nnz",
              val_dtype=np.float32,  # splint: ignore[SPL005] shard-builder signature default; callers override via Options.val_dtype
              partition: Optional[np.ndarray] = None,
              streamed: Optional[bool] = None,
              out_dir: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Pad nonzeros to the device count and shard them over `axis`.

    With `partition=None`: equal contiguous chunks (≙ mpi_tt_read's
    equal-nnz distribution, mpi_simple_distribute,
    src/mpi/mpi_io.c:587-648).  With a per-nonzero `partition` array
    (values in [0, ndev)): nonzero n is placed on device partition[n]
    — the FINE decomposition's user-supplied nonzero-level partition
    (≙ p_rearrange_fine, src/mpi/mpi_io.c:486-499), with buckets padded
    to the largest.  Pad entries point at row 0 with value 0 — harmless
    to every kernel.  See :func:`shard_nnz_host` for the streamed
    (bounded-RSS / disk-backed) build knobs.
    """
    inds, vals = shard_nnz_host(tt, mesh.shape[axis], val_dtype,
                                partition=partition, streamed=streamed,
                                out_dir=out_dir)
    inds_s = jax.device_put(inds, NamedSharding(mesh, P(None, axis)))
    vals_s = jax.device_put(vals, NamedSharding(mesh, P(axis)))
    return inds_s, vals_s


def shard_blocked_layouts(tt: SparseTensor, mesh: Mesh, opts: Options,
                          dims_pad: Tuple[int, ...], axis: str = "nnz",
                          val_dtype=np.float32,  # splint: ignore[SPL005] shard-builder signature default; callers override via Options.val_dtype
                          partition: Optional[np.ndarray] = None,
                          out_dir: Optional[str] = None,
                          chunk: int = 1 << 22):
    """Per-shard sorted blocked layouts so the sweep runs the
    single-chip blocked MTTKRP engine inside every shard (≙ each MPI
    rank building CSF over its local nonzeros, mpi_cpd.c:714).  The
    mode-m row space stays GLOBAL (the psum_scatter reduce owns the
    fence split), so the sentinel dim is dims_pad[sort_mode].

    `opts.block_alloc` governs the layout count exactly like the
    single-chip compiler (≙ splatt_csf_alloc): ONEMODE/TWOMODE build
    1–2 sorted copies (shared by reference across modes, the
    non-sorted ones running the generic scatter path); ALLMODE builds
    one per mode.

    Returns (host_meta, device_arrays): host_meta[m] holds the statics
    (block, seg_width, path, impl, sort_mode, sort_dim);
    device_arrays[m] the device-put (inds, vals, row_start) triple.

    Memmapped (out-of-core) tensors build via the streamed chunked
    passes — bucket scatter and the per-bucket counting sort both
    disk-backed under `out_dir` when given — so the optimized engine
    survives beyond-RAM scale (≙ mttkrp_csf per rank regardless of
    size, src/mpi/mpi_cpd.c:714).
    """
    import os

    from splatt_tpu.parallel.common import (alloc_build_modes,
                                            build_bucket_layout,
                                            is_memmapped,
                                            streamed_bucket_scatter)

    ndev = mesh.shape[axis]
    streamed = is_memmapped(tt.inds)
    fence = max(ndev, _pad_to(tt.nnz, ndev)) // ndev
    if streamed:
        if partition is None:
            def owner_fn(ic, s):
                return np.arange(s, s + ic.shape[1], dtype=np.int64) // fence
        else:
            part = np.asarray(partition, dtype=np.int64)

            def owner_fn(ic, s):
                return part[s:s + ic.shape[1]]

        binds, bvals, _, counts = streamed_bucket_scatter(
            tt.inds, tt.vals, owner_fn, ndev, val_dtype, chunk=chunk,
            out_dir=(os.path.join(out_dir, "shards")
                     if out_dir is not None else None))
    else:
        if partition is None:
            owner = np.arange(tt.nnz, dtype=np.int64) // fence
        else:
            owner = np.asarray(partition, dtype=np.int64)
        binds, bvals, _, counts = bucket_scatter(tt.inds, tt.vals, owner,
                                                 ndev, val_dtype)
    build_modes = alloc_build_modes(dims_pad, opts)
    built_meta = []
    built_arr = []
    for m in build_modes:
        i, v, rs, blk, S = build_bucket_layout(
            binds, bvals, counts, m, dims_pad[m], opts.nnz_block,
            chunk=chunk,
            out_dir=(os.path.join(out_dir, f"blocked_m{m}")
                     if out_dir is not None else None))
        path, impl = bucket_engine(S, opts)
        built_meta.append(dict(block=blk, seg_width=S, path=path,
                               impl=impl, sort_mode=m,
                               sort_dim=dims_pad[m]))
        built_arr.append((
            jax.device_put(i, NamedSharding(mesh, P(None, axis, None))),
            jax.device_put(v, NamedSharding(mesh, P(axis, None))),
            jax.device_put(rs, NamedSharding(mesh, P(axis, None)))))
    meta = []
    arrays = []
    for m in range(tt.nmodes):
        j = build_modes.index(m) if m in build_modes else 0
        mm = dict(built_meta[j])
        if mm["sort_mode"] != m:
            mm["path"] = "scatter"
        meta.append(mm)
        arrays.append(built_arr[j])
    return meta, tuple(arrays)


def shard_factors(factors: List[jax.Array], dims: Tuple[int, ...],
                  mesh: Mesh, axis: str = "nnz",
                  relabels: Optional[List[Optional[np.ndarray]]] = None
                  ) -> List[jax.Array]:
    """Row-shard factors, zero-padding rows to the device count.

    Zero pad rows keep Grams, norms and solves exact (they contribute
    nothing), mirroring how the reference's ownership fences
    (mat_ptrs, src/mpi/mpi_mat_distribute.c:558-582) exclude non-owned
    rows from every reduction.  `relabels[m]`, when given, places row
    `old` at label `relabels[m][old]` (comm-minimizing distribution).
    """
    ndev = mesh.shape[axis]
    out = []
    for m, (U, d) in enumerate(zip(factors, dims)):
        d_pad = _pad_to(d, ndev)
        U_pad = jnp.zeros((d_pad, U.shape[1]), dtype=U.dtype)
        rl = relabels[m] if relabels is not None else None
        if rl is None:
            U_pad = U_pad.at[:d].set(U[:d])
        else:
            U_pad = U_pad.at[jnp.asarray(rl)].set(U[:d])
        out.append(jax.device_put(U_pad, NamedSharding(mesh, P(axis, None))))
    return out


def sharded_mttkrp(inds: jax.Array, vals: jax.Array, factors: List[jax.Array],
                   mode: int, mesh: Mesh, axis: str = "nnz") -> jax.Array:
    """Distributed MTTKRP: result row-sharded like ``factors[mode]``.

    `factors` are row-sharded (dim_pad, R); `inds`/`vals` nnz-sharded.
    One all_gather per input factor, one psum_scatter for the output —
    the two row-exchange phases of the reference, as collectives.
    """
    nmodes = len(factors)
    dims_pad = tuple(int(f.shape[0]) for f in factors)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, axis), P(axis), *[P(axis, None)] * nmodes),
             out_specs=P(axis, None))
    def run(inds_l, vals_l, *factors_l):
        prod = vals_l[:, None].astype(factors_l[0].dtype)
        for k in range(nmodes):
            if k != mode:
                U = jax.lax.all_gather(factors_l[k], axis, axis=0, tiled=True)
                prod = prod * jnp.take(U, inds_l[k], axis=0, mode="clip")
        partial_out = jax.ops.segment_sum(prod.astype(acc_dtype(prod.dtype)),
                                          inds_l[mode],
                                          num_segments=dims_pad[mode])
        return jax.lax.psum_scatter(partial_out, axis, scatter_dimension=0,
                                    tiled=True)

    return run(inds, vals, *factors)


def make_sharded_sweep(mesh: Mesh, nmodes: int, reg: float,
                       dims_pad: Tuple[int, ...], axis: str = "nnz",
                       variant: str = "all2all",
                       cells: Optional[List[dict]] = None):
    """Build the jitted, shard_mapped one-iteration ALS sweep.

    `first_flag` is a replicated scalar array selecting 2-norm (iteration
    0) vs max-norm normalization (≙ src/cpd.c:343-347) so a single
    compilation serves every iteration.  `variant` picks the comm
    primitives for the two row-exchange phases (≙ SPLATT_OPTION_COMM):
    "all2all" = all_gather + psum_scatter, "ring" = ppermute ring
    (splatt_tpu.parallel.ring) with O(dim/ndev) peak factor memory,
    "async_ring" = the Pallas remote-copy ring
    (splatt_tpu.parallel.ring_kernels, docs/ring.md) that overlaps the
    exchange with the local compute on TPU and keeps the ppermute
    semantics bit-for-bit elsewhere.  "local_stub" is a TIMING-ONLY
    variant (measure_ring_overlap): the exchanges are replaced by
    local reads so a step costs exactly the compute — its outputs are
    mathematically WRONG and must never reach a driver.

    `cells` (shard_blocked_layouts meta; all2all only): the local
    MTTKRP runs the single-chip blocked engine over each shard's
    sorted arrays instead of the stream formulation.
    """
    ndev = mesh.shape[axis]
    factor_specs = tuple([P(axis, None)] * nmodes)
    gram_specs = tuple([P(None, None)] * nmodes)
    if cells is not None and variant != "all2all":
        raise ValueError("blocked local engine requires the all2all "
                         "variant (the ring reduce is blockwise)")
    cell_specs = tuple(
        (P(None, axis, None), P(axis, None), P(axis, None))
        for _ in range(nmodes)) if cells is not None else ()

    if variant == "ring":
        from splatt_tpu.parallel.ring import (blockwise_reduce_rows,
                                              ring_gather_rows)

        def gather_rows(U_l, idx):
            return ring_gather_rows(U_l, idx, axis, ndev)

        def reduce_rows(prod, idx, m):
            return blockwise_reduce_rows(prod, idx, axis, ndev,
                                         dims_pad[m] // ndev)
    elif variant == "async_ring":
        from splatt_tpu.parallel.ring_kernels import (
            async_blockwise_reduce_rows, async_ring_gather_rows)

        def gather_rows(U_l, idx):
            return async_ring_gather_rows(U_l, idx, axis, ndev)

        def reduce_rows(prod, idx, m):
            return async_blockwise_reduce_rows(prod, idx, axis, ndev,
                                               dims_pad[m] // ndev)
    elif variant == "local_stub":
        # compute-only baseline for the overlap metric: same per-step
        # masked passes and reductions, zero inter-device traffic
        def gather_rows(U_l, idx):
            block = U_l.shape[0]
            rows0 = jnp.zeros((idx.shape[0], U_l.shape[1]), U_l.dtype)
            my_id = jax.lax.axis_index(axis)

            def body(step, rows):
                shard_id = jnp.mod(my_id - step, ndev)
                mask = (idx // block) == shard_id
                local = jnp.where(mask, jnp.mod(idx, block), 0)
                picked = jnp.take(U_l, local, axis=0, mode="clip")
                return rows + jnp.where(mask[:, None], picked, 0)

            return jax.lax.fori_loop(0, ndev, body, rows0)

        def reduce_rows(prod, idx, m):
            block = dims_pad[m] // ndev
            my_id = jax.lax.axis_index(axis)
            out_dtype = acc_dtype(prod.dtype)

            def body(jb, acc):
                mask = (idx // block) == jb
                p = jax.ops.segment_sum(
                    (prod * mask[:, None]).astype(out_dtype),
                    jnp.where(mask, jnp.mod(idx, block), 0),
                    num_segments=block)
                return jnp.where(jb == my_id, p, acc)

            acc0 = jnp.zeros((block, prod.shape[1]), dtype=out_dtype)
            return jax.lax.fori_loop(0, ndev, body, acc0)
    elif variant == "all2all":
        def gather_rows(U_l, idx):
            # ≙ mpi_update_rows: fetch the rows of the other factors
            U = jax.lax.all_gather(U_l, axis, axis=0, tiled=True)
            return jnp.take(U, idx, axis=0, mode="clip")

        def reduce_rows(prod, idx, m):
            # local MTTKRP partials over the global row space (f32
            # accumulation for low-precision operands), then
            # ≙ mpi_reduce_rows: I keep the summed rows I own
            partial_out = jax.ops.segment_sum(
                prod.astype(acc_dtype(prod.dtype)), idx,
                num_segments=dims_pad[m])
            return jax.lax.psum_scatter(partial_out, axis,
                                        scatter_dimension=0, tiled=True)
    else:
        raise ValueError(f"unknown comm variant {variant!r}")

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, axis), P(axis), factor_specs, gram_specs,
                       P(), cell_specs),
             out_specs=(factor_specs, gram_specs, P(), P(), P()),
             check_vma=False)
    def sweep(inds_l, vals_l, factors_l, grams_l, first_flag, cells_l):
        factors_l = list(factors_l)
        grams_l = list(grams_l)
        dtype = factors_l[0].dtype
        lam = None
        M_l = None
        for m in range(nmodes):
            if cells is not None:
                # ≙ mpi_update_rows then the rank-local optimized
                # MTTKRP (mttkrp_csf, mpi_cpd.c:714) over the shard's
                # sorted blocked arrays, then mpi_reduce_rows
                ci, cv, crs = cells_l[m]
                R = factors_l[0].shape[1]
                fac_full = [
                    jax.lax.all_gather(factors_l[k], axis, axis=0,
                                       tiled=True) if k != m
                    # shape carrier for the output row space (values
                    # unused by the sorted paths; DCE'd)
                    else jnp.zeros((dims_pad[m], R), dtype)
                    for k in range(nmodes)]
                partial_out = blocked_local_mttkrp(
                    ci.reshape(nmodes, -1), cv.reshape(-1),
                    crs.reshape(-1), fac_full, m,
                    dim=cells[m]["sort_dim"], block=cells[m]["block"],
                    seg_width=cells[m]["seg_width"],
                    path=cells[m]["path"], impl=cells[m]["impl"],
                    sort_mode=cells[m]["sort_mode"])
                M_l = jax.lax.psum_scatter(partial_out, axis,
                                           scatter_dimension=0, tiled=True)
            else:
                prod = vals_l[:, None].astype(dtype)
                for k in range(nmodes):
                    if k != m:
                        prod = prod * gather_rows(factors_l[k], inds_l[k])
                M_l = reduce_rows(prod, inds_l[m], m)
            U_l, gram, lam = mode_update_tail(M_l, grams_l, m, reg,
                                              first_flag, axis,
                                              store_dtype=dtype)
            factors_l[m] = U_l
            grams_l[m] = gram
        znormsq, inner = fit_tail(lam, grams_l, M_l, factors_l[nmodes - 1],
                                  axis)
        return tuple(factors_l), tuple(grams_l), lam, znormsq, inner

    return jax.jit(sweep)


def make_sharded_profiled_sweep(mesh: Mesh, nmodes: int, reg: float,
                                dims_pad: Tuple[int, ...], store_dtype,
                                axis: str = "nnz",
                                cells: Optional[List[dict]] = None):
    """Split-jit profiled sharded sweep (all2all variant only): gather,
    local MTTKRP, reduce, update, and fit each run as their own
    shard_mapped program bracketed by blocking timers — the measured
    mttkrp/collective/solve attribution of ≙ mpi_time_stats
    (src/mpi/mpi_cpd.c:893-939).  Costs cross-phase fusion and
    materializes the gathered factors between phases; the fused
    :func:`make_sharded_sweep` is the production path.
    """
    factor_specs = tuple([P(axis, None)] * nmodes)
    gram_specs = tuple([P(None, None)] * nmodes)
    cell_spec = (P(None, axis, None), P(axis, None), P(axis, None))

    def make_gather(m):
        others = [k for k in range(nmodes) if k != m]

        @partial(shard_map, mesh=mesh, in_specs=(factor_specs,),
                 out_specs=tuple(P(None, None) for _ in others),
                 check_vma=False)
        def gather_m(factors_l):
            # ≙ mpi_update_rows: fetch the other factors whole
            return tuple(jax.lax.all_gather(factors_l[k], axis, axis=0,
                                            tiled=True) for k in others)

        return jax.jit(gather_m)

    def make_local(m):
        others = [k for k in range(nmodes) if k != m]
        gathered_specs = tuple(P(None, None) for _ in others)
        in_specs = ((P(None, axis), P(axis), gathered_specs)
                    + ((cell_spec,) if cells is not None else ()))

        @partial(shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=P(axis, None), check_vma=False)
        def local_m(inds_l, vals_l, gathered, *cell_m):
            if cells is not None:
                ci, cv, crs = cell_m[0]
                R = gathered[0].shape[1]
                fac_full = []
                gi = iter(gathered)
                for k in range(nmodes):
                    fac_full.append(
                        jnp.zeros((dims_pad[m], R), gathered[0].dtype)
                        if k == m else next(gi))
                return blocked_local_mttkrp(
                    ci.reshape(nmodes, -1), cv.reshape(-1),
                    crs.reshape(-1), fac_full, m,
                    dim=cells[m]["sort_dim"], block=cells[m]["block"],
                    seg_width=cells[m]["seg_width"],
                    path=cells[m]["path"], impl=cells[m]["impl"],
                    sort_mode=cells[m]["sort_mode"])
            prod = vals_l[:, None].astype(gathered[0].dtype)
            for j, k in enumerate(others):
                prod = prod * jnp.take(gathered[j], inds_l[k], axis=0,
                                       mode="clip")
            return jax.ops.segment_sum(
                prod.astype(acc_dtype(prod.dtype)), inds_l[m],
                num_segments=dims_pad[m])

        return jax.jit(local_m)

    def make_reduce(m):
        @partial(shard_map, mesh=mesh, in_specs=(P(axis, None),),
                 out_specs=P(axis, None), check_vma=False)
        def reduce_m(part_l):
            # ≙ mpi_reduce_rows: keep the summed rows I own
            return jax.lax.psum_scatter(part_l, axis,
                                        scatter_dimension=0, tiled=True)

        return jax.jit(reduce_m)

    def make_update(m):
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(axis, None), gram_specs, P()),
                 out_specs=(P(axis, None), P(), P()), check_vma=False)
        def update_m(M_l, grams_l, flag):
            return mode_update_tail(M_l, list(grams_l), m, reg, flag,
                                    axis, store_dtype=store_dtype)

        return jax.jit(update_m)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), gram_specs, P(axis, None), P(axis, None)),
             out_specs=(P(), P()), check_vma=False)
    def fit_fn(lam, grams_l, M_l, U_l):
        return fit_tail(lam, list(grams_l), M_l, U_l, axis)

    gathers = [make_gather(m) for m in range(nmodes)]
    locals_ = [make_local(m) for m in range(nmodes)]
    reduces = [make_reduce(m) for m in range(nmodes)]
    updates = [make_update(m) for m in range(nmodes)]
    fit_jit = jax.jit(fit_fn)

    from splatt_tpu.utils.env import host_fence as sync
    from splatt_tpu.utils.timers import timers

    def sweep(inds, vals, factors, grams, flag, cells_dev=()):
        factors = list(factors)
        grams = list(grams)
        lam = None
        M = None
        for m in range(nmodes):
            with timers.time("dist_gather"):
                gathered = sync(gathers[m](tuple(factors)))
            extra = (cells_dev[m],) if cells is not None else ()
            with timers.time("dist_mttkrp"):
                part = sync(locals_[m](inds, vals, gathered, *extra))
            with timers.time("dist_comm"):
                M = sync(reduces[m](part))
            with timers.time("dist_update"):
                factors[m], grams[m], lam = sync(
                    updates[m](M, tuple(grams), flag))
        with timers.time("dist_fit"):
            znormsq, inner = sync(fit_jit(lam, tuple(grams), M,
                                          factors[nmodes - 1]))
        return tuple(factors), tuple(grams), lam, znormsq, inner

    return sweep


#: ordered comm-engine fallback chains (docs/ring.md): a failing
#: strategy degrades CLASSIFIED to the next entry — async ring to the
#: hop-barriered ppermute ring to the all2all collectives, which have
#: no preconditions and cannot fail to apply (the terminal engine).
_COMM_CHAINS = {
    CommPattern.ALL2ALL: ("all2all",),
    CommPattern.POINT2POINT: ("ring", "all2all"),
    CommPattern.ASYNC_RING: ("async_ring", "ring", "all2all"),
}


def refuse_async_ring_on_tpu() -> None:
    """ASYNC_RING fails loudly on a TPU, whatever the engine-fallback
    setting: the RDMA kernels pass their exchange check on a v5e 2x2
    (chip_smoke --four-chips), but the async-ring CPD at 20M nonzeros
    finished no iteration in 359 s (PR 21, PERF.md).  Neither a hang
    nor a quiet ppermute stand-in is acceptable, so the run is refused
    before any device work."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            "--comm async_ring is refused on TPU: its RDMA ring CPD "
            "stalls at scale on a v5e 2x2 (PERF.md, open questions); "
            "use --comm point2point or all2all")


def comm_chain(comm: CommPattern) -> tuple:
    """The ordered comm-strategy fallback chain for a requested
    pattern (best first, terminal last)."""
    return _COMM_CHAINS[comm]


def _comm_shape_key(dims_pad, ndev: int, rank: int, dtype) -> str:
    """Demotion scope of a comm-engine failure — its own ``:comm``
    suffix keeps ring demotions disjoint from the MTTKRP engine keys
    (an async-ring OOM indicts the async ring at this shape, never the
    all2all path or a compute engine)."""
    dims = "x".join(str(int(d)) for d in dims_pad)
    return f"d{dims}:w{ndev}:r{int(rank)}:{jnp.dtype(dtype).name}:comm"


def _select_comm_sweep(chain, mesh, nmodes, reg, dims_pad, axis, cells_meta,
                       inds, vals, cells_dev, factors, grams, dtype, opts):
    """Build the sweep on the best LIVE comm strategy, probing each
    non-terminal candidate with one discarded step invocation (the
    sweep is pure, so the probe costs compute but never state).  A
    probe failure is classified, demotes ``comm.<variant>`` under the
    comm shape key (per-shape for RESOURCE/TIMEOUT, process-wide
    otherwise) and falls to the next strategy with a ``comm_fallback``
    run-report event — the ladder the ``comm.ring_exchange`` chaos
    drills assert on.  Returns (variant, step)."""
    from splatt_tpu import resilience

    ndev = mesh.shape[axis]
    rank = int(factors[0].shape[1])
    ckey = _comm_shape_key(dims_pad, ndev, rank, dtype)
    fallback = (opts.engine_fallback if opts.engine_fallback is not None
                else resilience.fallback_enabled())
    # demotion pruning: a previously indicted strategy is skipped, but
    # the terminal all2all is always live
    live = [v for v in chain
            if v == chain[-1]
            or not resilience.is_demoted(f"comm.{v}", ckey)]
    for i, variant in enumerate(live):
        sweep = make_sharded_sweep(mesh, nmodes, reg, dims_pad, axis=axis,
                                   variant=variant, cells=cells_meta)

        def step(f, g, flag, sweep=sweep):
            return sweep(inds, vals, f, g, flag, cells_dev)

        if i == len(live) - 1 or not fallback:
            # terminal (or fallback disabled: fail loudly at the real
            # first step, not a probe)
            return variant, step
        try:
            probe = step(factors, grams, jnp.asarray(1.0, dtype=dtype))
            # async failures surface at the fence, not the call
            jax.block_until_ready(probe[2])
            return variant, step
        except Exception as e:
            cls = resilience.classify_failure(e)
            resilience.demote_engine(f"comm.{variant}", e, shape_key=ckey)
            resilience.run_report().add(
                "comm_fallback", strategy=variant, fallback_to=live[i + 1],
                failure_class=cls.value,
                error=resilience.failure_message(e)[:200])
            if opts.verbosity >= Verbosity.LOW:
                print(f"  comm engine {variant} failed ({cls.value}); "
                      f"falling back to {live[i + 1]}")
    raise AssertionError("unreachable: the terminal comm engine returns")


def _make_exchange_only(mesh, nmodes, dims_pad, axis, rank, dtype,
                        hops: int):
    """A jitted program that performs EXACTLY one sweep's ring traffic
    (every gather leg's hops + the reduce leg) with no MTTKRP compute —
    the fully-exposed exchange time, i.e. the denominator of the
    achieved-overlap metric (docs/ring.md).  `hops` follows the variant
    as it actually runs: ndev ppermutes per leg for the sync ring (and
    the async variant's CPU fallback), ndev-1 real RDMA hops for the
    Pallas async ring — an overstated denominator would inflate the
    reported overlap."""
    ndev = mesh.shape[axis]
    factor_specs = tuple([P(axis, None)] * nmodes)

    @partial(shard_map, mesh=mesh, in_specs=(factor_specs,),
             out_specs=P(axis, None), check_vma=False)
    def exchange(factors_l):
        perm = [(i, (i + 1) % ndev) for i in range(ndev)]

        def hop(_, U):
            return jax.lax.ppermute(U, axis, perm)

        tot = jnp.zeros((1, rank), dtype)
        for m in range(nmodes):
            for k in range(nmodes):
                if k != m:
                    U = jax.lax.fori_loop(0, hops, hop, factors_l[k])
                    tot = tot + U[:1]
            blk = jnp.zeros((dims_pad[m] // ndev, rank),
                            acc_dtype(jnp.dtype(dtype)))
            blk = jax.lax.fori_loop(0, hops, hop, blk)
            tot = tot + blk[:1].astype(dtype)
        return tot

    return jax.jit(exchange)


def measure_ring_overlap(mesh, nmodes, reg, dims_pad, axis, variant,
                         inds, vals, factors, grams, dtype,
                         reps: int = 3, step_fn=None) -> dict:
    """Measure the ACHIEVED comm/compute overlap of a ring sweep
    (docs/ring.md defines the metric):

        exchange_s  — the sweep's ring traffic alone, fully exposed
        compute_s   — a "local_stub" sweep step (identical compute,
                      zero traffic; timing-only — its math is wrong)
        step_s      — the real sweep step (comm + compute)

        exposed = max(0, step_s - compute_s)
        hidden  = max(0, exchange_s - exposed)
        overlap_frac = hidden / exchange_s

    All three run warm (compile excluded, median of `reps`).  The wire
    model's per-device bytes ride along so MULTICHIP artifacts can put
    the measured seconds next to the modeled traffic.  On CPU the
    fallback engines expose every hop — overlap_frac near 0 is the
    honest reading there, labelled by ``backend``/``engine``.

    `step_fn(factors, grams, flag)`, when the caller already built and
    compiled the production sweep (sharded_cpd_als did, for its comm
    probe), is timed directly instead of re-tracing an identical sweep
    — the real step's compile is not paid twice.
    """
    from splatt_tpu import trace

    # the measurement pays extra compiles (stub + exchange-only
    # programs) — attribute it so a traced distributed run shows the
    # overlap probe's cost next to the sweep it instruments
    with trace.span("dist.measure_overlap", variant=variant):
        return _measure_ring_overlap(
            mesh, nmodes, reg, dims_pad, axis, variant, inds, vals,
            factors, grams, dtype, reps, step_fn)


def _measure_ring_overlap(mesh, nmodes, reg, dims_pad, axis, variant,
                          inds, vals, factors, grams, dtype, reps,
                          step_fn) -> dict:
    """:func:`measure_ring_overlap` body, inside its span."""
    import time as _time

    from splatt_tpu.parallel.common import comm_volume_model
    from splatt_tpu.parallel.ring_kernels import async_ring_supported
    from splatt_tpu.utils.env import host_fence

    ndev = mesh.shape[axis]
    rank = int(factors[0].shape[1])
    flag = jnp.asarray(0.0, dtype=dtype)
    if step_fn is None:
        sweep = make_sharded_sweep(mesh, nmodes, reg, dims_pad, axis=axis,
                                   variant=variant)

        def step_fn(f, g, fl):
            return sweep(inds, vals, f, g, fl, ())
    stub = make_sharded_sweep(mesh, nmodes, reg, dims_pad, axis=axis,
                              variant="local_stub")
    rdma = (variant == "async_ring" and ndev >= 2
            and async_ring_supported())
    exchange = _make_exchange_only(mesh, nmodes, dims_pad, axis, rank,
                                   dtype,
                                   hops=(ndev - 1) if rdma else ndev)

    def timed(fn) -> float:
        host_fence(fn())  # warm: compile + first run excluded
        ts = []
        for _ in range(max(reps, 1)):
            t0 = _time.perf_counter()
            host_fence(fn())
            ts.append(_time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    t_comm = timed(lambda: exchange(tuple(factors)))
    t_comp = timed(lambda: stub(inds, vals, factors, grams, flag, ())[2])
    t_step = timed(lambda: step_fn(factors, grams, flag)[2])
    exposed = max(0.0, t_step - t_comp)
    hidden = max(0.0, t_comm - exposed)
    overlap = hidden / t_comm if t_comm > 0 else 0.0
    model = comm_volume_model(
        dims_pad, rank, jnp.dtype(dtype).itemsize, ndev=ndev,
        variant=variant,
        acc_itemsize=jnp.dtype(acc_dtype(jnp.dtype(dtype))).itemsize)
    return dict(variant=variant,
                backend=jax.default_backend(),
                engine="pallas_rdma" if rdma else "ppermute_fallback",
                step_s=round(t_step, 6), compute_s=round(t_comp, 6),
                exchange_s=round(t_comm, 6),
                exposed_comm_s=round(exposed, 6),
                hidden_comm_s=round(hidden, 6),
                overlap_frac=round(overlap, 4),
                model_mb_per_device=round(
                    model["gather_mb"] + model["reduce_mb"]
                    + model["allreduce_mb"], 4),
                per_hop_mb=model["per_hop_mb"],
                overlap_eligible_frac=model["overlap_eligible_frac"])


def sharded_cpd_als(tt: SparseTensor, rank: int, mesh: Optional[Mesh] = None,
                    opts: Optional[Options] = None,
                    init: Optional[List[jax.Array]] = None,
                    axis: str = "nnz",
                    partition: Optional[np.ndarray] = None,
                    row_distribute: Optional[str] = None,
                    local_engine: Optional[str] = None,
                    out_dir: Optional[str] = None,
                    checkpoint_path: Optional[str] = None,
                    checkpoint_every: int = 10,
                    resume: bool = True,
                    measure_overlap: Optional[bool] = None
                    ) -> KruskalTensor:
    """Distributed CPD-ALS over a device mesh (≙ the mpirun cpd path,
    src/cmds/mpi_cmd_cpd.c:175-338).

    `opts.comm_pattern` (default: SPLATT_COMM, else ALL2ALL) picks the
    row-exchange strategy; POINT2POINT/ASYNC_RING runs degrade
    classified down the comm chain (docs/ring.md) and, unless
    `measure_overlap` is False (None = auto at verbosity >= HIGH;
    True forces it — the CLI does for --json ring runs), report the
    achieved comm/compute overlap as a ``ring_overlap`` run-report
    event.

    Results are rank-count invariant: the same seed gives the same
    factors at any device count (≙ mpi_mat_rand, src/splatt_mpi.h:368-386)
    because initialization happens in the global row space before
    sharding, and all reductions are deterministic collectives.

    `row_distribute="greedy"`: comm-minimizing factor-row relabeling —
    each shard's touched rows are greedily claimed into its own fence
    (≙ p_greedy_mat_distribution, src/mpi/mpi_mat_distribute.c:436-548)
    — before fences are cut; original row order is restored on gather.

    `local_engine`: "blocked" (all2all variant only; the default) runs
    the single-chip blocked MTTKRP engine over per-shard sorted layouts
    inside the sweep (≙ mttkrp_csf per rank, mpi_cpd.c:714); "stream"
    keeps the naive formulation (the differential oracle; always used
    by the ring variant, whose reduce is blockwise).  Memmapped
    (out-of-core) tensors keep the blocked engine: the shard build and
    the per-shard sorts run as streamed chunked passes (disk-backed
    under `out_dir` when given), so host RSS stays bounded at any
    scale.
    """
    opts = (opts or default_opts()).validate()
    mesh, axis = single_axis_of(mesh, axis)
    mesh = mesh or make_mesh(axis_names=(axis,))
    ndev = mesh.shape[axis]
    nmodes = tt.nmodes
    dims_pad = tuple(_pad_to(d, ndev) for d in tt.dims)
    xnormsq = tt.normsq()

    dtype = resolve_dtype(opts, tt.vals.dtype)

    orig_dims = tt.dims
    relabels = None
    if row_distribute == "greedy":
        from splatt_tpu.parallel.distribute import comm_minimizing_relabels

        shard_of = (np.asarray(partition, dtype=np.int64)
                    if partition is not None else None)
        relabels, dstats = comm_minimizing_relabels(
            np.asarray(tt.inds), orig_dims, ndev, shard_of=shard_of)
        if opts.verbosity >= Verbosity.HIGH:
            # ≙ the comm-volume reduction mpi_send_recv_stats reports
            for st in dstats:
                print(f"  rowdist mode {st['mode']}: local touches "
                      f"{st['local_before']:.1%} -> {st['local_after']:.1%}")
        from splatt_tpu.parallel.common import relabel_tensor

        tt = relabel_tensor(tt, relabels, dims_pad)
    elif row_distribute == "balanced":
        # nnz-weighted factor-row relabeling (≙ the chains-on-chains
        # p_find_layer_boundaries, docs/layout-balance.md): hot slices
        # are spread across the equal-width row fences by a
        # capacity-constrained LPT pack, so no device's fence owns a
        # disproportionate share of the gather/reduce row traffic — the
        # balanced-sharding leg of the skewed-tensor playbook
        from splatt_tpu.parallel.common import balanced_relabel

        relabels = [balanced_relabel(tt.mode_histogram(m), ndev,
                                     dims_pad[m] // ndev)
                    if ndev > 1 else None
                    for m in range(nmodes)]
        # (the achieved fence balance is computed once, post-relabel,
        # by the fence_mm block below — which also prints the HIGH-
        # verbosity per-mode report, so no second full-tensor pass)
        from splatt_tpu.parallel.common import relabel_tensor

        tt = relabel_tensor(tt, relabels, dims_pad)
    elif row_distribute is not None:
        raise ValueError(f"unknown row_distribute {row_distribute!r}")

    comm = resolve_comm_pattern(opts)
    if comm is CommPattern.ASYNC_RING and ndev > 1:
        refuse_async_ring_on_tpu()
    chain = comm_chain(comm)
    ring_family = chain[0] != "all2all"
    if local_engine is None:
        # shared auto policy, plus the FINE-only condition: the ring
        # variants' blockwise reduce is stream-only
        from splatt_tpu.parallel.common import auto_local_engine

        local_engine = ("stream" if ring_family
                        else auto_local_engine(tt, out_dir))
    elif local_engine == "blocked" and ring_family:
        # never silently ignore an explicit engine request (the ring
        # sweeps are stream-only; make_sharded_sweep has the same
        # guard) — and a comm fallback landing on all2all keeps the
        # stream engine it started with rather than rebuilding layouts
        raise ValueError(f"local_engine='blocked' is not supported with "
                         f"the {comm.value} (ring) comm pattern; use "
                         f"ALL2ALL or local_engine='stream'")
    cells_meta = None
    cells_dev = ()
    if local_engine == "blocked" and not ring_family:
        cells_meta, cells_dev = shard_blocked_layouts(
            tt, mesh, opts, dims_pad, axis=axis, val_dtype=dtype,
            partition=partition, out_dir=out_dir)
        # the blocked sweep never reads the stream shard arrays — put
        # 1-entry-per-device dummies instead of a dead O(nnz) HBM copy
        inds = jax.device_put(np.zeros((nmodes, ndev), np.int32),
                              NamedSharding(mesh, P(None, axis)))
        vals = jax.device_put(np.zeros(ndev, dtype),
                              NamedSharding(mesh, P(axis)))
    elif local_engine not in ("blocked", "stream"):
        raise ValueError(f"unknown local_engine {local_engine!r}")
    else:
        inds, vals = shard_nnz(tt, mesh, axis=axis, val_dtype=dtype,
                               partition=partition, out_dir=out_dir)
    # init in the ORIGINAL row space (rank-count/distribution
    # invariance, ≙ mpi_mat_rand); relabels only affect placement
    factors_host = (init if init is not None
                    else init_factors(orig_dims, rank, opts.seed(),
                                      dtype=dtype))
    factors = tuple(shard_factors(
        [jnp.asarray(f, dtype=dtype) for f in factors_host],
        orig_dims, mesh, axis=axis, relabels=relabels))
    from splatt_tpu.ops.linalg import gram

    gram_sharding = NamedSharding(mesh, P(None, None))
    grams = tuple(
        jax.device_put(gram(U), gram_sharding) for U in factors
    )

    # ≙ mpi_rank_stats + mpi_send_recv_stats.  Measured occupancy,
    # not the equal-chunk assumption: padding trails, so the last
    # chunk(s) hold the shortfall.  Always RECORDED (the
    # layout_imbalance event rides `splatt cpd --json` and MULTICHIP
    # artifacts — docs/layout-balance.md); printed at HIGH.
    if partition is not None:
        counts = np.bincount(np.asarray(partition), minlength=ndev)
    else:
        chunk = max(ndev, _pad_to(tt.nnz, ndev)) // ndev
        counts = np.clip(tt.nnz - chunk * np.arange(ndev), 0, chunk)
    from splatt_tpu.parallel.common import record_shard_imbalance

    # per-mode factor-row fence weights: the row traffic the balanced
    # rowdist exists to even out — a device whose fence owns hot
    # slices gates the gather/reduce legs of the ring.  The fence
    # histogram is a full O(nnz) host pass per mode (sequential reads
    # of a memmapped index stream on the out-of-core path), so it is
    # only paid when a rowdist policy makes it the evidence, or at
    # HIGH verbosity as a diagnostic — never as unconditional startup
    # cost on every sharded run
    fence_mm = None
    if row_distribute is not None or opts.verbosity >= Verbosity.HIGH:
        from splatt_tpu.utils.env import max_mean_ratio

        fence_mm = {}
        for m in range(nmodes):
            fences = np.add.reduceat(
                np.bincount(np.asarray(tt.inds[m]), minlength=dims_pad[m]),
                np.arange(0, dims_pad[m], dims_pad[m] // ndev))
            fence_mm[str(m)] = max_mean_ratio(fences)
            if opts.verbosity >= Verbosity.HIGH:
                print(imbalance_report(fences, f"mode{m} row-fence"))
    record_shard_imbalance(
        "shard", counts,
        policy=row_distribute or ("partition" if partition is not None
                                  else "equal"),
        **({"row_fence_max_mean": fence_mm} if fence_mm is not None
           else {}))
    if opts.verbosity >= Verbosity.HIGH:
        print(imbalance_report(counts, "shard"))
    profiled = (opts.verbosity >= Verbosity.HIGH and not ring_family)
    if profiled:
        # split-jit phases with blocking timers: measured gather/mttkrp/
        # reduce/solve attribution (≙ mpi_time_stats); all2all only —
        # the ring variants' overlap makes phase barriers meaningless,
        # so they report the achieved-overlap metric instead
        variant = "all2all"
        sweep = make_sharded_profiled_sweep(mesh, nmodes,
                                            opts.regularization, dims_pad,
                                            dtype, axis=axis,
                                            cells=cells_meta)

        def step(factors, grams, flag):
            return sweep(inds, vals, factors, grams, flag, cells_dev)

        from splatt_tpu.parallel.common import wrap_profiled_step

        step = wrap_profiled_step(step)
    else:
        # comm-engine selection with the classified fallback ladder
        # (docs/ring.md): async_ring -> ring -> all2all.  This (and the
        # overlap probe below) runs BEFORE run_distributed_als opens
        # its enabling scope, so the Options.trace per-run pin must be
        # honored here too
        from splatt_tpu import trace

        with trace.enabling(opts.trace):
            with trace.span("dist.comm_select") as _sp:
                variant, step = _select_comm_sweep(
                    chain, mesh, nmodes, opts.regularization, dims_pad,
                    axis, cells_meta, inds, vals, cells_dev, factors,
                    grams, dtype, opts)
                _sp.set(variant=variant)
    if opts.verbosity >= Verbosity.HIGH:
        # the wire model follows the SELECTED strategy, not an all2all
        # assumption (ISSUE 8 satellite)
        for line in comm_volume_report(dims_pad, rank,
                                       np.dtype(dtype).itemsize, ndev=ndev,
                                       variant=variant):
            print(line)
    if variant in ("ring", "async_ring") and measure_overlap is not False \
            and (measure_overlap or opts.verbosity >= Verbosity.HIGH):
        # achieved-overlap metric (docs/ring.md): exchange time hidden
        # vs exposed, next to the wire model's per-device bytes —
        # reported as a ring_overlap run-report event so `splatt cpd
        # --json` distributed runs (the CLI passes measure_overlap=True
        # there) and MULTICHIP artifacts carry the number.  Auto only
        # at HIGH, like the other startup diagnostics: the measurement
        # compiles two extra programs and runs ~a dozen step-scale
        # invocations — not a cost every default run should pay.
        # Best-effort: a measurement failure must never take down the
        # run it measures.
        from splatt_tpu import resilience, trace

        try:
            with trace.enabling(opts.trace):
                ov = measure_ring_overlap(
                    mesh, nmodes, opts.regularization, dims_pad, axis,
                    variant, inds, vals, factors, grams, dtype,
                    step_fn=step)
            resilience.run_report().add("ring_overlap", **ov)
            if opts.verbosity >= Verbosity.LOW:
                print(f"  ring overlap [{ov['engine']}]: "
                      f"exchange {ov['exchange_s']:.4f}s, "
                      f"{100 * ov['overlap_frac']:.0f}% hidden "
                      f"(exposed {ov['exposed_comm_s']:.4f}s of "
                      f"step {ov['step_s']:.4f}s)")
        except Exception as e:
            cls = resilience.classify_failure(e)
            if opts.verbosity >= Verbosity.LOW:
                print(f"  ring overlap measurement skipped "
                      f"({cls.value}: {resilience.failure_message(e)[:120]})")

    out = run_distributed_als(step, factors, grams, rank, opts, xnormsq,
                              orig_dims, dtype, row_select=relabels,
                              checkpoint_path=checkpoint_path,
                              checkpoint_every=checkpoint_every,
                              resume=resume)
    if profiled:
        from splatt_tpu.parallel.common import dist_phase_report

        for line in dist_phase_report():
            print(line)
    return out
