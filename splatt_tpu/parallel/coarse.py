"""Coarse-grained decomposition: owner-computes over per-mode copies.

≙ the reference's COARSE decomposition (types_config.h:179-190,
src/cmds/mpi_cmd_cpd.c:223-258): each rank owns a contiguous block of
*every* mode's slices and keeps one filtered tensor copy per mode
(hence the ALLMODE CSF requirement).  Updating mode m needs **no
output reduction at all** — a rank holds every nonzero that touches its
rows of mode m — at the price of replicating the nonzeros nmodes times
and gathering the input factors.

TPU mapping over a 1-D mesh axis ``d``:
  - per mode m, nonzeros are sorted by mode m and bucketed by the
    equal row fences of axis d (pad cells to the max bucket);
  - factor m is row-sharded over d;
  - update m: ``all_gather`` the other factors (≙ mpi_update_rows),
    local gather-prod + segment-sum into the owned block, local solve,
    λ/Gram ``psum`` — and no reduce_rows anywhere.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from splatt_tpu.config import Options, default_opts, resolve_dtype
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import init_factors
from splatt_tpu.kruskal import KruskalTensor
from splatt_tpu.ops.mttkrp import acc_dtype
from splatt_tpu.parallel.common import (blocked_local_mttkrp, bucket_engine,
                                        bucket_scatter, fit_tail,
                                        mode_update_tail,
                                        run_distributed_als)
from splatt_tpu.parallel.mesh import make_mesh, single_axis_of
from splatt_tpu.utils.env import ceil_to


def _bucket_by_mode(tt: SparseTensor, mode: int, ndev: int, val_dtype,
                    streamed: Optional[bool] = None,
                    out_dir: Optional[str] = None,
                    chunk: int = 1 << 22):
    """Bucket nonzeros by the equal row fences of `mode`.

    Returns (inds (nmodes, ndev, C) int32 with mode-m indices local to
    the fence, vals (ndev, C), block_rows, counts).

    `streamed` (auto: when tt holds memmapped indices) runs the
    bucketing in chunked passes — host RSS O(chunk + bucket metadata)
    — with optionally disk-backed outputs under `out_dir`, so a
    beyond-RAM tensor builds its per-mode copies end-to-end.
    """
    from splatt_tpu.parallel.common import (is_memmapped,
                                            streamed_bucket_scatter)

    dim_pad = ceil_to(max(tt.dims[mode], ndev), ndev)
    block = dim_pad // ndev
    if streamed is None:
        streamed = is_memmapped(tt.inds)
    if streamed:
        def postprocess(placed):
            placed[mode] %= block
            return placed

        binds, bvals, _, counts = streamed_bucket_scatter(
            tt.inds, tt.vals, lambda ic, s: ic[mode] // block, ndev,
            val_dtype, chunk=chunk, out_dir=out_dir,
            postprocess=postprocess)
        return binds, bvals, block, counts
    owner = tt.inds[mode] // block
    binds, bvals, _, counts = bucket_scatter(tt.inds, tt.vals, owner, ndev,
                                             val_dtype)
    binds[mode] %= block  # localize to the fence (pad slots stay 0)
    return binds, bvals, block, counts


def coarse_cpd_als(tt: SparseTensor, rank: int, mesh: Optional[Mesh] = None,
                   opts: Optional[Options] = None,
                   init: Optional[List[jax.Array]] = None,
                   axis: str = "d",
                   local_engine: Optional[str] = None,
                   out_dir: Optional[str] = None,
                   row_distribute: Optional[str] = None,
                   checkpoint_path: Optional[str] = None,
                   checkpoint_every: int = 10,
                   resume: bool = True) -> KruskalTensor:
    """Distributed CPD-ALS, coarse-grained owner-computes.

    `row_distribute="balanced"` (docs/layout-balance.md): nnz-weighted
    row relabeling per mode (chains-on-chains style — the
    capacity-constrained LPT pack of balanced_relabel) before the
    equal fences are cut, so a hot slice no longer fattens one rank's
    bucket — every per-mode cell is padded to the FULLEST bucket, so
    bucket imbalance is wasted compute on every device.  Original row
    order is restored on gather (run_distributed_als row_select).

    `local_engine`: "blocked" (the default) sorts each per-mode bucket
    and runs the single-chip blocked MTTKRP engine inside the sweep
    (≙ mttkrp_csf over each rank's per-mode tensor copy); "stream"
    keeps the naive formulation (the differential oracle).  Memmapped
    (out-of-core) tensors keep the blocked engine: the buckets build
    via streamed chunked passes and the sorted layouts via the chunked
    counting sort (streamed_blocked_buckets) — both disk-backed under
    `out_dir` when given, so host RSS stays bounded at any scale
    (≙ every rank running the optimized mttkrp_csf regardless of
    tensor size, src/mpi/mpi_cpd.c:714).
    """
    import os

    opts = (opts or default_opts()).validate()
    mesh, axis = single_axis_of(mesh, axis)
    mesh = mesh or make_mesh(axis_names=(axis,))
    ndev = mesh.shape[axis]
    nmodes = tt.nmodes
    xnormsq = tt.normsq()
    dtype = resolve_dtype(opts, tt.vals.dtype)
    if local_engine is None:
        from splatt_tpu.parallel.common import auto_local_engine

        local_engine = auto_local_engine(tt, out_dir)
    if local_engine not in ("blocked", "stream"):
        raise ValueError(f"unknown local_engine {local_engine!r}")
    blocked = local_engine == "blocked"

    orig_dims = tt.dims
    relabels = None
    if row_distribute == "balanced":
        # nnz-weighted per-mode relabeling (docs/layout-balance.md):
        # rows LPT-packed into the equal fences by slice weight, so
        # every rank's bucket — and with it the pad-to-fullest cell —
        # balances.  All modes relabel at once: mode k's indices feed
        # the gathered factor-k lookups inside every other mode's
        # update, so the labeling must be globally consistent.
        from splatt_tpu.parallel.common import (balanced_relabel,
                                                relabel_tensor)

        relabels = []
        for m in range(nmodes):
            dim_pad = ceil_to(max(tt.dims[m], ndev), ndev)
            relabels.append(
                balanced_relabel(tt.mode_histogram(m), ndev,
                                 dim_pad // ndev)
                if ndev > 1 else None)
        tt = relabel_tensor(
            tt, relabels, tuple(ceil_to(max(d, ndev), ndev)
                                for d in tt.dims))
    elif row_distribute is not None:
        raise ValueError(f"unknown row_distribute {row_distribute!r} "
                         f"(coarse supports 'balanced')")

    # one sorted+bucketed copy per mode (≙ per-mode tensors + ALLMODE);
    # per-mode out_dir subdirs: the memmap file names inside are fixed
    per_mode = [_bucket_by_mode(
        tt, m, ndev, dtype,
        out_dir=(os.path.join(out_dir, f"mode{m}")
                 if out_dir is not None else None))
        for m in range(nmodes)]
    blocks = tuple(b for (_, _, b, _) in per_mode)
    dims_pad = tuple(b * ndev for b in blocks)
    # achieved bucket balance per mode (pad-to-fullest makes max/mean
    # exactly the wasted-compute factor): recorded for --json /
    # MULTICHIP (docs/layout-balance.md)
    from splatt_tpu.parallel.common import record_shard_imbalance

    for m, (_, _, _, counts) in enumerate(per_mode):
        record_shard_imbalance("coarse_bucket", counts,
                               policy=row_distribute or "equal", mode=m)
    nnz_sharding = NamedSharding(mesh, P(None, axis, None))
    val_sharding = NamedSharding(mesh, P(axis, None))
    if blocked:
        from splatt_tpu.parallel.common import build_bucket_layout

        cells = []
        inds_dev = []
        vals_dev = []
        rs_dev = []
        for m, (bi, bv, blk_rows, counts) in enumerate(per_mode):
            i, v, rs, blkk, S = build_bucket_layout(
                bi, bv, counts, m, blk_rows, opts.nnz_block,
                out_dir=(os.path.join(out_dir, f"mode{m}", "blocked")
                         if out_dir is not None else None))
            path, impl = bucket_engine(S, opts)
            cells.append(dict(block=blkk, seg_width=S, path=path,
                              impl=impl))
            inds_dev.append(jax.device_put(i, nnz_sharding))
            vals_dev.append(jax.device_put(v, val_sharding))
            rs_dev.append(jax.device_put(rs, val_sharding))
    else:
        cells = None
        inds_dev = [jax.device_put(i, nnz_sharding)
                    for (i, _, _, _) in per_mode]
        vals_dev = [jax.device_put(v, val_sharding)
                    for (_, v, _, _) in per_mode]
        rs_dev = []

    # init in the ORIGINAL row space (rank-count/distribution
    # invariance); relabels only affect placement
    factors_host = (init if init is not None
                    else init_factors(orig_dims, rank, opts.seed(),
                                      dtype=dtype))
    factors = []
    for m, U in enumerate(factors_host):
        U_pad = jnp.zeros((dims_pad[m], U.shape[1]), dtype=dtype)
        U = jnp.asarray(U, dtype=dtype)[:orig_dims[m]]
        if relabels is not None and relabels[m] is not None:
            U_pad = U_pad.at[jnp.asarray(relabels[m])].set(U)
        else:
            U_pad = U_pad.at[:orig_dims[m]].set(U)
        factors.append(jax.device_put(
            U_pad, NamedSharding(mesh, P(axis, None))))
    factors = tuple(factors)
    from splatt_tpu.ops.linalg import gram

    grams = tuple(jax.device_put(gram(U), NamedSharding(mesh, P()))
                  for U in factors)

    factor_specs = tuple([P(axis, None)] * nmodes)
    gram_specs = tuple([P()] * nmodes)
    inds_specs = tuple([P(None, axis, None)] * nmodes)
    vals_specs = tuple([P(axis, None)] * nmodes)
    rs_specs = (tuple([P(axis, None)] * nmodes) if blocked else ())
    reg = opts.regularization

    @partial(shard_map, mesh=mesh,
             in_specs=(inds_specs, vals_specs, rs_specs, factor_specs,
                       gram_specs, P()),
             out_specs=(factor_specs, gram_specs, P(), P(), P()),
             check_vma=False)
    def sweep(inds_l, vals_l, rs_l, factors_l, grams_l, first_flag):
        factors_l = list(factors_l)
        grams_l = list(grams_l)
        lam = None
        M_l = None
        for m in range(nmodes):
            ic = inds_l[m].reshape(nmodes, -1)
            vc = vals_l[m].reshape(-1)
            if blocked:
                # ≙ mpi_update_rows, then the rank-local optimized
                # MTTKRP over this mode's sorted copy — owner-computes:
                # NO output reduction
                fac_full = [
                    jax.lax.all_gather(factors_l[k], axis, axis=0,
                                       tiled=True) if k != m
                    else factors_l[m]  # local fence IS the row space
                    for k in range(nmodes)]
                M_l = blocked_local_mttkrp(
                    ic, vc, rs_l[m].reshape(-1), fac_full, m,
                    dim=blocks[m], block=cells[m]["block"],
                    seg_width=cells[m]["seg_width"],
                    path=cells[m]["path"], impl=cells[m]["impl"])
            else:
                prod = vc[:, None].astype(factors_l[0].dtype)
                for k in range(nmodes):
                    if k != m:
                        # ≙ mpi_update_rows: fetch the other factors
                        U = jax.lax.all_gather(factors_l[k], axis, axis=0,
                                               tiled=True)
                        prod = prod * jnp.take(U, ic[k], axis=0,
                                               mode="clip")
                # owner-computes: all nonzeros for my rows are local,
                # so the MTTKRP block needs NO reduction
                M_l = jax.ops.segment_sum(
                    prod.astype(acc_dtype(prod.dtype)), ic[m],
                    num_segments=blocks[m])
            U_l, gram, lam = mode_update_tail(M_l, grams_l, m, reg,
                                              first_flag, axis,
                                              store_dtype=dtype)
            factors_l[m] = U_l
            grams_l[m] = gram
        znormsq, inner = fit_tail(lam, grams_l, M_l, factors_l[nmodes - 1],
                                  axis)
        return tuple(factors_l), tuple(grams_l), lam, znormsq, inner

    sweep = jax.jit(sweep)

    def step(factors, grams, flag):
        return sweep(tuple(inds_dev), tuple(vals_dev), tuple(rs_dev),
                     factors, grams, flag)

    return run_distributed_als(step, factors, grams, rank, opts, xnormsq,
                               orig_dims, dtype, row_select=relabels,
                               checkpoint_path=checkpoint_path,
                               checkpoint_every=checkpoint_every,
                               resume=resume)
