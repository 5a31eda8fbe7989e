"""n-D medium-grained grid decomposition (≙ the reference's flagship
distributed mode: the cartesian MEDIUM decomposition of src/mpi/).

The reference arranges ranks in an n-D grid (one axis per tensor mode,
p_get_best_mpi_dim src/mpi/mpi_io.c:537-574), gives each rank the
nonzeros whose coordinates fall in its cell, and fences factor-row
ownership along each axis ("layers").  The payoff: **MTTKRP inputs are
always rank-local** (a cell's nonzeros only touch the factor blocks of
its own layers) and only the *output* rows must be summed across the
layer (src/mpi/mpi_cpd.c's reduce_rows), plus small Gram/λ allreduces.

TPU mapping, one `shard_map` over a mesh with one axis per mode:

  - factor m:  (dim_pad_m, R), sharded over axis `m<m>`, replicated on
    the other axes — exactly the reference's layer ownership.
  - nonzeros: host-compiled into cells, arrays shaped
    (g_0, ..., g_{n-1}, cell_nnz) so each device holds its own cell;
    indices stored *local to the cell's blocks* (≙ the reference
    relocalizing indices to layer coordinates, mpi_io.c:816-824).
  - mode-m update: local gather-prod (NO communication — inputs are
    local by construction) → segment-sum into the local row block →
    ``psum over every axis except m`` (the layer reduce — this is
    mpi_reduce_rows+mpi_update_rows collapsed into one collective,
    since afterwards every device in the layer holds the full summed
    block) → local solve → λ/Gram psum over axis m only.

Row fences are equal-sized (static shapes).  The reference instead
computes nnz-balanced fences (p_find_layer_boundaries) and relabels
rows; the TPU equivalent of that balancing is to apply a relabeling
permutation (splatt_tpu.reorder, e.g. `random`) before building the
grid — equal fences over a randomized labeling ≈ balanced cells, and
the permutation bookkeeping restores factor row order afterwards.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from splatt_tpu.config import Options, Verbosity, default_opts, resolve_dtype
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import init_factors
from splatt_tpu.kruskal import KruskalTensor
from splatt_tpu.ops.mttkrp import acc_dtype
from splatt_tpu.parallel.common import (balanced_relabel,
                                        blocked_local_mttkrp, bucket_engine,
                                        bucket_scatter, comm_volume_report,
                                        fit_tail, imbalance_report,
                                        mode_update_tail,
                                        run_distributed_als,
                                        streamed_bucket_scatter)
from splatt_tpu.parallel.mesh import auto_grid
from splatt_tpu.utils.env import ceil_to


def _axis(m: int) -> str:
    return f"m{m}"


@dataclasses.dataclass
class GridDecomp:
    """Host-compiled grid decomposition of a COO tensor.

    Arrays are laid out with one leading dim per grid axis so a
    NamedSharding puts exactly one cell on each device.
    """

    grid: Tuple[int, ...]
    dims_pad: Tuple[int, ...]      # per mode, divisible by grid[m]
    block_rows: Tuple[int, ...]    # dims_pad[m] // grid[m]
    cell_nnz: int                  # padded nnz per cell
    inds_local: np.ndarray         # (nmodes, *grid, cell_nnz) int32
    vals: np.ndarray               # (*grid, cell_nnz)
    nnz: int
    fill: float                    # nnz / (ncells * cell_nnz) — balance
    cell_counts: np.ndarray        # (ncells,) true occupancy per cell
    # per-mode old→new row label maps from the nnz-balanced fences
    # (None per mode = identity; ≙ the relabeling after
    # p_find_layer_boundaries / mpi_mat_distribute's perm)
    relabels: Optional[List[Optional[np.ndarray]]] = None

    @property
    def nmodes(self) -> int:
        return len(self.grid)

    @staticmethod
    def build(tt: SparseTensor, grid: Optional[Tuple[int, ...]] = None,
              n_devices: Optional[int] = None,
              val_dtype=np.float32,  # splint: ignore[SPL005] shard-builder signature default; callers override via Options.val_dtype
              balance: Optional[bool] = False,
              streamed: Optional[bool] = None,
              out_dir: Optional[str] = None,
              chunk: int = 1 << 22) -> "GridDecomp":
        """≙ mpi_tt_read's rearrange-to-owners (p_rearrange_medium,
        src/mpi/mpi_io.c:451-473) done as a host-side bucketing.

        `balance`: nnz-balance the row fences by relabeling rows
        (balanced_relabel ≙ p_find_layer_boundaries,
        src/mpi/mpi_io.c:365-439).  None = auto: balance when the
        equal-fence fill is poor (< 0.5) and the relabeling improves it
        — this is what *acts* on the fill statistic the reference
        prints.  Every cell is padded to the fullest cell, so fill is
        both the memory and the compute efficiency of the sweep.

        Default is False (no relabeling) because a relabeled build
        changes factor row placement: callers that scatter factors
        through :meth:`shard_factors` must restore order with
        :meth:`row_select` when gathering.  grid_cpd_als does and
        enables auto mode; direct build() users opt in explicitly.

        `streamed` (auto: when tt holds memmapped indices) bounds host
        RSS at O(chunk + cell metadata) by running the decomposition in
        chunked passes (streamed_bucket_scatter ≙ the reference's
        root-streamed chunk distribution, src/mpi/mpi_io.c:587-648);
        with `out_dir` the bucketed arrays are disk-backed memmaps, so
        a tensor bigger than host RAM decomposes end-to-end.
        """
        nmodes = tt.nmodes
        if grid is None:
            ndev = n_devices if n_devices is not None else len(jax.devices())
            grid = auto_grid(ndev, tt.dims)
        grid = tuple(int(g) for g in grid)
        dims_pad = tuple(ceil_to(max(d, g), g) for d, g in zip(tt.dims, grid))
        block_rows = tuple(dp // g for dp, g in zip(dims_pad, grid))
        ncells = int(np.prod(grid))
        if streamed is None:
            from splatt_tpu.parallel.common import is_memmapped

            streamed = is_memmapped(tt.inds)
        if streamed:
            return GridDecomp._build_streamed(
                tt, grid, dims_pad, block_rows, ncells, val_dtype,
                balance, out_dir, chunk)

        def cells_of(inds_rel):
            cell = np.zeros(tt.nnz, dtype=np.int64)
            for m in range(nmodes):
                cell = cell * grid[m] + inds_rel[m] // block_rows[m]
            return cell

        def fill_of(cell):
            if tt.nnz == 0:
                return 1.0
            counts = np.bincount(cell, minlength=ncells)
            return tt.nnz / max(ncells * int(counts.max()), 1)

        inds_rel = tt.inds
        relabels: Optional[List[Optional[np.ndarray]]] = None
        cell = cells_of(inds_rel)
        fill0 = fill_of(cell)
        if balance or (balance is None and fill0 < 0.5):
            rl = [balanced_relabel(tt.mode_histogram(m), grid[m],
                                   block_rows[m])
                  if grid[m] > 1 else None
                  for m in range(nmodes)]
            cand = np.stack([r[tt.inds[m]] if r is not None else tt.inds[m]
                             for m, r in enumerate(rl)])
            cell_b = cells_of(cand)
            if balance or fill_of(cell_b) > fill0:
                inds_rel, relabels, cell = cand, rl, cell_b

        binds, vals, cell_nnz, counts = bucket_scatter(inds_rel, tt.vals,
                                                       cell, ncells,
                                                       val_dtype)
        # localize indices to the cell's block fences (pad slots hold
        # index 0, and 0 % block == 0 — harmless)
        for m in range(nmodes):
            binds[m] %= block_rows[m]

        return GridDecomp(
            grid=grid, dims_pad=dims_pad, block_rows=block_rows,
            cell_nnz=cell_nnz,
            inds_local=binds.reshape((nmodes, *grid, cell_nnz)),
            vals=vals.reshape((*grid, cell_nnz)),
            nnz=tt.nnz,
            fill=tt.nnz / max(ncells * cell_nnz, 1),
            cell_counts=counts,
            relabels=relabels,
        )

    @staticmethod
    def _build_streamed(tt, grid, dims_pad, block_rows, ncells, val_dtype,
                        balance, out_dir, chunk) -> "GridDecomp":
        """Chunked-pass build: never materializes an O(nnz) temporary
        beyond the (optionally disk-backed) bucketed output itself."""
        nmodes = tt.nmodes
        nnz = tt.nnz

        def cells_of_chunk(ic, rl):
            cell = np.zeros(ic.shape[1], dtype=np.int64)
            for m in range(nmodes):
                col = rl[m][ic[m]] if rl and rl[m] is not None else ic[m]
                cell = cell * grid[m] + col // block_rows[m]
            return cell

        def counts_for(rl):
            from splatt_tpu.parallel.common import _drop_pages

            c = np.zeros(ncells, dtype=np.int64)
            for s in range(0, nnz, chunk):
                ic = np.asarray(tt.inds[:, s:min(nnz, s + chunk)])
                c += np.bincount(cells_of_chunk(ic, rl), minlength=ncells)
                # mapped input pages count toward RSS until advised
                # away — per-chunk keeps the pass O(chunk) resident
                _drop_pages(tt.inds)
            return c

        def fill_of(counts):
            return (nnz / max(ncells * int(counts.max()), 1)
                    if nnz else 1.0)

        def hist_of(m):
            from splatt_tpu.parallel.common import _drop_pages

            h = np.zeros(tt.dims[m], dtype=np.int64)
            col = tt.inds[m]
            for s in range(0, nnz, chunk):
                h += np.bincount(np.asarray(col[s:min(nnz, s + chunk)]),
                                 minlength=tt.dims[m])
                _drop_pages(tt.inds)
            return h

        relabels = None
        counts = counts_for(None)
        fill0 = fill_of(counts)
        if balance or (balance is None and fill0 < 0.5):
            cand = [balanced_relabel(hist_of(m), grid[m], block_rows[m])
                    if grid[m] > 1 else None for m in range(nmodes)]
            counts_b = counts_for(cand)
            if balance or fill_of(counts_b) > fill0:
                relabels, counts = cand, counts_b

        def postprocess(placed):
            for m in range(nmodes):
                rl = relabels[m] if relabels is not None else None
                col = rl[placed[m]] if rl is not None else placed[m]
                placed[m] = col % block_rows[m]
            return placed

        # counts already computed while deciding balance: the scatter
        # needs only one more pass over the tensor
        binds, bvals, cell_nnz, counts = streamed_bucket_scatter(
            tt.inds, tt.vals,
            lambda ic, s: cells_of_chunk(ic, relabels),
            ncells, val_dtype, chunk=chunk, out_dir=out_dir,
            postprocess=postprocess, counts=counts)

        return GridDecomp(
            grid=grid, dims_pad=dims_pad, block_rows=block_rows,
            cell_nnz=cell_nnz,
            inds_local=binds.reshape((nmodes, *grid, cell_nnz)),
            vals=bvals.reshape((*grid, cell_nnz)),
            nnz=nnz,
            fill=nnz / max(ncells * cell_nnz, 1),
            cell_counts=counts,
            relabels=relabels,
        )

    def make_mesh(self, devices=None) -> Mesh:
        devs = list(devices if devices is not None else jax.devices())
        n = int(np.prod(self.grid))
        if n > len(devs):
            grid = "x".join(str(g) for g in self.grid)
            raise ValueError(f"grid {grid} needs {n} devices, only "
                             f"{len(devs)} available")
        mesh_devs = np.array(devs[:n]).reshape(self.grid)
        return Mesh(mesh_devs, tuple(_axis(m) for m in range(self.nmodes)))

    def device_put(self, mesh: Mesh):
        axes = [_axis(m) for m in range(self.nmodes)]
        inds = jax.device_put(
            self.inds_local, NamedSharding(mesh, P(None, *axes, None)))
        vals = jax.device_put(
            self.vals, NamedSharding(mesh, P(*axes, None)))
        return inds, vals

    def shard_factors(self, factors: List[jax.Array], mesh: Mesh):
        out = []
        for m, U in enumerate(factors):
            dp = self.dims_pad[m]
            U_pad = jnp.zeros((dp, U.shape[1]), dtype=U.dtype)
            rl = self.relabels[m] if self.relabels is not None else None
            if rl is None:
                U_pad = U_pad.at[:U.shape[0]].set(U)
            else:
                # balanced fences: row `old` lives at label rl[old]
                U_pad = U_pad.at[jnp.asarray(rl)].set(U)
            out.append(jax.device_put(
                U_pad, NamedSharding(mesh, P(_axis(m), None))))
        return tuple(out)

    def row_select(self) -> Optional[List[Optional[np.ndarray]]]:
        """Per-mode gather indices restoring original row order from a
        padded factor (for run_distributed_als)."""
        return None if self.relabels is None else list(self.relabels)

    def build_cell_layouts(self, opts: Options,
                           out_dir: Optional[str] = None,
                           chunk: int = 1 << 22) -> "CellLayouts":
        """Per-cell sorted blocked layouts so the sweep runs the
        single-chip blocked MTTKRP engine inside every cell (≙ each
        rank building CSF over its local nonzeros and calling the same
        optimized mttkrp_csf, src/mpi/mpi_cpd.c:714).

        `opts.block_alloc` governs the layout count exactly like the
        single-chip compiler (≙ splatt_csf_alloc): ONEMODE/TWOMODE
        build 1–2 sorted copies and the remaining modes run the
        generic scatter path on the first; ALLMODE builds one per mode.

        Memmapped (disk-backed streamed) decompositions sort via the
        chunked counting-sort build, with the layout memmaps under
        `out_dir` (default: beside the decomposition's own files) —
        the blocked engine survives out-of-core scale.
        """
        from splatt_tpu.parallel.common import (_memmap_dir,
                                                alloc_build_modes,
                                                build_bucket_layout,
                                                is_memmapped)

        nmodes = self.nmodes
        ncells = int(np.prod(self.grid))
        binds = self.inds_local.reshape(nmodes, ncells, -1)
        bvals = self.vals.reshape(ncells, -1)
        if is_memmapped(binds) and out_dir is None:
            out_dir = _memmap_dir(binds)
        build_modes = alloc_build_modes(
            [self.block_rows[m] for m in range(nmodes)], opts)
        if out_dir is not None and opts.verbosity >= Verbosity.LOW:
            # another full sorted copy per build mode lands on disk —
            # say where and how big BEFORE writing, so a silently
            # chosen directory (beside the user's decomposition files)
            # and its space cost are observable.  These memmaps persist
            # after the run: cleanup is the caller's job (docs/).
            per_mode = (binds.size * binds.itemsize
                        + bvals.size * bvals.itemsize)
            print(f"  cell layouts: memmapped under {out_dir} "
                  f"(cells_m<mode>/, ~{per_mode / 1e9:.2f} GB per build "
                  f"mode x {len(build_modes)} mode(s)); not cleaned up "
                  f"automatically")
        layouts = []
        for m in build_modes:
            i, v, rs, blk, S = build_bucket_layout(
                binds, bvals, self.cell_counts, m, self.block_rows[m],
                opts.nnz_block, chunk=chunk,
                out_dir=(os.path.join(out_dir, f"cells_m{m}")
                         if out_dir is not None else None))
            path, impl = bucket_engine(S, opts)
            layouts.append(dict(
                inds=i.reshape((nmodes, *self.grid, -1)),
                vals=v.reshape((*self.grid, -1)),
                row_start=rs.reshape((*self.grid, -1)),
                block=blk, seg_width=S, path=path, impl=impl,
                sort_mode=m, sort_dim=self.block_rows[m]))
        mode_map = {m: (build_modes.index(m) if m in build_modes else 0)
                    for m in range(nmodes)}
        return CellLayouts(layouts=layouts, mode_map=mode_map)


@dataclasses.dataclass
class CellLayouts:
    """Sorted+blocked cell arrays for the grid sweep, one entry per
    built layout plus a mode→layout map (see
    GridDecomp.build_cell_layouts)."""

    layouts: List[dict]
    mode_map: dict

    def device_put(self, mesh: Mesh, nmodes: int):
        """Per-MODE cell dicts for the sweep; layouts device_put once
        and shared by reference across the modes that map to them.
        A mode whose layout is sorted for another mode runs the
        generic scatter path (≙ an internal/leaf CSF traversal)."""
        axes = [_axis(m) for m in range(nmodes)]
        placed = []
        for lay in self.layouts:
            placed.append(dict(
                inds=jax.device_put(lay["inds"],
                                    NamedSharding(mesh, P(None, *axes, None))),
                vals=jax.device_put(lay["vals"],
                                    NamedSharding(mesh, P(*axes, None))),
                row_start=jax.device_put(
                    lay["row_start"], NamedSharding(mesh, P(*axes, None))),
                block=lay["block"], seg_width=lay["seg_width"],
                path=lay["path"], impl=lay["impl"],
                sort_mode=lay["sort_mode"], sort_dim=lay["sort_dim"]))
        out = []
        for m in range(nmodes):
            lay = dict(placed[self.mode_map[m]])
            if lay["sort_mode"] != m:
                lay["path"] = "scatter"
            out.append(lay)
        return out


def make_grid_sweep(mesh: Mesh, decomp: GridDecomp, reg: float,
                    cells: Optional[List[dict]] = None):
    """One jitted shard_mapped ALS sweep over the n-D grid.

    With `cells` (the per-mode dicts from CellLayouts.device_put): the
    local MTTKRP runs the single-chip blocked engine over each cell's
    sorted arrays (≙ mpi ranks reusing the optimized mttkrp_csf,
    mpi_cpd.c:714); without, the naive stream formulation (kept as the
    differential oracle for the blocked sweep).
    """
    nmodes = decomp.nmodes
    axes = [_axis(m) for m in range(nmodes)]
    factor_specs = tuple(P(_axis(m), None) for m in range(nmodes))
    gram_specs = tuple([P()] * nmodes)
    block_rows = decomp.block_rows
    cell_specs = tuple(
        (P(None, *axes, None), P(*axes, None), P(*axes, None))
        for _ in range(nmodes)) if cells is not None else ()

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, *axes, None), P(*axes, None),
                       factor_specs, gram_specs, P(), cell_specs),
             out_specs=(factor_specs, gram_specs, P(), P(), P()),
             check_vma=False)
    def sweep(inds_l, vals_l, factors_l, grams_l, first_flag, cells_l):
        factors_l = list(factors_l)
        grams_l = list(grams_l)
        dtype = factors_l[0].dtype
        # local cell views: squeeze the grid axes (all size 1 per device)
        inds_c = inds_l.reshape(nmodes, -1)
        vals_c = vals_l.reshape(-1)
        lam = None
        M_l = None
        for m in range(nmodes):
            # inputs are cell-local: no communication (the medium-grain
            # payoff — ≙ only layer rows ever being touched)
            if cells is not None:
                ci, cv, crs = cells_l[m]
                partial_out = blocked_local_mttkrp(
                    ci.reshape(nmodes, -1), cv.reshape(-1),
                    crs.reshape(-1), factors_l, m,
                    dim=cells[m]["sort_dim"], block=cells[m]["block"],
                    seg_width=cells[m]["seg_width"],
                    path=cells[m]["path"], impl=cells[m]["impl"],
                    sort_mode=cells[m]["sort_mode"])
            else:
                prod = vals_c[:, None].astype(dtype)
                for k in range(nmodes):
                    if k != m:
                        prod = prod * jnp.take(factors_l[k], inds_c[k],
                                               axis=0, mode="clip")
                partial_out = jax.ops.segment_sum(
                    prod.astype(acc_dtype(prod.dtype)), inds_c[m],
                    num_segments=block_rows[m])
            # layer reduce (≙ mpi_reduce_rows + mpi_update_rows): after
            # this, every device in the mode-m layer holds the block
            other_axes = tuple(axes[k] for k in range(nmodes) if k != m)
            M_l = jax.lax.psum(partial_out, other_axes) if other_axes \
                else partial_out
            # λ/Gram allreduce over the owning axis only (blocks on the
            # other axes are replicas)
            U_l, gram, lam = mode_update_tail(M_l, grams_l, m, reg,
                                              first_flag, axes[m],
                                              store_dtype=dtype)
            factors_l[m] = U_l
            grams_l[m] = gram
        znormsq, inner = fit_tail(lam, grams_l, M_l, factors_l[nmodes - 1],
                                  axes[nmodes - 1])
        return tuple(factors_l), tuple(grams_l), lam, znormsq, inner

    return jax.jit(sweep)


def make_grid_profiled_sweep(mesh: Mesh, decomp: GridDecomp, reg: float,
                             store_dtype, cells: Optional[List[dict]] = None):
    """Split-jit profiled grid sweep: each phase (local MTTKRP, layer
    reduce, solve/normalize/gram update, fit) is its own shard_mapped
    program bracketed by blocking timers, so the mttkrp-vs-collective-
    vs-solve split is MEASURED (≙ mpi_time_stats reporting per-phase
    avg/max across ranks, src/mpi/mpi_cpd.c:893-939 — SPMD phases are
    barrier-synchronized, so wall clock IS the across-device max).
    Costs cross-phase fusion; the fused :func:`make_grid_sweep` is the
    production path.
    """
    nmodes = decomp.nmodes
    axes = [_axis(m) for m in range(nmodes)]
    factor_specs = tuple(P(_axis(m), None) for m in range(nmodes))
    gram_specs = tuple([P()] * nmodes)
    block_rows = decomp.block_rows
    cell_spec = (P(None, *axes, None), P(*axes, None), P(*axes, None))

    def make_local(m):
        in_specs = ((P(None, *axes, None), P(*axes, None), factor_specs)
                    + ((cell_spec,) if cells is not None else ()))

        @partial(shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=P(*axes, None, None), check_vma=False)
        def local_m(inds_l, vals_l, factors_l, *cell_m):
            if cells is not None:
                ci, cv, crs = cell_m[0]
                part = blocked_local_mttkrp(
                    ci.reshape(nmodes, -1), cv.reshape(-1),
                    crs.reshape(-1), list(factors_l), m,
                    dim=cells[m]["sort_dim"], block=cells[m]["block"],
                    seg_width=cells[m]["seg_width"],
                    path=cells[m]["path"], impl=cells[m]["impl"],
                    sort_mode=cells[m]["sort_mode"])
            else:
                inds_c = inds_l.reshape(nmodes, -1)
                vals_c = vals_l.reshape(-1)
                prod = vals_c[:, None].astype(factors_l[0].dtype)
                for k in range(nmodes):
                    if k != m:
                        prod = prod * jnp.take(factors_l[k], inds_c[k],
                                               axis=0, mode="clip")
                part = jax.ops.segment_sum(
                    prod.astype(acc_dtype(prod.dtype)), inds_c[m],
                    num_segments=block_rows[m])
            return part.reshape((1,) * nmodes + part.shape)

        return jax.jit(local_m)

    def make_reduce(m):
        other_axes = tuple(axes[k] for k in range(nmodes) if k != m)

        @partial(shard_map, mesh=mesh, in_specs=(P(*axes, None, None),),
                 out_specs=P(_axis(m), None), check_vma=False)
        def reduce_m(parts_l):
            p = parts_l.reshape(parts_l.shape[-2:])
            return jax.lax.psum(p, other_axes) if other_axes else p

        return jax.jit(reduce_m)

    def make_update(m):
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(_axis(m), None), gram_specs, P()),
                 out_specs=(P(_axis(m), None), P(), P()),
                 check_vma=False)
        def update_m(M_l, grams_l, flag):
            return mode_update_tail(M_l, list(grams_l), m, reg, flag,
                                    axes[m], store_dtype=store_dtype)

        return jax.jit(update_m)

    last = nmodes - 1

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), gram_specs, P(_axis(last), None),
                       P(_axis(last), None)),
             out_specs=(P(), P()), check_vma=False)
    def fit_fn(lam, grams_l, M_l, U_l):
        return fit_tail(lam, list(grams_l), M_l, U_l, axes[last])

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
            extra = (cells_dev[m],) if cells is not None else ()
            with timers.time("dist_mttkrp"):
                parts = sync(locals_[m](inds, vals, tuple(factors),
                                        *extra))
            with timers.time("dist_comm"):
                M = sync(reduces[m](parts))
            with timers.time("dist_update"):
                factors[m], grams[m], lam = sync(
                    updates[m](M, tuple(grams), flag))
        with timers.time("dist_fit"):
            znormsq, inner = sync(fit_jit(lam, tuple(grams), M,
                                          factors[last]))
        return tuple(factors), tuple(grams), lam, znormsq, inner

    return sweep


def grid_cpd_als(tt: SparseTensor, rank: int,
                 grid: Optional[Tuple[int, ...]] = None,
                 mesh: Optional[Mesh] = None,
                 opts: Optional[Options] = None,
                 init: Optional[List[jax.Array]] = None,
                 relabel: Optional[str] = None,
                 local_engine: Optional[str] = None,
                 out_dir: Optional[str] = None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 10,
                 resume: bool = True) -> KruskalTensor:
    """Distributed CPD-ALS over an n-D grid mesh (MEDIUM decomposition).

    `local_engine`: "blocked" (the default) runs the single-chip
    blocked MTTKRP engine inside every cell over per-cell sorted
    layouts (≙ mttkrp_csf per rank, mpi_cpd.c:714); "stream" keeps the
    naive gather+segment_sum formulation (the differential oracle).
    Memmapped (out-of-core) tensors keep the blocked engine: the
    decomposition builds via streamed chunked passes and the cell
    layouts via the chunked counting sort, disk-backed under `out_dir`
    when given — bounded host RSS at any scale.

    `relabel` picks the fence-balancing strategy:

    - "balanced" (also the automatic default when the equal-fence fill
      is poor): nnz-balanced fences via capacity-constrained row
      relabeling (balanced_relabel ≙ p_find_layer_boundaries,
      src/mpi/mpi_io.c:365-439);
    - any splatt_tpu.reorder PERM_TYPES entry ("random"/"graph"/
      "hgraph"/"fibsched"): a full index relabeling before decomposing
      — equal fences over relabeled indices ≈ balanced statistically.

    Factor row order is restored afterwards in both cases.
    """
    opts = (opts or default_opts()).validate()
    dtype = resolve_dtype(opts, tt.vals.dtype)

    balance = None  # auto: balance when equal fences fill poorly
    if relabel == "balanced":
        balance, relabel = True, None
    elif relabel is not None:
        balance = False  # explicit relabeling supersedes fence balancing
    perm = None
    if relabel is not None:
        if checkpoint_path is not None:
            # a PERM_TYPES relabel permutes the index space BEFORE the
            # decomposition, so checkpoints would be written in the
            # permuted row space — indistinguishable by shape from an
            # original-space checkpoint on resume.  Refuse loudly
            # rather than silently resume wrong rows.
            raise ValueError(
                "checkpoint_path cannot be combined with a PERM_TYPES "
                "relabel (checkpoints would be in the permuted row "
                "space); use relabel='balanced' or checkpoint without "
                "relabeling")
        from splatt_tpu.reorder import reorder

        perm = reorder(tt, relabel, seed=opts.seed())
        tt = perm.apply(tt)
        if init is not None:
            # init rows are in original labels; move them to relabeled
            # space (row new = row iperm[new] of the original)
            init = [np.asarray(U)[perm.iperms[m]]
                    if perm.iperms[m] is not None else U
                    for m, U in enumerate(init)]

    # A user-supplied mesh either already has the m<k> grid axes (use its
    # shape as the grid) or is treated as a pool of devices to arrange.
    devices = None
    if mesh is not None:
        expected = tuple(_axis(m) for m in range(tt.nmodes))
        if tuple(mesh.axis_names) == expected:
            grid = grid or tuple(mesh.shape[a] for a in expected)
        else:
            devices = list(np.asarray(mesh.devices).flatten())
            grid = grid or auto_grid(len(devices), tt.dims)
            mesh = None

    decomp = GridDecomp.build(tt, grid=grid,
                              n_devices=len(devices) if devices else None,
                              val_dtype=dtype, balance=balance,
                              out_dir=(os.path.join(out_dir, "scatter")
                                       if out_dir is not None else None))
    mesh = mesh or decomp.make_mesh(devices=devices)
    xnormsq = tt.normsq()

    # achieved cell balance, always recorded (layout_imbalance rides
    # --json / MULTICHIP — docs/layout-balance.md): every cell is
    # padded to the fullest, so max/mean IS the wasted-compute factor
    from splatt_tpu.parallel.common import record_shard_imbalance

    record_shard_imbalance(
        "grid_cell", decomp.cell_counts,
        policy=("balanced" if decomp.relabels is not None else "equal"),
        fill=round(float(decomp.fill), 3))

    if opts.verbosity >= Verbosity.HIGH:
        # ≙ mpi_rank_stats + mpi_send_recv_stats (src/stats.c:298-457,
        # src/splatt_mpi.h:453-463)
        print(f"GRID {'x'.join(str(g) for g in decomp.grid)} "
              f"fill={decomp.fill:0.2f}")
        print(imbalance_report(decomp.cell_counts, "cell"))
        for line in comm_volume_report(
                decomp.dims_pad, rank,
                np.dtype(dtype).itemsize, grid=decomp.grid):
            print(line)

    cells_dev = ()
    cells_host = None
    if local_engine is None:
        from splatt_tpu.parallel.common import auto_local_engine

        local_engine = auto_local_engine(tt, out_dir)
    if local_engine == "blocked":
        cells_host = decomp.build_cell_layouts(
            opts, out_dir=out_dir).device_put(mesh, tt.nmodes)
    elif local_engine != "stream":
        raise ValueError(f"unknown local_engine {local_engine!r}")
    if cells_host is not None:
        cells_dev = tuple((c["inds"], c["vals"], c["row_start"])
                          for c in cells_host)
        # the blocked sweep never reads the stream COO arrays — put
        # 1-entry dummies instead of keeping a dead O(nnz) copy in HBM
        axes_p = [_axis(m) for m in range(tt.nmodes)]
        inds = jax.device_put(
            np.zeros((tt.nmodes, *decomp.grid, 1), np.int32),
            NamedSharding(mesh, P(None, *axes_p, None)))
        vals = jax.device_put(
            np.zeros((*decomp.grid, 1), dtype),
            NamedSharding(mesh, P(*axes_p, None)))
    else:
        inds, vals = decomp.device_put(mesh)
    factors_host = (init if init is not None
                    else init_factors(tt.dims, rank, opts.seed(),
                                      dtype=dtype))
    factors = decomp.shard_factors(
        [jnp.asarray(f, dtype=dtype) for f in factors_host], mesh)
    from splatt_tpu.ops.linalg import gram

    gram_sharding = NamedSharding(mesh, P())
    grams = tuple(jax.device_put(gram(U), gram_sharding) for U in factors)

    profiled = opts.verbosity >= Verbosity.HIGH
    if profiled:
        # split-jit phases with blocking timers: measured per-phase
        # attribution (≙ mpi_time_stats) at the cost of fusion
        sweep = make_grid_profiled_sweep(mesh, decomp,
                                         opts.regularization, dtype,
                                         cells=cells_host)
    else:
        sweep = make_grid_sweep(mesh, decomp, opts.regularization,
                                cells=cells_host)

    def step(factors, grams, flag):
        return sweep(inds, vals, factors, grams, flag, cells_dev)

    if profiled:
        from splatt_tpu.parallel.common import wrap_profiled_step

        step = wrap_profiled_step(step)

    out = run_distributed_als(step, factors, grams, rank, opts, xnormsq,
                              tt.dims, dtype,
                              row_select=decomp.row_select(),
                              checkpoint_path=checkpoint_path,
                              checkpoint_every=checkpoint_every,
                              resume=resume)
    if profiled:
        from splatt_tpu.parallel.common import dist_phase_report

        for line in dist_phase_report():
            print(line)
    if perm is not None:
        out = KruskalTensor(
            factors=[jnp.asarray(perm.apply_to_factor(np.asarray(U), m))
                     for m, U in enumerate(out.factors)],
            lam=out.lam, fit=out.fit)
    return out
