"""MTTKRP algorithm comparison harness (≙ src/bench.c + cmd_bench.c).

The reference's `splatt bench` times MTTKRP algorithms {splatt, csf,
giga, ttbox, coord} per mode with thread scaling (src/bench.c:50-436).
The TPU equivalents are the execution paths of
:mod:`splatt_tpu.ops.mttkrp`: {stream, sorted_onehot(+pallas),
privatized, scatter}; thread scaling has no analog (XLA owns the chip),
so the sweep axis is the path × engine matrix instead.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import BlockAlloc, Options, resolve_dtype
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import init_factors
from splatt_tpu.ops.mttkrp import (choose_impl, mttkrp_blocked,
                                   mttkrp_stream, mttkrp_ttbox)

ALGS = ("stream", "blocked", "blocked_pallas", "scatter", "ttbox",
        "native")


def _alg_plan(alg: str, layout, mode: int, dim: int, opts: Options):
    """Map a bench algorithm name to (path, impl) for mttkrp_blocked,
    or None when the config cannot run (privatized width over cap).
    Raises on unknown names — shared by timing and cross-checking."""
    if alg == "scatter":
        return (("sorted_scatter" if layout.mode == mode else "scatter"),
                "xla")
    if alg in ("blocked", "blocked_pallas"):
        path = "sorted_onehot" if layout.mode == mode else "privatized"
        if path == "privatized" and dim + 16 > opts.priv_cap:
            return None
        impl = ("xla" if alg == "blocked" else choose_impl(
            Options(use_pallas=True, val_dtype=opts.val_dtype)))
        return path, impl
    raise ValueError(f"unknown algorithm {alg!r}")


def _time_call(fn, warmup: int = 1, reps: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_mttkrp(tt: SparseTensor, rank: int = 16,
                 algs: Sequence[str] = ALGS,
                 opts: Optional[Options] = None,
                 reps: int = 3, return_layouts: bool = False):
    """Per-mode wall clock for each algorithm; returns alg -> [sec/mode]
    (with `return_layouts`, also the per-mode ModeLayouts for the
    roofline model).

    ≙ the per-mode timing loop of src/bench.c:84-117.
    """
    opts = opts or Options(block_alloc=BlockAlloc.ALLMODE)
    dtype = resolve_dtype(opts, tt.vals.dtype)
    factors = init_factors(tt.dims, rank, opts.seed() or 1, dtype=dtype)
    inds = jnp.asarray(tt.inds)
    vals = jnp.asarray(tt.vals, dtype=dtype)
    results: Dict[str, List[float]] = {}

    needs_blocked = any(a not in ("stream", "ttbox") for a in algs)
    bs = BlockedSparse.from_coo(tt, opts) if needs_blocked else None

    for alg in algs:
        times: List[float] = []
        for mode in range(tt.nmodes):
            if alg == "stream":
                fn = lambda: mttkrp_stream(inds, vals, factors, mode,
                                           tt.dims[mode])
            elif alg == "ttbox":
                fn = lambda: mttkrp_ttbox(inds, vals, factors, mode,
                                          tt.dims[mode])
            elif alg == "native":
                from splatt_tpu.ops.mttkrp import _run_native, plan_mttkrp

                layout = bs.layout_for(mode)
                if plan_mttkrp(bs, factors, mode,
                               impl="native").engine != "native":
                    times.append(float("nan"))
                    continue
                fn = lambda: _run_native(layout, factors, mode)
            else:
                layout = bs.layout_for(mode)
                plan = _alg_plan(alg, layout, mode, tt.dims[mode], opts)
                if plan is None:
                    times.append(float("nan"))
                    continue
                path, impl = plan
                fn = lambda: mttkrp_blocked(layout, factors, mode,
                                            path=path, impl=impl)
            times.append(_time_call(fn, reps=reps))
        results[alg] = times
    if return_layouts:
        layouts = ([bs.layout_for(m) for m in range(tt.nmodes)]
                   if bs is not None else None)
        return results, layouts
    return results


def crosscheck_mttkrp(tt: SparseTensor, rank: int = 16,
                      algs: Sequence[str] = ALGS,
                      opts: Optional[Options] = None) -> float:
    """Verify every algorithm computes the same MTTKRP: max deviation
    from the stream result over all modes, *relative* to the result's
    magnitude (summation-order noise scales with value magnitudes and
    nnz).  ≙ the role of the reference's `bench --write` dumps:
    cross-validating algorithm outputs rather than timing them."""
    import sys


    opts = opts or Options(block_alloc=BlockAlloc.ALLMODE)
    dtype = resolve_dtype(opts, tt.vals.dtype)
    factors = init_factors(tt.dims, rank, opts.seed() or 1, dtype=dtype)
    inds = jnp.asarray(tt.inds)
    vals = jnp.asarray(tt.vals, dtype=dtype)
    bs = BlockedSparse.from_coo(tt, opts)
    worst = 0.0
    skipped = 0
    for mode in range(tt.nmodes):
        ref = np.asarray(mttkrp_stream(inds, vals, factors, mode,
                                       tt.dims[mode]))
        for alg in algs:
            if alg == "stream":
                continue
            if alg == "ttbox":
                out = mttkrp_ttbox(inds, vals, factors, mode,
                                   tt.dims[mode])
            elif alg == "native":
                from splatt_tpu.ops.mttkrp import _run_native, plan_mttkrp

                layout = bs.layout_for(mode)
                out = (_run_native(layout, factors, mode)
                       if plan_mttkrp(bs, factors, mode,
                                      impl="native").engine == "native"
                       else None)
                if out is None:
                    skipped += 1
                    continue
            else:
                layout = bs.layout_for(mode)
                plan = _alg_plan(alg, layout, mode, tt.dims[mode], opts)
                if plan is None:
                    skipped += 1
                    continue
                path, impl = plan
                out = mttkrp_blocked(layout, factors, mode, path=path,
                                     impl=impl)
            scale = max(float(np.max(np.abs(ref))), 1.0)
            dev = float(np.max(np.abs(np.asarray(out) - ref))) / scale
            worst = max(worst, dev)
    if skipped:
        print(f"crosscheck: {skipped} (alg, mode) configs skipped "
              f"(privatized width over priv_cap)", file=sys.stderr)
    return worst


def format_bench(results: Dict[str, List[float]]) -> str:
    lines = []
    for alg, times in results.items():
        cols = "  ".join(f"mode{m}: {'  nan  ' if np.isnan(t) else f'{t:0.5f}'}"
                         for m, t in enumerate(times))
        total = np.nansum(times)
        lines.append(f"  {alg:<16s} {cols}  total: {total:0.5f}s")
    return "\n".join(lines)


# -- roofline model ---------------------------------------------------------

def hbm_peak_gbs() -> Optional[float]:
    """Peak HBM bandwidth of device 0 (splatt_tpu/devices.py), or None
    off-TPU; an unknown TPU kind raises."""
    from splatt_tpu.devices import device_spec

    spec = device_spec()
    return spec.hbm_gbs if spec is not None else None


def mttkrp_bytes(alg: str, tt: SparseTensor, rank: int, mode: int,
                 itemsize: int, layout=None) -> float:
    """First-order HBM bytes moved by one MTTKRP (the roofline model
    the blocked format was designed against; ≙ the hand arithmetic of
    the reference's perf analysis).  Counts logical traffic: index +
    value streams, one factor-row fetch per nonzero per input mode
    (gathers on sparse coordinates miss), and the output — plus each
    algorithm's own intermediates:

    - stream/scatter: gather+Hadamard fuse into the segment/scatter
      sum, no intermediate;
    - blocked (one-hot, xla_scan engine): block partials (nb, S, R)
      written then scatter-combined (read+write);
    - blocked_pallas fused engines: the factor TABLES stream once
      (VMEM-resident) instead of once per nonzero — the design's
      whole premise — plus the same partials;
    - ttbox: one full index+value pass per rank column.
    """
    nnz = tt.nnz
    nmodes = tt.nmodes
    acc = 4  # f32 accumulator width
    out = tt.dims[mode] * rank * acc
    idx_val = nnz * (nmodes * 4 + itemsize)
    rows = (nmodes - 1) * nnz * rank * itemsize
    if alg == "stream":
        return idx_val + rows + out
    if alg == "ttbox":
        return rank * (idx_val + (nmodes - 1) * nnz * itemsize) + out
    if alg == "scatter":
        return idx_val + rows + out
    if alg in ("blocked", "blocked_pallas"):
        nb = layout.nblocks if layout is not None else 1
        S = layout.seg_width if layout is not None else 8
        partials = 2 * nb * S * rank * acc
        if alg == "blocked_pallas":
            tables = sum(d * rank * itemsize
                         for k, d in enumerate(tt.dims) if k != mode)
            return idx_val + tables + partials + out
        return idx_val + rows + partials + out
    if alg == "native":
        return idx_val + rows + out
    raise ValueError(f"unknown algorithm {alg!r}")


def mttkrp_bytes_encoded(alg: str, X: BlockedSparse, rank: int, mode: int,
                         factor_itemsize: int) -> float:
    """ACHIEVED HBM bytes of one MTTKRP over a compiled
    :class:`BlockedSparse` — the same traffic structure as
    :func:`mttkrp_bytes`, but the index/value streams are costed at the
    layout's STORED widths (``ModeLayout.storage_bytes``: narrow v2
    local indices + per-block bases, bf16 values) and the factor terms
    at the factors' actual itemsize.  This is what bench reports per
    path (docs/format.md): the fixed i32/f32 model would claim the
    compact format moves bytes it no longer does.
    """
    lay = X.layout_for(mode)
    nmodes, nnz = lay.nmodes, lay.nnz
    acc = 4  # f32 accumulator width
    out = X.dims[mode] * rank * acc
    streams = lay.storage_bytes()     # encoded idx + bases + vals + starts
    if getattr(lay, "encoding", "v1") == "dense":
        # dense tile layout (docs/dense.md): value tiles + pad mask
        # stream once (storage_bytes — ZERO index bytes, the point of
        # the format), the non-mode factor tables stream once into the
        # Khatri-Rao operand, and the KR matrix (span x R) is
        # materialized (write + read)
        tables = sum(d * rank * factor_itemsize
                     for k, d in enumerate(X.dims) if k != mode)
        kr = 2 * lay.span * rank * factor_itemsize
        return streams + tables + kr + out
    rows = (nmodes - 1) * nnz * rank * factor_itemsize
    if alg in ("blocked", "blocked_pallas"):
        partials = 2 * lay.nblocks * lay.seg_width * rank * acc
        if alg == "blocked_pallas":
            tables = sum(d * rank * factor_itemsize
                         for k, d in enumerate(X.dims) if k != mode)
            return streams + tables + partials + out
        return streams + rows + partials + out
    # stream/scatter formulation over the layout's encoded arrays
    return streams + rows + out


def mttkrp_decode_bytes(X: BlockedSparse, rank: int, mode: int,
                        engine: str) -> float:
    """Extra HBM bytes the named engine's operand prep spends DECODING
    an encoded layout before its kernel runs (docs/format.md) — the
    traffic the in-kernel decode line exists to delete.  Zero for v1
    layouts and for the stream-native engines
    (:data:`splatt_tpu.ops.mttkrp.STREAM_NATIVE_ENGINES`: xla_scan
    decodes per scan chunk, the xla scatter inside its fusion).  The prep-decoding Pallas engines rematerialize
    every mode's global-i32 stream (write + read), and the transposed-
    table kernels additionally stream the sublane-replicated request
    tiles ``_prep_t_operands`` materializes — "achieved bytes ≈ 2x
    encoded" for those engines.  bench reports the
    per-path ratio as ``decode_overhead`` next to
    ``model_gb_per_path``."""
    from splatt_tpu.ops.mttkrp import STREAM_NATIVE_ENGINES
    from splatt_tpu.utils.env import ceil_to

    lay = X.layout_for(mode)
    if (getattr(lay, "encoding", "v1") in ("v1", "dense")
            or engine in STREAM_NATIVE_ENGINES or engine == "native"):
        return 0.0
    decoded = 2.0 * lay.nmodes * lay.nnz_pad * 4   # i32 write + read
    if engine in ("fused_t", "fused_tg"):
        b_pad = ceil_to(lay.block, 128)
        for k, d in enumerate(X.dims):
            if k != mode:
                d_pad = ceil_to(int(d), 128)
                ck = -(-b_pad // d_pad)
                decoded += 2.0 * lay.nblocks * ck * 8 * d_pad * 4
    return decoded


def mxu_peak_gflops() -> Optional[float]:
    """Peak MXU compute of device 0 (bf16 GFLOP/s), or None off-TPU;
    an unknown TPU kind raises."""
    from splatt_tpu.devices import device_spec

    spec = device_spec()
    return spec.mxu_gflops if spec is not None else None


def mttkrp_flops(alg: str, X: BlockedSparse, rank: int,
                 mode: int) -> float:
    """First-order flop count of one MTTKRP over a compiled
    :class:`BlockedSparse` — the compute half of the roofline model
    (docs/dense.md) beside the bytes-only :func:`mttkrp_bytes_encoded`.

    - dense tile layout: the batched matmul's MACs over the PADDED
      cell space (2 * cells * R — pad rows are real MXU work, which is
      exactly why the verdict thresholds padded density) plus the
      Khatri-Rao build (span * R multiplies);
    - sparse paths: one Hadamard chain + accumulate per nonzero per
      rank column (2 * nnz * R * (nmodes-1)), plus the one-hot
      expansion's dense MACs (2 * nblocks * S * block * R) for the
      one-hot algorithms — work amplification the bytes model cannot
      see.
    """
    lay = X.layout_for(mode)
    if getattr(lay, "encoding", "v1") == "dense":
        geo = lay.geometry
        return 2.0 * geo.cells * rank + geo.span * rank
    flops = 2.0 * lay.nnz * rank * (lay.nmodes - 1)
    if alg in ("blocked", "blocked_pallas"):
        flops += 2.0 * lay.nblocks * lay.seg_width * lay.block * rank
    return flops


def roofline_verdict(bytes_moved: float, flops: float,
                     spec=None) -> Optional[dict]:
    """Classify one path against the device roofline: arithmetic
    intensity (flops/byte), the device ridge point (peak flops / peak
    bandwidth), and which side of it the path sits on.  `spec` (a
    devices.DeviceSpec) defaults to device 0's; off-TPU there is no
    roofline and the verdict is None."""
    from splatt_tpu.devices import device_spec

    spec = spec if spec is not None else device_spec()
    if spec is None:
        return None
    intensity = flops / max(bytes_moved, 1.0)
    ridge = spec.mxu_gflops / spec.hbm_gbs
    return dict(intensity=round(intensity, 3), ridge=round(ridge, 3),
                bound=("compute" if intensity >= ridge else "memory"))


def roofline_report(tt: SparseTensor, results: Dict[str, List[float]],
                    rank: int, itemsize: int,
                    layouts=None) -> List[str]:
    """Per-alg/mode effective bandwidth lines: model GB/s and, on TPU,
    % of the HBM peak (≙ src/bench.c printing per-algorithm times —
    extended with the bytes model so a reader sees headroom, not just
    seconds)."""
    peak = hbm_peak_gbs()
    lines = []
    for alg, times in results.items():
        cells = []
        for m, t in enumerate(times):
            if np.isnan(t) or t <= 0:
                cells.append(f"mode{m}:    --  ")
                continue
            lay = layouts[m] if layouts is not None else None
            gbs = mttkrp_bytes(alg, tt, rank, m, itemsize, lay) / t / 1e9
            pct = f" ({100 * gbs / peak:3.0f}%)" if peak else ""
            cells.append(f"mode{m}: {gbs:6.1f}{pct}")
        label = f"  {alg:<16s}"
        lines.append(label + "  ".join(cells)
                     + ("  GB/s of HBM peak" if peak else "  GB/s (model)"))
    return lines
