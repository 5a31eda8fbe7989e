"""Chip smoke: splatt-tpu's main path, end to end, on a TPU.

    python chip_smoke.py               # one chip: NELL-2-scale CPD-ALS
    python chip_smoke.py --four-chips  # the distributed CPDs on 4 chips

The one-chip run is what ``python -m splatt_tpu.cli cpd`` does —
``SparseTensor → BlockedSparse.from_coo → cpd_als`` — on a tensor of
FROSTT NELL-2's shape (12092 x 9184 x 28818, 76,879,419 nonzeros, f32
values, made from ``--seed`` on the host), at rank 50, allmode layouts,
default Options and 5 ALS iterations, with the Pallas MTTKRP engines.
The tensor has a planted rank-50 structure (:func:`nell2_tensor`), so
the fit climbs well above zero and a wrong sweep shows in it.
It fails (nonzero exit, no result line) unless:

- JAX's first device is a TPU;
- every mode's MTTKRP, through the dispatch the sweep uses, matches a
  float64 numpy reference on a seeded sample of output rows at rtol
  2e-5;
- the engine that ran in every mode is the Pallas engine the plan
  named, with no runtime demotion and no capability probe verdict other
  than "ok" (engine fallback is off);
- every iteration's fit is finite, the fit does not fall after
  iteration 2, and the final fit reaches FIT_FLOOR.

``--four-chips`` runs only the medium-grain grid and the fine-grain
decomposition (all2all) on a 4-device mesh at the same tensor, seed
and iterations, plus the one-chip ``cpd_als`` they are compared with.
Their final fits must agree with it within 1e-4, and every device must
hold its own shards.  Then the async ring's two RDMA exchanges must
match numpy on a small input within a short deadline, and an
async-ring CPD must be refused loudly: at the 20M cut it finished no
iteration in 359 s (PR 21), so the library refuses it on TPU.

Times printed here are smoke readings, not benchmark numbers.  The last
line of stdout is ``{"ok": true, "device": {...}}`` and nothing else.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

import numpy as np

NELL2_DIMS = (12092, 9184, 28818)
NELL2_NNZ = 76_879_419
RANK = 50
ITERS = 5
MIN_NNZ = 20_000_000
SAMPLE_ROWS = 1024
RTOL = 2e-5
FIT_AGREE = 1e-4
#: the whole smoke, compilation included, ends within this
DEADLINE_S = 1150.0
#: the RDMA ring precheck (small input) ends within this
RING_CHECK_S = 120.0
#: f32 rounding of the fit itself (1 - residual/norm)
FIT_NOISE = 1e-5
#: 5 iterations on the planted tensor reach fit 0.64-0.72 (CPU runs at
#: 1M-10M nonzeros, PR 21); a broken sweep stays near 0
FIT_FLOOR = 0.3


class SmokeFailure(Exception):
    """A phase of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def nell2_tensor(nnz: int, seed: int):
    """A NELL-2-shaped COO tensor with a planted rank-RANK structure,
    made in bulk on the host from `seed`.

    Component r is a dense sub-cube: the outer product of three
    positive vectors (values in [0.5, 1.5)) over disjoint row sets, one
    per mode; the sub-cubes are sized so that RANK of them hold `nnz`
    nonzeros, and the last is cut to make the count exact.  Each row
    set is spread evenly over its mode, so blocks span a few rows as on
    NELL-2 (about 13k nonzeros per row here, 2.7k-8.4k there), and
    rows outside every set stay empty.  The tensor is exactly rank
    RANK but for the cut, so ALS climbs towards fit 1.
    """
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(seed)
    per = nnz / RANK
    n0 = n1 = math.ceil(per ** (1 / 3))
    sizes = (n0, n1, math.ceil(per / (n0 * n1)))
    cell = np.arange(sizes[0] * sizes[1] * sizes[2])
    local = (cell // (sizes[1] * sizes[2]), (cell // sizes[2]) % sizes[1],
             cell % sizes[2])
    inds = np.empty((len(NELL2_DIMS), RANK * cell.size), dtype=np.int64)
    vals = np.ones(RANK * cell.size, dtype=np.float32)
    for m, (d, n) in enumerate(zip(NELL2_DIMS, sizes)):
        rows = (rng.permutation(RANK * n) * d // (RANK * n)).reshape(RANK, n)
        vec = rng.uniform(0.5, 1.5, (RANK, n)).astype(np.float32)
        inds[m] = rows[:, local[m]].ravel()
        vals *= vec[:, local[m]].ravel()
    return SparseTensor(inds[:, :nnz], vals[:nnz], NELL2_DIMS)


def reference_rows(tt, factors, mode: int, rows: np.ndarray) -> np.ndarray:
    """float64 numpy MTTKRP of `mode`, restricted to the sorted output
    `rows`: one bincount per rank column, so memory stays at a few
    nonzero-length vectors."""
    sel = np.isin(tt.inds[mode], rows)
    inds = tt.inds[:, sel]
    vals = tt.vals[sel].astype(np.float64)
    out_row = np.searchsorted(rows, inds[mode])
    others = [k for k in range(tt.nmodes) if k != mode]
    out = np.empty((len(rows), factors[0].shape[1]))
    for r in range(out.shape[1]):
        w = vals.copy()
        for k in others:
            w *= factors[k][inds[k], r]
        out[:, r] = np.bincount(out_row, weights=w, minlength=len(rows))
    return out


def check_mttkrp(tt, bs, factors, seed: int) -> dict:
    """Every mode's MTTKRP through the sweep's dispatch vs the host
    reference; returns the plan per mode."""
    import jax

    from splatt_tpu.ops.mttkrp import mttkrp, plan_mttkrp

    host = [np.asarray(U, dtype=np.float64) for U in factors]
    rng = np.random.default_rng(seed + 1)
    plans = {}
    for m in range(tt.nmodes):
        plan = plan_mttkrp(bs, factors, m)
        plans[m] = plan
        check(plan.impl in ("pallas", "pallas_interpret")
              and plan.engine in PALLAS_ENGINES,
              f"mode {m} plans {plan}, not a Pallas engine")
        t0 = time.perf_counter()
        got = jax.block_until_ready(mttkrp(bs, factors, m))
        secs = time.perf_counter() - t0
        got = np.asarray(got, dtype=np.float64)
        held = np.bincount(tt.inds[m], minlength=tt.dims[m]) > 0
        check(not got[~held].any(),
              f"mode {m}: MTTKRP rows of empty slices are not zero")
        nonempty = np.flatnonzero(held)
        rows = np.sort(rng.choice(nonempty, replace=False,
                                  size=min(SAMPLE_ROWS, nonempty.size)))
        want = reference_rows(tt, host, m, rows)
        got = got[rows]
        err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                           1e-30)))
        say(f"mode {m}: plan {plan.path}/{plan.engine}; MTTKRP vs numpy "
            f"on {len(rows)} held rows: max rel err {err:.3e} (rtol "
            f"{RTOL}), {int((~held).sum())} empty rows zero; "
            f"first call {secs:.3f}s incl. compile")
        check(np.allclose(got, want, rtol=RTOL, atol=0.0),
              f"mode {m} MTTKRP differs from the host reference "
              f"(max rel err {err:.3e} > {RTOL})")
    return plans


PALLAS_ENGINES = ("unfused_pallas", "fused_dense")


def check_engines(plans: dict) -> None:
    """The engine each mode dispatched is the planned Pallas engine: no
    runtime demotion, no capability probe verdict but "ok"."""
    from splatt_tpu import resilience, trace
    from splatt_tpu.ops.pallas_kernels import PROBE_STATES

    demoted = resilience.demotions()
    check(not demoted, f"engines demoted at runtime: {demoted}")
    bad = {k: v for k, v in PROBE_STATES.items() if v != "ok"}
    check(not bad, f"capability probes not ok: {bad}")
    ran = {}
    for s in trace.spans("mttkrp.dispatch"):
        ran.setdefault(int(s["args"]["mode"]), set()).add(
            s["args"]["engine"])
    for m, plan in plans.items():
        check(ran.get(m) == {plan.engine},
              f"mode {m} ran {sorted(ran.get(m, []))}, plan named "
              f"{plan.engine}")


def iteration_fits() -> list:
    from splatt_tpu import trace

    its = sorted(trace.spans("cpd.iter"), key=lambda s: s["args"]["it"])
    return [(s["args"]["it"], s["args"].get("fit"), s["dur"])
            for s in its]


def check_fits(fits: list, iters: int) -> None:
    check(len(fits) == iters, f"ran {len(fits)} iterations, not {iters}")
    vals = [f for _, f, _ in fits]
    check(all(f is not None and math.isfinite(f) for f in vals),
          f"non-finite fit: {vals}")
    for i in range(2, len(vals)):
        check(vals[i] >= vals[i - 1] - FIT_NOISE,
              f"fit fell after iteration 2: {vals}")
    check(vals[-1] >= FIT_FLOOR,
          f"final fit {vals[-1]:.6f} is below {FIT_FLOOR} on the planted "
          f"rank-{RANK} tensor")


def one_chip(args) -> None:
    import jax

    from splatt_tpu import trace
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import BlockAlloc, Options
    from splatt_tpu.cpd import cpd_als, init_factors

    t0 = time.perf_counter()
    tt = nell2_tensor(args.nnz, args.seed)
    say(f"tensor {tt.dims} nnz {tt.nnz:,} made in "
        f"{time.perf_counter() - t0:.1f}s (seed {args.seed})")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, max_iterations=ITERS,
                   random_seed=args.seed, engine_fallback=False,
                   trace=True, tolerance=0.0)
    t0 = time.perf_counter()
    bs = BlockedSparse.from_coo(tt, opts)
    jax.block_until_ready([lay.vals for lay in bs.layouts])
    say(f"layouts built and placed in {time.perf_counter() - t0:.1f}s "
        f"(set-up)")
    factors = init_factors(tt.dims, RANK, opts.seed())
    trace.reset()
    plans = check_mttkrp(tt, bs, factors, args.seed)
    trace.reset()
    t0 = time.perf_counter()
    out = cpd_als(bs, RANK, opts)
    jax.block_until_ready(out.factors)
    wall = time.perf_counter() - t0
    fits = iteration_fits()
    for it, fit, dur in fits:
        say(f"iteration {it}: fit {fit:.6f} ({dur:.3f}s)")
    check_fits(fits, ITERS)
    check_engines(plans)
    # iterations 1 and 2 compile the sweep's two specializations
    # (first=True/False); the steady reading starts at iteration 3
    steady = sorted(d for _, _, d in fits[2:])
    sec_it = steady[len(steady) // 2]
    compile_s = fits[0][2] + fits[1][2] - 2 * sec_it
    say("engine per mode: " + " ".join(
        f"mode{m}={p.path}/{p.engine}" for m, p in sorted(plans.items())))
    say(f"smoke reading, not a benchmark: iterations 1-2 "
        f"{fits[0][2]:.3f}s + {fits[1][2]:.3f}s (include the sweep "
        f"compile, ~{compile_s:.3f}s over steady), steady {sec_it:.4f} "
        f"s/iter (median of iterations 3-{ITERS}), cpd_als wall "
        f"{wall:.2f}s")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


class PlacementLog:
    """Records, for every array the library places across several
    devices with ``jax.device_put``, how many bytes each device holds —
    metadata only, no reference is kept."""

    def __init__(self):
        import jax

        self.records = []
        self._put = jax.device_put

    def __enter__(self):
        import jax

        def observe(x, *a, **k):
            out = self._put(x, *a, **k)
            for leaf in jax.tree_util.tree_leaves(out):
                shards = getattr(leaf, "addressable_shards", ())
                if len({s.device.id for s in shards}) > 1:
                    per = {}
                    for s in shards:
                        per[s.device.id] = (per.get(s.device.id, 0)
                                            + s.data.nbytes)
                    self.records.append((tuple(leaf.shape), per))
            return out

        jax.device_put = observe
        return self

    def __exit__(self, *exc):
        import jax

        jax.device_put = self._put

    def check_spread(self, label: str, ndev: int) -> dict:
        """Every device holds a real share of the largest sharded array
        the run placed; returns its per-device bytes."""
        check(self.records, f"{label}: nothing was placed across devices")
        shape, per = max(self.records, key=lambda r: sum(r[1].values()))
        total = sum(per.values())
        check(len(per) == ndev and min(per.values()) >= total / (4 * ndev),
              f"{label}: the largest sharded array {shape} sits on "
              f"devices {per}, not spread over {ndev}")
        return per


def check_ring_exchange(ndev: int) -> None:
    """The async ring's two RDMA exchanges alone, on a small input, vs
    numpy: the gather must pick exactly the requested rows and the
    reduce must sum every chip's partials into the owner's block."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from splatt_tpu.parallel.mesh import make_mesh
    from splatt_tpu.parallel.ring_kernels import (
        async_blockwise_reduce_rows, async_ring_gather_rows)

    block, n = 256, 50_000
    mesh = make_mesh(ndev, axis_names=("x",))
    rng = np.random.default_rng(1)
    U = rng.standard_normal((ndev * block, RANK)).astype(np.float32)
    prod = rng.standard_normal((ndev * n, RANK)).astype(np.float32)
    idx = rng.integers(0, ndev * block, size=ndev * n).astype(np.int32)

    def run(body, x):
        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(P("x", None), P("x")),
                               out_specs=P("x", None), check_vma=False))
        return np.asarray(jax.block_until_ready(fn(x, idx)))

    t0 = time.perf_counter()
    got = run(lambda u, i: async_ring_gather_rows(u, i, "x", ndev), U)
    gerr = float(np.max(np.abs(got - U[idx]) / np.abs(U[idx])))
    check(gerr <= 1e-6, f"async ring gather differs from numpy (max rel "
          f"err {gerr:.3e})")
    got = run(lambda p, i: async_blockwise_reduce_rows(p, i, "x", ndev,
                                                       block), prod)
    want = np.zeros((ndev * block, RANK))
    np.add.at(want, idx, prod.astype(np.float64))
    err = float(np.max(np.abs(got - want)))
    check(np.allclose(got, want, rtol=1e-4, atol=1e-3),
          f"async ring reduce differs from numpy (max abs err {err:.3e})")
    say(f"async ring RDMA exchanges match numpy (gather max rel err "
        f"{gerr:.3e}, reduce max abs err {err:.3e}) in "
        f"{time.perf_counter() - t0:.1f}s incl. compile")


def four_chips(args, dog) -> None:
    """The distributed CPDs on a 4-device mesh vs one-chip cpd_als."""
    import jax

    from splatt_tpu import resilience
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import (BlockAlloc, CommPattern, Decomposition,
                                   Options)
    from splatt_tpu.cpd import cpd_als, init_factors
    from splatt_tpu.parallel import distributed_cpd_als
    from splatt_tpu.parallel.ring_kernels import async_ring_supported

    ndev = 4
    tt = nell2_tensor(args.nnz, args.seed)
    say(f"tensor {tt.dims} nnz {tt.nnz:,} (seed {args.seed})")
    base = dict(max_iterations=ITERS, random_seed=args.seed,
                engine_fallback=False, tolerance=0.0)
    init = [np.asarray(U) for U in init_factors(tt.dims, RANK, args.seed)]

    opts = Options(block_alloc=BlockAlloc.ALLMODE, **base)
    t0 = time.perf_counter()
    ref = float(cpd_als(BlockedSparse.from_coo(tt, opts), RANK, opts,
                        init=init).fit)
    say(f"one-chip cpd_als: final fit {ref:.8f} "
        f"({time.perf_counter() - t0:.1f}s incl. set-up)")
    check(ref >= FIT_FLOOR, f"one-chip final fit {ref:.8f} is below "
          f"{FIT_FLOOR} on the planted rank-{RANK} tensor")

    runs = [("medium", Decomposition.MEDIUM, None),
            ("fine", Decomposition.FINE, CommPattern.ALL2ALL)]
    for label, decomp, comm in runs:
        opts = Options(decomposition=decomp, comm_pattern=comm, **base)
        resilience.run_report().clear()
        t0 = time.perf_counter()
        with PlacementLog() as log:
            try:
                fit = float(distributed_cpd_als(tt, RANK, opts=opts,
                                                init=init).fit)
            except Exception as e:
                raise SmokeFailure(f"{label}: {type(e).__name__}: "
                                   f"{str(e)[:300]}") from e
        secs = time.perf_counter() - t0
        per = log.check_spread(label, ndev)
        fallbacks = (resilience.run_report().events("comm_fallback")
                     + resilience.run_report().events("engine_demotion")
                     + resilience.demotions())
        check(not fallbacks, f"{label}: fell back: {fallbacks}")
        check(math.isfinite(fit) and abs(fit - ref) <= FIT_AGREE,
              f"{label}: final fit {fit:.8f} vs one-chip {ref:.8f} "
              f"(limit {FIT_AGREE})")
        say(f"{label}: final fit {fit:.8f} (|diff| {abs(fit - ref):.2e}); "
            f"largest sharded array bytes per device {per}; "
            f"{secs:.1f}s incl. set-up (smoke reading)")
    for d in jax.devices()[:ndev]:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", 0)
        say(f"device {d.id}: peak_bytes_in_use {peak}")
        check(peak > 0, f"device {d.id} never held anything")

    check(async_ring_supported(), "the async ring kernels are not "
          "supported here: the exchanges would use the ppermute dataflow")
    dog.phase("async ring RDMA exchanges", RING_CHECK_S)
    check_ring_exchange(ndev)
    dog.phase("async ring refusal")
    opts = Options(decomposition=Decomposition.FINE,
                   comm_pattern=CommPattern.ASYNC_RING, **base)
    try:
        distributed_cpd_als(tt, RANK, opts=opts, init=init)
    except NotImplementedError as e:
        say(f"async ring CPD refused loudly: {str(e)[:120]}")
    else:
        raise SmokeFailure("the async ring CPD was not refused on TPU")


class Watchdog:
    """Fails the smoke, and ends the process, when the whole run or the
    current phase outlasts its deadline: a device call that never
    returns (a hung collective) must not hold the chip until an outer
    limit kills it."""

    def __init__(self, seconds: float):
        self.end = time.monotonic() + seconds
        self.timer = None
        self.phase("smoke")

    def phase(self, label: str, seconds: float = math.inf) -> None:
        import threading

        if self.timer is not None:
            self.timer.cancel()
        left = self.end - time.monotonic()
        limit = min(seconds, left)

        def fire():
            import os

            print(f"chip_smoke: FAIL: {label} outlasted its deadline "
                  f"({limit:.0f}s)", file=sys.stderr, flush=True)
            os._exit(3)

        self.timer = threading.Timer(max(limit, 0.0), fire)
        self.timer.daemon = True
        self.timer.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the distributed CPDs on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nnz", type=int, default=NELL2_NNZ,
                    help=f"nonzeros (cut only as the time limit forces; "
                         f"never below {MIN_NNZ:,})")
    ap.add_argument("--deadline", type=float, default=DEADLINE_S,
                    help="seconds after which the smoke fails and exits, "
                         "whatever it is waiting on")
    args = ap.parse_args(argv)
    dog = Watchdog(args.deadline)
    try:
        from splatt_tpu.utils.env import apply_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: splatt_tpu is not importable ({e}); run "
              f"from the root of a splatt-tpu checkout", file=sys.stderr)
        return 2
    cache = apply_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU: JAX's first device is "
              f"{dev.platform} ({dev.device_kind}); this smoke runs only "
              f"on the chip", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: FAIL: {len(devs)} device(s), need {need}",
              file=sys.stderr)
        return 1
    if args.nnz < MIN_NNZ:
        print(f"chip_smoke: FAIL: --nnz {args.nnz:,} is below the "
              f"{MIN_NNZ:,} floor", file=sys.stderr)
        return 1
    if args.nnz != NELL2_NNZ:
        say(f"CUT: nnz {args.nnz:,} instead of NELL-2's {NELL2_NNZ:,}")
    say(f"device_kind {dev.device_kind!r}, {len(devs)} device(s); "
        f"compile cache {cache}")
    try:
        if args.four_chips:
            four_chips(args, dog)
        else:
            one_chip(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
