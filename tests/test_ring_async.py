"""Async ICI ring sweep (docs/ring.md).

Contract under test:

- **bit parity**: the ASYNC_RING strategy's CPU/interpret fallback
  preserves today's ppermute semantics — async-ring ≡ ppermute-ring ≡
  all2all factors BIT-identically on the seeded synthetic CPD (the
  gather adds exactly one non-zero term per nonzero, and the reduce
  keeps psum ordering off-TPU);
- **fallback ladder**: a ``comm.ring_exchange`` failure degrades
  classified down the comm chain — async_ring -> ring -> all2all —
  with ``comm_fallback`` run-report events and the failed strategy
  demoted under its own ``:comm`` shape key; the terminal all2all is
  never demoted (an async-ring OOM must not indict it), and with
  engine fallback off the failure is loud;
- **overlap metric**: measure_ring_overlap reports the achieved
  exchange-hidden fraction next to the wire model's per-device bytes,
  and ring-variant runs emit it as a ``ring_overlap`` event (what
  `splatt cpd --json` and MULTICHIP artifacts carry);
- **wire model**: comm_volume_model stops assuming all2all — the ring
  legs carry per-hop bytes and the overlap-eligible fraction.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from splatt_tpu import resilience
from splatt_tpu.config import (CommPattern, Options, Verbosity,
                               resolve_comm_pattern)
from splatt_tpu.cpd import cpd_als, init_factors
from splatt_tpu.parallel.common import comm_volume_model, comm_volume_report
from splatt_tpu.parallel.mesh import make_mesh
from splatt_tpu.parallel.ring_kernels import (async_blockwise_reduce_rows,
                                              async_ring_gather_rows,
                                              async_ring_supported)
from splatt_tpu.parallel.sharded import (comm_chain, measure_ring_overlap,
                                         shard_factors, shard_nnz,
                                         sharded_cpd_als)
from splatt_tpu.utils import faults
from splatt_tpu.utils.env import ceil_to
from tests import gen


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    resilience.reset_demotions()
    resilience.run_report().clear()
    yield
    faults.reset()
    resilience.reset_demotions()
    resilience.run_report().clear()


def _opts(**kw):
    kw.setdefault("random_seed", 42)
    kw.setdefault("verbosity", Verbosity.NONE)
    kw.setdefault("val_dtype", np.float64)
    return Options(**kw)


def _arm(text):
    for site, spec in faults.parse_schedule(text).items():
        faults.arm(site, spec)


def _run(comm, mesh, tt, init, **kw):
    return sharded_cpd_als(tt, rank=5, mesh=mesh, init=init,
                           opts=_opts(max_iterations=5, comm_pattern=comm,
                                      **kw.pop("opts_kw", {})), **kw)


# -- parity -----------------------------------------------------------------


def test_async_ring_unit_parity():
    """The async gather/reduce primitives ≡ their ppermute versions on
    the fallback path (and trivially on a 1-wide axis)."""
    ndev = 8
    mesh = make_mesh(n_devices=ndev)
    rng = np.random.default_rng(0)
    dim_pad, R, nnz = 40, 6, 64
    U = jnp.asarray(rng.random((dim_pad, R)))
    idx = jnp.asarray(rng.integers(0, dim_pad, size=nnz).astype(np.int32))
    U_s = jax.device_put(U, NamedSharding(mesh, P("nnz", None)))

    @partial(shard_map, mesh=mesh, in_specs=(P("nnz", None), P(None)),
             out_specs=P(None), check_vma=False)
    def run(U_l, idx_rep):
        return async_ring_gather_rows(U_l, idx_rep, "nnz", ndev)

    got = run(U_s, idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(U)[np.asarray(idx)])

    prod = rng.random((ndev * 32, R))
    ridx = rng.integers(0, dim_pad, size=ndev * 32).astype(np.int32)
    prod_s = jax.device_put(jnp.asarray(prod),
                            NamedSharding(mesh, P("nnz", None)))
    ridx_s = jax.device_put(jnp.asarray(ridx),
                            NamedSharding(mesh, P("nnz")))

    @partial(shard_map, mesh=mesh, in_specs=(P("nnz", None), P("nnz")),
             out_specs=P("nnz", None), check_vma=False)
    def red(prod_l, idx_l):
        return async_blockwise_reduce_rows(prod_l, idx_l, "nnz", ndev,
                                           dim_pad // ndev)

    want = np.zeros((dim_pad, R))
    np.add.at(want, ridx, prod)
    np.testing.assert_allclose(np.asarray(red(prod_s, ridx_s)), want,
                               atol=1e-12)


def test_async_ring_cpd_bit_parity_three_ways():
    """Acceptance: async-ring ≡ ppermute-ring ≡ all2all factors
    BIT-identically on the seeded synthetic CPD (CPU/interpret)."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    a = _run(CommPattern.ALL2ALL, mesh, tt, init, local_engine="stream")
    b = _run(CommPattern.POINT2POINT, mesh, tt, init)
    c = _run(CommPattern.ASYNC_RING, mesh, tt, init)
    assert float(a.fit) == float(b.fit) == float(c.fit)
    for fa, fb, fc in zip(a.factors, b.factors, c.factors):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
        np.testing.assert_array_equal(np.asarray(fb), np.asarray(fc))


def test_async_ring_single_device_degenerate():
    """ndev=1: the async strategy runs (fallback path, trivial ring)
    and matches the single-device driver."""
    tt = gen.fixture_tensor("med4")
    init = init_factors(tt.dims, 4, 42, dtype=jnp.float64)
    single = cpd_als(tt, rank=4, opts=_opts(max_iterations=5), init=init)
    ring = sharded_cpd_als(tt, rank=4, mesh=make_mesh(n_devices=1),
                           init=init,
                           opts=_opts(max_iterations=5,
                                      comm_pattern=CommPattern.ASYNC_RING))
    assert float(ring.fit) == pytest.approx(float(single.fit), abs=1e-8)


def test_async_ring_supported_is_false_on_cpu():
    """Tier-1 runs the ppermute-fallback dataflow — the RDMA kernels
    require a real TPU backend."""
    assert async_ring_supported() is False


# -- comm chain / env resolution --------------------------------------------


def test_comm_chain_shapes():
    assert comm_chain(CommPattern.ALL2ALL) == ("all2all",)
    assert comm_chain(CommPattern.POINT2POINT) == ("ring", "all2all")
    assert comm_chain(CommPattern.ASYNC_RING) == ("async_ring", "ring",
                                                  "all2all")


def test_resolve_comm_pattern_env(monkeypatch):
    assert resolve_comm_pattern(_opts()) is CommPattern.ALL2ALL
    monkeypatch.setenv("SPLATT_COMM", "async_ring")
    assert resolve_comm_pattern(_opts()) is CommPattern.ASYNC_RING
    # explicit option beats the env default
    assert resolve_comm_pattern(
        _opts(comm_pattern=CommPattern.POINT2POINT)) \
        is CommPattern.POINT2POINT
    monkeypatch.setenv("SPLATT_COMM", "bogus")
    with pytest.raises(ValueError):
        resolve_comm_pattern(_opts())


# -- fallback ladder --------------------------------------------------------


def test_comm_fallback_lands_on_sync_ring():
    """One injected async-ring failure: the sweep degrades classified
    to the ppermute ring (comm_fallback event, comm.async_ring
    demoted) and still converges bit-identically to a clean ring run."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    clean = _run(CommPattern.POINT2POINT, mesh, tt, init)
    resilience.run_report().clear()
    resilience.reset_demotions()
    with faults.inject("comm.ring_exchange", "runtime", times=1):
        out = _run(CommPattern.ASYNC_RING, mesh, tt, init)
    evs = resilience.run_report().events("comm_fallback")
    assert [(e["strategy"], e["fallback_to"]) for e in evs] \
        == [("async_ring", "ring")]
    assert [d.engine for d in resilience.demotions()] == ["comm.async_ring"]
    assert float(out.fit) == float(clean.fit)
    for fa, fb in zip(out.factors, clean.factors):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_comm_fallback_oom_scoped_never_demotes_all2all():
    """Acceptance: an async-ring OOM demotes the ring engines PER
    SHAPE under the ':comm' key and lands on all2all — which is never
    demoted; a different shape keeps the async ring live."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    _arm("comm.ring_exchange:oom:2")
    out = _run(CommPattern.ASYNC_RING, mesh, tt, init)
    assert np.isfinite(float(out.fit))
    evs = resilience.run_report().events("comm_fallback")
    assert [(e["strategy"], e["fallback_to"]) for e in evs] \
        == [("async_ring", "ring"), ("ring", "all2all")]
    assert all(e["failure_class"] == "resource" for e in evs)
    dem = {d.engine: d for d in resilience.demotions()}
    assert set(dem) == {"comm.async_ring", "comm.ring"}
    for d in dem.values():
        assert d.shape_key is not None and d.shape_key.endswith(":comm")
    assert not resilience.is_demoted("comm.all2all", None)
    # per-shape scoping: another shape's key is untouched
    assert not resilience.is_demoted("comm.async_ring",
                                     "d8x8x8:w8:r5:float64:comm")
    # MTTKRP engine keys are a different namespace entirely
    assert not resilience.is_demoted("unfused_pallas")


def test_demoted_comm_engine_skipped_next_run():
    """A second run at the demoted shape goes straight to the sync
    ring — no repeated probe failure, no new fallback event."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    _arm("comm.ring_exchange:runtime:1")
    _run(CommPattern.ASYNC_RING, mesh, tt, init)
    assert len(resilience.run_report().events("comm_fallback")) == 1
    faults.reset()
    out = _run(CommPattern.ASYNC_RING, mesh, tt, init)
    # still exactly ONE event: the demoted async engine was pruned,
    # not re-probed and re-failed
    assert len(resilience.run_report().events("comm_fallback")) == 1
    assert np.isfinite(float(out.fit))


@pytest.fixture
def x32():
    """The chip path runs in 32-bit mode (conftest turns x64 on for the
    f64 differential tests; the kernels' scalar index math is i32)."""
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", x64)


def _tpu_interpret():
    """TPU interpret mode with the DMA-on-wait model and the race
    detector: a DMA runs only when a semaphore it signals is waited on,
    so a wait that another slot's DMA could satisfy reads stale data."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.reset_tpu_interpret_mode_state()
    return pltpu.InterpretParams(dma_execution_mode="on_wait",
                                 detect_races=True)


def _races_found() -> bool:
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    races = interpret_pallas_call.races
    return bool(races is not None and races.races_found)


@pytest.mark.parametrize("ndev", [2, 4])
@pytest.mark.parametrize("kernel", ["gather", "reduce"])
def test_rdma_ring_kernel_interpret_matches_numpy(x32, kernel, ndev):
    """The Pallas RDMA ring kernels themselves — remote copies, credit
    and barrier semaphores — under TPU interpret mode on CPU devices:
    the gather picks exactly the requested rows, the reduce sums every
    device's partials into the owner's block, and no race is found."""
    import splatt_tpu.parallel.ring_kernels as rk

    block, R, n = 16, 8, 1500
    mesh = make_mesh(n_devices=ndev, axis_names=("x",))
    rng = np.random.default_rng(ndev)
    idx = rng.integers(0, ndev * block, size=ndev * n).astype(np.int32)
    params = _tpu_interpret()
    if kernel == "gather":
        U = rng.standard_normal((ndev * block, R)).astype(np.float32)

        def body(u, i):
            return rk._gather_pallas(u, i, "x", ndev, interpret=params)

        args, want = (U, idx), U[idx]
    else:
        prod = rng.standard_normal((ndev * n, R)).astype(np.float32)

        def body(p, i):
            return rk._reduce_pallas(p, i, "x", ndev, block,
                                     interpret=params)

        want = np.zeros((ndev * block, R))
        np.add.at(want, idx, prod.astype(np.float64))
        args = (prod, idx)
    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P("x", None), P("x")),
                           out_specs=P("x", None), check_vma=False))
    got = np.asarray(fn(*args))
    assert not _races_found()
    if kernel == "gather":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_async_ring_sweep_on_rdma_kernels_matches_all2all(x32, monkeypatch):
    """A whole ASYNC_RING CPD with the sweep on the RDMA kernels (TPU
    interpret mode standing in for the chip) reaches the all2all fit
    and factors, with no comm fallback and no race."""
    import splatt_tpu.parallel.ring_kernels as rk
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(0)
    dims, nnz = (40, 36, 52), 1500
    tt = SparseTensor(np.stack([rng.integers(0, d, nnz) for d in dims]),
                      rng.random(nnz), dims)
    init = init_factors(dims, 8, 0, dtype=jnp.float32)
    mesh = make_mesh(n_devices=4)
    params = _tpu_interpret()
    monkeypatch.setattr(rk, "async_ring_supported", lambda: True)
    monkeypatch.setattr(rk, "_gather_pallas",
                        partial(rk._gather_pallas, interpret=params))
    monkeypatch.setattr(rk, "_reduce_pallas",
                        partial(rk._reduce_pallas, interpret=params))
    out = sharded_cpd_als(tt, rank=8, mesh=mesh, init=init,
                          opts=_opts(max_iterations=2,
                                     comm_pattern=CommPattern.ASYNC_RING,
                                     val_dtype=np.float32,
                                     engine_fallback=False))
    ref = sharded_cpd_als(tt, rank=8, mesh=mesh, init=init,
                          opts=_opts(max_iterations=2,
                                     val_dtype=np.float32,
                                     engine_fallback=False))
    assert not _races_found()
    assert not resilience.run_report().events("comm_fallback")
    assert float(out.fit) == pytest.approx(float(ref.fit), abs=1e-5)
    for fa, fb in zip(out.factors, ref.factors):
        np.testing.assert_allclose(np.asarray(fa), np.asarray(fb),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fallback", [True, False])
def test_async_ring_refused_on_tpu(monkeypatch, fallback):
    """On a TPU an ASYNC_RING CPD is refused before any device work,
    whatever the fallback setting — never a hang, never a quiet
    ppermute stand-in (its at-scale stall is open, PERF.md)."""
    import splatt_tpu.parallel.sharded as sh

    tt = gen.fixture_tensor("med")
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    monkeypatch.setattr(sh.jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="refused on TPU"):
        _run(CommPattern.ASYNC_RING, make_mesh(n_devices=8), tt, init,
             opts_kw=dict(engine_fallback=fallback))
    assert not resilience.run_report().events("comm_fallback")


def test_comm_fallback_disabled_fails_loudly():
    """engine_fallback off = the differential-test contract: the
    injected comm failure escapes instead of being rescued."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    _arm("comm.ring_exchange:runtime:1")
    with pytest.raises(Exception, match="injected"):
        _run(CommPattern.ASYNC_RING, mesh, tt, init,
             opts_kw=dict(engine_fallback=False))
    assert not resilience.run_report().events("comm_fallback")


def test_chaos_comm_drill_degrades_classified():
    """The `splatt chaos` comm drill: an armed ring-exchange fault
    under the ASYNC_RING strategy converges-or-degrades with
    comm_fallback evidence — never an unhandled exception."""
    from splatt_tpu import chaos

    res = chaos.run_chaos(schedule="comm.ring_exchange:oom:2", smoke=True)
    assert res.ok, res.violations
    assert res.fired.get("comm.ring_exchange") == 2
    kinds = {e["kind"] for e in res.events}
    assert "comm_fallback" in kinds


# -- overlap metric + wire model --------------------------------------------


def _sharded_operands(tt, mesh, rank=5):
    ndev = mesh.shape["nnz"]
    dims_pad = tuple(ceil_to(d, ndev) for d in tt.dims)
    inds, vals = shard_nnz(tt, mesh, val_dtype=np.float64)
    init = init_factors(tt.dims, rank, 42, dtype=jnp.float64)
    facs = tuple(shard_factors([jnp.asarray(f) for f in init], tt.dims,
                               mesh))
    from splatt_tpu.ops.linalg import gram

    grams = tuple(jax.device_put(
        gram(U), NamedSharding(mesh, P(None, None))) for U in facs)
    return dims_pad, inds, vals, facs, grams


def test_measure_ring_overlap_fields():
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    dims_pad, inds, vals, facs, grams = _sharded_operands(tt, mesh)
    ov = measure_ring_overlap(mesh, tt.nmodes, 0.0, dims_pad, "nnz",
                              "async_ring", inds, vals, facs, grams,
                              jnp.float64, reps=1)
    assert ov["variant"] == "async_ring"
    assert ov["engine"] == "ppermute_fallback"  # CPU: honest labelling
    assert 0.0 <= ov["overlap_frac"] <= 1.0
    assert ov["exchange_s"] > 0 and ov["step_s"] > 0
    assert ov["model_mb_per_device"] > 0
    assert ov["exposed_comm_s"] >= 0 and ov["hidden_comm_s"] >= 0
    assert 0.0 <= ov["overlap_eligible_frac"] < 1.0


def test_ring_overlap_event_emitted():
    """A ring-variant driver run with measurement on emits the
    ring_overlap event `splatt cpd --json` serializes."""
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    _run(CommPattern.ASYNC_RING, mesh, tt, init, measure_overlap=True)
    evs = resilience.run_report().events("ring_overlap")
    assert len(evs) == 1
    assert evs[0]["variant"] == "async_ring"
    assert "overlap_frac" in evs[0] and "model_mb_per_device" in evs[0]
    # and off by default at NONE verbosity
    resilience.run_report().clear()
    _run(CommPattern.ASYNC_RING, mesh, tt, init)
    assert not resilience.run_report().events("ring_overlap")


def test_comm_volume_model_ring_legs():
    """The wire model follows the selected strategy (ISSUE 8
    satellite): ring legs carry per-hop bytes and the async variant an
    overlap-eligible fraction; all2all keeps the collective model."""
    dims_pad = (64, 64, 64)
    a = comm_volume_model(dims_pad, 8, 8, ndev=8, variant="all2all")
    r = comm_volume_model(dims_pad, 8, 8, ndev=8, variant="ring")
    x = comm_volume_model(dims_pad, 8, 8, ndev=8, variant="async_ring")
    assert a["variant"] == "all2all" and a["overlap_eligible_frac"] == 0.0
    assert r["hops"] == 8 and r["per_hop_mb"] > 0
    assert x["hops"] == 7 and 0 < x["overlap_eligible_frac"] < 1
    # the async ring moves fewer gather bytes than the sync ring's
    # wasted final hop, and its reduce is point-to-point (half the
    # psum's 2x)
    assert x["gather_mb"] < r["gather_mb"]
    assert x["reduce_mb"] < r["reduce_mb"]
    # report lines name the strategy instead of assuming all2all
    line = comm_volume_report(dims_pad, 8, 8, ndev=8,
                              variant="async_ring")[0]
    assert "async ring" in line and "overlap-eligible" in line
    assert "all_gather" in comm_volume_report(dims_pad, 8, 8, ndev=8)[0]


def test_blocked_engine_rejected_for_async_ring():
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    with pytest.raises(ValueError, match="ring"):
        sharded_cpd_als(tt, rank=5, mesh=mesh,
                        opts=_opts(max_iterations=2,
                                   comm_pattern=CommPattern.ASYNC_RING),
                        local_engine="blocked")
