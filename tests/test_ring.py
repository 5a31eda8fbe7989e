"""Ring (point-to-point) communication variant tests.

≙ the reference testing its POINT2POINT row-exchange variant against
ALL2ALL semantics — both must give identical math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from functools import partial

from splatt_tpu.config import CommPattern, Options, Verbosity
from splatt_tpu.cpd import cpd_als, init_factors
from splatt_tpu.parallel.mesh import make_mesh
from splatt_tpu.parallel.ring import blockwise_reduce_rows, ring_gather_rows
from splatt_tpu.parallel.sharded import sharded_cpd_als
from tests import gen


def _opts(**kw):
    kw.setdefault("random_seed", 42)
    kw.setdefault("verbosity", Verbosity.NONE)
    kw.setdefault("val_dtype", np.float64)
    return Options(**kw)


def test_ring_gather_rows_unit():
    """ring gather == plain gather of the full matrix."""
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
        return ring_gather_rows(U_l, idx_rep, "nnz", ndev)

    got = run(U_s, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(U)[np.asarray(idx)],
                               atol=1e-12)


def test_blockwise_reduce_rows_unit():
    """blockwise ring reduce == segment_sum + manual row split."""
    ndev = 4
    mesh = make_mesh(n_devices=ndev)
    rng = np.random.default_rng(1)
    dim_pad, R = 16, 3
    block = dim_pad // ndev
    nnz_per_dev = 32
    prod = rng.random((ndev * nnz_per_dev, R))
    idx = rng.integers(0, dim_pad, size=ndev * nnz_per_dev).astype(np.int32)
    prod_s = jax.device_put(jnp.asarray(prod),
                            NamedSharding(mesh, P("nnz", None)))
    idx_s = jax.device_put(jnp.asarray(idx), NamedSharding(mesh, P("nnz")))

    @partial(shard_map, mesh=mesh, in_specs=(P("nnz", None), P("nnz")),
             out_specs=P("nnz", None), check_vma=False)
    def run(prod_l, idx_l):
        return blockwise_reduce_rows(prod_l, idx_l, "nnz", ndev, block)

    got = np.asarray(run(prod_s, idx_s))
    want = np.zeros((dim_pad, R))
    np.add.at(want, idx, prod)
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_ring_cpd_matches_all2all():
    tt = gen.fixture_tensor("med")
    mesh = make_mesh(n_devices=8)
    init = init_factors(tt.dims, 5, 42, dtype=jnp.float64)
    a = sharded_cpd_als(tt, rank=5, mesh=mesh, init=init,
                        opts=_opts(max_iterations=6,
                                   comm_pattern=CommPattern.ALL2ALL))
    b = sharded_cpd_als(tt, rank=5, mesh=mesh, init=init,
                        opts=_opts(max_iterations=6,
                                   comm_pattern=CommPattern.POINT2POINT))
    assert float(b.fit) == pytest.approx(float(a.fit), abs=1e-9)
    for fa, fb in zip(a.factors, b.factors):
        np.testing.assert_allclose(np.asarray(fa), np.asarray(fb), atol=1e-8)


def test_ring_cpd_matches_single_device():
    tt = gen.fixture_tensor("med4")
    init = init_factors(tt.dims, 4, 42, dtype=jnp.float64)
    single = cpd_als(tt, rank=4, opts=_opts(max_iterations=5), init=init)
    ring = sharded_cpd_als(tt, rank=4, mesh=make_mesh(n_devices=4),
                           init=init,
                           opts=_opts(max_iterations=5,
                                      comm_pattern=CommPattern.POINT2POINT))
    assert float(ring.fit) == pytest.approx(float(single.fit), abs=1e-8)
