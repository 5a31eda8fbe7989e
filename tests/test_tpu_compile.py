"""Ahead-of-time compiles of the main path's Pallas kernels for a v5e.

The TPU compiler is installed here and compiles for a described chip
that is not attached, so what Mosaic would refuse on the chip (VMEM
over the limit, unaligned blocks, unsupported gathers) fails here, at
no chip time.  Shapes are NELL-2's (12092 x 9184 x 28818, 76.9M nnz)
at rank 50 and the default nnz_block; nothing runs, so nothing about
results or times is tested.

The topology is described only inside the module fixture: a described
TPU loads libtpu, which one process at a time may hold.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu.blocked import build_layout
from splatt_tpu.coo import SparseTensor

NELL2 = (12092, 9184, 28818)
NNZ = 76_879_419
RANK = 50
BLOCK = 4096
#: max output rows one 4096-nonzero block spans at NELL-2 density
SEG = 8
#: what Mosaic (jax 0.9.0) says for a lane gather wider than one vreg
GATHER_REFUSAL = "Multiple source vregs along gather dimension"


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the kernels' VMEM budget steered to
    the v5e row of splatt_tpu/devices.py."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import splatt_tpu.ops.pallas_kernels as pk
    from splatt_tpu.devices import DEVICE_SPECS

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    kind = topo.devices[0].device_kind
    assert kind in DEVICE_SPECS
    # the chip path runs in 32-bit mode (conftest turns x64 on for the
    # f64 differential tests; Mosaic index maps must stay i32)
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(pk, "_vmem_limit",
                       lambda: DEVICE_SPECS[kind].vmem_limit)
            yield topo
    finally:
        jax.config.update("jax_enable_x64", x64)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def spec_layout(sharding, mode, dims=NELL2, nnz=NNZ, seg=SEG):
    """A ModeLayout of `nnz` nonzeros as shapes only: built for real on
    a few blocks, then every per-block and per-nonzero axis is scaled
    to the full block count."""
    rng = np.random.default_rng(0)
    n = 4 * BLOCK
    inds = np.stack([rng.integers(0, d, n) for d in dims])
    lay = build_layout(SparseTensor(inds, np.ones(n), dims), mode,
                       block=BLOCK, val_dtype=np.float32,
                       dense=False, record_stats=False)
    nb = -(-nnz // BLOCK)

    def scale(x):
        shape = tuple(nb if s == lay.nblocks else
                      nb * BLOCK if s == lay.nnz_pad else s
                      for s in x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)

    lay = jax.tree_util.tree_map(scale, lay)
    return dataclasses.replace(lay, seg_width=seg, nnz=nnz)


def spec_factors(sharding, dims=NELL2):
    return [jax.ShapeDtypeStruct((d, RANK), jnp.float32, sharding=sharding)
            for d in dims]


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_unfused_pallas_engine_compiles(one_chip, mode):
    """The engine the chip runs for NELL-2: a scan over block chunks,
    XLA gather + Hadamard, Mosaic one-hot reduce."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _mttkrp_blocked_jit

    lay = spec_layout(one_chip, mode)
    compiled = _mttkrp_blocked_jit.lower(
        lay, spec_factors(one_chip), mode, "sorted_onehot", "pallas",
        _SCAN_TARGET, "unfused_pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("kernel,width", [("sorted", SEG),
                                          ("full", 1024)])
def test_onehot_reduce_compiles(one_chip, kernel, width):
    """The one-hot reduce kernels at one scan step's shapes (the chunk
    _scan_fused takes at rank 50); the full kernel at onehot_cap."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _block_chunks
    from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                               onehot_reduce_sorted,
                                               vmem_chunk)

    C = _block_chunks(-(-NNZ // BLOCK), RANK * BLOCK, _SCAN_TARGET)
    local = jax.ShapeDtypeStruct((C, BLOCK), jnp.int32, sharding=one_chip)
    prod = jax.ShapeDtypeStruct((C, BLOCK, RANK), jnp.float32,
                                sharding=one_chip)
    chunk = vmem_chunk(width, BLOCK, RANK)
    assert chunk >= 1
    fn = onehot_reduce_sorted if kernel == "sorted" else onehot_reduce_full
    fn.lower(local, prod, width, chunk=chunk).compile()


def _fused(name):
    from splatt_tpu.ops import pallas_kernels as pk

    return {"fused_t": pk.fused_mttkrp_t, "fused_tg": pk.fused_mttkrp_tg}[name]


@pytest.mark.parametrize("name", ["fused_t", "fused_tg"])
def test_fused_gather_refused_at_nell2_widths(one_chip, name):
    """The in-kernel gather family at NELL-2 widths: Mosaic refuses the
    lane gather over a 9184-row table.  This is why engine_chain keeps
    these engines off the chip above 128 gathered rows; when a JAX
    release lowers it, this test fails and the gate can go."""
    lay = spec_layout(one_chip, 0)
    with pytest.raises(Exception, match=GATHER_REFUSAL):
        _fused(name).lower(lay, spec_factors(one_chip), mode=0,
                           width=SEG, accumulate=False,
                           interpret=False).compile()


@pytest.mark.parametrize("name", ["fused_t", "fused_tg"])
def test_fused_gather_within_one_vreg(one_chip, name):
    """...and where every gathered factor fits one 128-lane vreg they
    compile (the chain admits them on the chip)."""
    dims = (NELL2[0], 128, 120)
    lay = spec_layout(one_chip, 0, dims=dims, nnz=1000 * BLOCK)
    _fused(name).lower(lay, spec_factors(one_chip, dims), mode=0,
                       width=SEG, accumulate=False,
                       interpret=False).compile()


def test_fused_dense_compiles(one_chip):
    """The dense-mode MXU kernel at the densemode cell's shape, rank 50."""
    from splatt_tpu.blocked import build_dense_layout
    from splatt_tpu.ops.pallas_kernels import dense_vmem_ok, fused_dense

    dims = (24, 256, 512)
    rng = np.random.default_rng(0)
    n = 200_000
    inds = np.stack([rng.integers(0, d, n) for d in dims])
    lay = build_dense_layout(SparseTensor(inds, np.ones(n), dims), 0,
                             val_dtype=np.float32)
    facs = spec_factors(one_chip, dims)
    assert dense_vmem_ok(lay, facs, 0)
    lay = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), lay)
    fused_dense.lower(lay, facs, mode=0, interpret=False).compile()


@pytest.mark.parametrize("kernel", ["gather", "reduce"])
def test_rdma_ring_kernels_compile_for_2x2(topo, kernel):
    """The async ring's RDMA kernels inside the fine decomposition's
    shard_map on a 4-chip mesh, at the four-chip smoke's size (20M
    nonzeros, 5M per chip) and NELL-2's widest mode (7205 rows per
    chip), rank 50."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import splatt_tpu.parallel.ring_kernels as rk

    ndev, nnz = 4, 20_000_000
    block = -(-NELL2[2] // ndev)
    mesh = Mesh(np.array(topo.devices), ("x",))

    def spec(shape, dtype, p):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, p))

    idx = spec((nnz,), jnp.int32, P("x"))
    if kernel == "gather":
        def body(u, i):
            return rk._gather_pallas(u, i, "x", ndev)

        first = spec((ndev * block, RANK), jnp.float32, P("x", None))
    else:
        def body(p, i):
            return rk._reduce_pallas(p, i, "x", ndev, block)

        first = spec((nnz, RANK), jnp.float32, P("x", None))
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None), P("x")),
                           out_specs=P("x", None), check_vma=False))
    compiled = fn.lower(first, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
