"""Ahead-of-time compiles of the main path's Pallas kernels for a v5e.

The TPU compiler is installed here and compiles for a described chip
that is not attached, so what Mosaic would refuse on the chip (VMEM
over the limit, unaligned blocks, unsupported gathers) fails here, at
no chip time.  Shapes are NELL-2's (12092 x 9184 x 28818, 76.9M nnz)
at ranks 50 and 200 and the default nnz_block; nothing runs, so
nothing about results or times is tested.

The topology is described only inside the module fixture: a described
TPU loads libtpu, which one process at a time may hold.
"""

import dataclasses
import re

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


def spec_factors(sharding, dims=NELL2, rank=RANK):
    return [jax.ShapeDtypeStruct((d, rank), jnp.float32, sharding=sharding)
            for d in dims]


MODE_RANKS = [(m, r) for r in (RANK, 200) for m in range(3)]
MODE_RANK_IDS = [f"{m}" for m in range(3)] + [f"{m}-r200" for m in range(3)]


@pytest.fixture(scope="module")
def engine_program(one_chip):
    """The compiled unfused_pallas engine per (mode, rank), compiled
    once for the tests that read it."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _mttkrp_blocked_jit

    done = {}

    def compiled(mode, rank):
        if (mode, rank) not in done:
            done[mode, rank] = _mttkrp_blocked_jit.lower(
                spec_layout(one_chip, mode),
                spec_factors(one_chip, rank=rank), mode, "sorted_onehot",
                "pallas", _SCAN_TARGET, "unfused_pallas").compile()
        return done[mode, rank]

    return compiled


@pytest.mark.parametrize("mode,rank", MODE_RANKS, ids=MODE_RANK_IDS)
def test_unfused_pallas_engine_compiles(engine_program, mode, rank):
    """The engine the chip runs for NELL-2: a scan over block chunks,
    XLA gather, Mosaic Hadamard product and one-hot reduce.  At rank 200
    a scan step holds 10 blocks, which the kernel takes as 10 grid
    steps of 1: no step's (C, B, R) rows are padded to a larger
    chunk."""
    compiled = engine_program(mode, rank)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(rf"= f32\[\d+,{BLOCK},{rank}\]\S* pad\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30


def _while_state(text):
    """The operand tuple of the program's scan loop, ``%while = (...)``."""
    m = re.search(r"%while[.\d]* = \((.*?)\) while\(", text)
    assert m, "no while loop in the compiled program"
    return m.group(1)


@pytest.mark.parametrize("mode,rank", MODE_RANKS, ids=MODE_RANK_IDS)
def test_gathered_tables_stay_in_vmem(engine_program, mode, rank):
    """Every factor table the mode gathers sits in VMEM (memory space
    S(1)) in the scan loop's state: the one-hot kernel reserves only
    what its blocks need, so at rank 200 the 28818-row table fits
    beside the other one.  Under a kernel that asks for the whole
    vmem_limit it stays in HBM in modes 0 and 1."""
    state = _while_state(engine_program(mode, rank).as_text())
    for k, d in enumerate(NELL2):
        if k != mode:
            (layout,) = re.findall(rf"f32\[{d},{rank}\]\{{([^}}]*)\}}",
                                   state)
            assert layout.endswith("S(1)"), (d, layout)


@pytest.mark.parametrize("mode,rank", MODE_RANKS, ids=MODE_RANK_IDS)
def test_no_products_outside_the_kernel(engine_program, mode, rank):
    """The scan step hands the gathered rows to the one-hot kernel,
    which forms their Hadamard product in VMEM: the compiled program
    has no multiply shaped like a step's (C, B, R) products, or their
    flat (C·B, R) form, outside the kernel, and the kernel reads the
    ids, the values and both gathered row arrays."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _block_chunks

    C = _block_chunks(-(-NNZ // BLOCK), rank * BLOCK, _SCAN_TARGET)
    text = engine_program(mode, rank).as_text()
    for shape in (f"{C},{BLOCK},{rank}", f"{C * BLOCK},{rank}"):
        assert not re.search(rf"= f32\[{shape}\]\S* multiply\(", text), shape
        assert not re.search(rf"%\S*multiply\S*fusion\S* = f32\[{shape}\]",
                             text), shape
    (call,) = [line for line in text.splitlines()
               if "onehot_reduce_sorted" in line
               and "tpu_custom_call" in line and " = " in line]
    constraints = call.split("operand_layout_constraints={")[1]
    constraints = constraints.split("}}")[0]
    assert re.findall(r"(\w+)\[([\d,]+)\]", constraints) == [
        ("s32", f"{C},1,{BLOCK}"), ("f32", f"{C},1,{BLOCK}"),
        ("f32", f"{C},{BLOCK},{rank}"), ("f32", f"{C},{BLOCK},{rank}")]


def _scoped_sizes(text, name):
    """Scoped-VMEM bytes each compiled custom call `name` asks for."""
    return [int(size) for line in text.splitlines()
            if f"%{name}" in line and "tpu_custom_call" in line
            for size in re.findall(
                r'"scoped_memory_configs":\[\{[^]]*?"size":"(\d+)"', line)]


@pytest.mark.parametrize(
    "kernel,width,rank",
    [("sorted", SEG, RANK), ("full", 1024, RANK), ("full", 4088, RANK),
     ("sorted", SEG, 200), ("full", 1024, 200)],
    ids=["sorted-8", "full-1024", "full-4088", "sorted-8-r200",
         "full-1024-r200"])
def test_onehot_reduce_compiles(one_chip, kernel, width, rank):
    """The one-hot reduce kernels at one scan step's shapes and the
    chunk _scan_fused gives them (the full kernel at onehot_cap, and at
    rank 50 at the privatized path's widest, where the dot streams the
    one-hot and keeps no whole copy of it), fed the ids, values and two
    gathered row arrays by fusions as in the scan step, compile under
    exactly onehot_vmem_request's reservation, which stays below the
    device's vmem_limit.  (Fed straight from parameters, a kernel is not
    held to its reservation at compile time.)"""
    from splatt_tpu.devices import DEVICE_SPECS
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _block_chunks
    from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                               onehot_reduce_sorted,
                                               onehot_vmem_request,
                                               vmem_chunk)

    C = _block_chunks(-(-NNZ // BLOCK), rank * BLOCK, _SCAN_TARGET)
    local = jax.ShapeDtypeStruct((C, BLOCK), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((C, BLOCK), jnp.float32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((C, BLOCK, rank), jnp.float32,
                               sharding=one_chip)
    chunk = vmem_chunk(width, BLOCK, rank, 2, jnp.float32)
    assert chunk == 1
    want = onehot_vmem_request(chunk, width, BLOCK, rank, 2, jnp.float32)
    kind = next(iter(one_chip.device_set)).device_kind
    assert want < DEVICE_SPECS[kind].vmem_limit
    fn = onehot_reduce_sorted if kernel == "sorted" else onehot_reduce_full
    text = jax.jit(lambda l, v, a, b: fn(l + 1, v * 2, [a * 2, b * 3],
                                         width, chunk=chunk)).lower(
        local, vals, row, row).compile().as_text()
    assert _scoped_sizes(text, f"onehot_reduce_{kernel}") == [want]


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


@pytest.mark.parametrize("rank", [50, 200])
def test_als_solve_compiles(one_chip, rank):
    """The ALS update's normal-equation solve: Cholesky, and the
    eigendecomposition pseudoinverse under a ``lax.cond`` on the
    device, for NELL-2's longest mode."""
    from splatt_tpu.ops.linalg import solve_normals_report

    lhs = jax.ShapeDtypeStruct((rank, rank), jnp.float32, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((NELL2[2], rank), jnp.float32,
                               sharding=one_chip)
    compiled = jax.jit(solve_normals_report).lower(lhs, rhs).compile()
    assert "conditional" in compiled.as_text()
