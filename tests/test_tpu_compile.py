"""Ahead-of-time compiles of the main path's Pallas kernels for a v5e.

The TPU compiler is installed here and compiles for a described chip
that is not attached, so what Mosaic would refuse on the chip (VMEM
over the limit, unaligned blocks, unsupported gathers) fails here, at
no chip time.  Shapes are NELL-2's (12092 x 9184 x 28818, 76.9M nnz)
at ranks 50 and 200, and FROSTT Enron's (6066 x 5699 x 244268 x 1176,
54.2M nnz) at rank 25, at the default nnz_block; nothing runs, so
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

ENRON = (6066, 5699, 244268, 1176)
ENRON_NNZ = 54_202_099
ENRON_RANK = 25
#: (blocks, seg_width) per Enron mode under balanced packing at block
#: 4096 on Zipf(1.0) slices: the 244,268-row mode's lightest slices
#: hold 4-5 nonzeros, so its blocks span 64-96 rows by seed (96 on the
#: chip for one seed)
ENRON_BLOCKS = {0: (13233, 8), 1: (13233, 8), 2: (13264, 96),
                3: (13233, 8)}


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


def spec_layout(sharding, mode, dims=NELL2, nnz=NNZ, seg=SEG,
                nblocks=None):
    """A ModeLayout of `nnz` nonzeros in `nblocks` blocks (default: as
    few as hold them) as shapes only: built for real on a few blocks,
    one more than the modes so that no other axis has their length,
    then every per-block and per-nonzero axis is scaled to the full
    block count."""
    rng = np.random.default_rng(0)
    n = (len(dims) + 1) * BLOCK
    inds = np.stack([rng.integers(0, d, n) for d in dims])
    lay = build_layout(SparseTensor(inds, np.ones(n), dims), mode,
                       block=BLOCK, val_dtype=np.float32,
                       dense=False, record_stats=False)
    assert lay.nblocks == len(dims) + 1
    nb = nblocks or -(-nnz // BLOCK)

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
ENRON_MODES = [(m, ENRON_RANK) for m in range(4)]
ENRON_IDS = [f"enron{m}" for m in range(4)]


def engine_args(sharding, mode, rank):
    """(layout, factors) of one mode's MTTKRP as shapes: NELL-2 at
    `rank`, or Enron at ENRON_RANK."""
    if rank == ENRON_RANK:
        nb, seg = ENRON_BLOCKS[mode]
        return (spec_layout(sharding, mode, ENRON, ENRON_NNZ, seg, nb),
                spec_factors(sharding, ENRON, rank))
    return spec_layout(sharding, mode), spec_factors(sharding, rank=rank)


@pytest.fixture(scope="module")
def engine_program(one_chip):
    """The compiled unfused_pallas engine per (mode, rank), compiled
    once for the tests that read it; rank 25 is Enron's."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _mttkrp_blocked_jit

    done = {}

    def compiled(mode, rank):
        if (mode, rank) not in done:
            done[mode, rank] = _mttkrp_blocked_jit.lower(
                *engine_args(one_chip, mode, rank), mode, "sorted_onehot",
                "pallas", _SCAN_TARGET, "unfused_pallas").compile()
        return done[mode, rank]

    return compiled


@pytest.mark.parametrize("mode,rank", MODE_RANKS + ENRON_MODES,
                         ids=MODE_RANK_IDS + ENRON_IDS)
def test_unfused_pallas_engine_compiles(engine_program, mode, rank):
    """The engine the chip runs for NELL-2 and Enron: a scan over block
    chunks, XLA gather, Mosaic Hadamard product and one-hot reduce.  At
    rank 200 a scan step holds 10 blocks, which the kernel takes as 10
    grid steps of 1: no step's (C, B, R) rows are padded to a larger
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


def _tables_in_hbm(text, dims, rank, mode):
    """Modes whose factor table the scan loop's state holds outside
    VMEM (memory space S(1))."""
    state = _while_state(text)
    out = []
    for k, d in enumerate(dims):
        if k != mode:
            (layout,) = re.findall(rf"f32\[{d},{rank}\]\{{([^}}]*)\}}",
                                   state)
            if not layout.endswith("S(1)"):
                out.append(k)
    return out


@pytest.mark.parametrize("mode,rank", ENRON_MODES, ids=ENRON_IDS)
def test_enron_tables_stay_in_vmem_but_the_long_mode(engine_program, mode,
                                                     rank):
    """At Enron's rank 25 a scan step's row arrays pad 25 lanes to 128:
    sized by their padded bytes (26 blocks, 156 MiB for three row
    arrays) the step leaves every gathered table that fits in VMEM, and
    only the 244,268-row table (119.3 MiB padded) in HBM.  Sized by
    their logical elements (81 blocks) the step left every table, the
    3 MB ones too, in HBM."""
    hbm = _tables_in_hbm(engine_program(mode, rank).as_text(), ENRON,
                         rank, mode)
    assert hbm == ([] if mode == 2 else [2])


@pytest.mark.parametrize("mode,rank", MODE_RANKS + ENRON_MODES,
                         ids=MODE_RANK_IDS + ENRON_IDS)
def test_tables_hbm_is_the_compiled_placement(engine_program, mode, rank):
    """The dispatch span's ``tables_hbm``, reckoned from shapes beside
    the kernel's request and the step's row arrays, names exactly the
    tables the compiled program keeps outside VMEM."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _vmem_attrs

    lay, factors = engine_args(None, mode, rank)
    dims = ENRON if rank == ENRON_RANK else NELL2
    want = _tables_in_hbm(engine_program(mode, rank).as_text(), dims, rank,
                          mode)
    attrs = _vmem_attrs(lay, factors, mode, "sorted_onehot",
                        "unfused_pallas", _SCAN_TARGET)
    assert attrs["tables_hbm"] == want


@pytest.mark.parametrize("mode", [0, 3], ids=["enron0", "enron3"])
def test_tables_hbm_past_the_row_bound(one_chip, monkeypatch, mode):
    """A scan step sized by the element bound alone (81 blocks at rank
    25: three row arrays of 162 MiB padded) leaves every gathered table
    of the compiled program in HBM, the 0.6-3.1 MB ones too, and
    ``tables_hbm``, given the step's padded row bytes, names all three.
    Within the bound the same reckoning names only the long table."""
    import sys

    mk = sys.modules["splatt_tpu.ops.mttkrp"]
    lay, factors = engine_args(None, mode, ENRON_RANK)
    kernel = mk._kernel_vmem(lay, factors, mode, "sorted_onehot",
                             "unfused_pallas")
    rows = 3 * mk._row_bytes(BLOCK, ENRON_RANK, jnp.float32)
    others = [k for k in range(4) if k != mode]
    assert mk._tables_hbm(factors, mode, kernel, 26 * rows) == [2]
    with monkeypatch.context() as mp:
        mp.setattr(mk, "_scan_row_bytes", lambda: 1 << 40)
        # a scan_target of its own, so no trace made under the real
        # bound is reused; it gives the same 81 blocks
        target = mk._SCAN_TARGET + 1
        assert mk._scan_chunk(ENRON_BLOCKS[mode][0], 8, BLOCK, ENRON_RANK,
                              3, jnp.float32, True, target) == 81
        text = mk._mttkrp_blocked_jit.lower(
            *engine_args(one_chip, mode, ENRON_RANK), mode,
            "sorted_onehot", "pallas", target,
            "unfused_pallas").compile().as_text()
    assert re.search(rf"f32\[\d+,81,{BLOCK}\]", _while_state(text))
    assert _tables_in_hbm(text, ENRON, ENRON_RANK, mode) == others
    assert mk._tables_hbm(factors, mode, kernel, 81 * rows) == others


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


@pytest.mark.parametrize("mode,rank", MODE_RANKS + ENRON_MODES,
                         ids=MODE_RANK_IDS + ENRON_IDS)
def test_no_products_outside_the_kernel(engine_program, mode, rank):
    """The scan step hands the gathered rows to the one-hot kernel,
    which forms their Hadamard product in VMEM: the compiled program
    has no multiply shaped like a step's (C, B, R) products, or their
    flat (C·B, R) form, outside the kernel, and the kernel reads the
    ids, the values and every gathered row array (two for NELL-2,
    three for Enron)."""
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _scan_chunk

    lay, factors = engine_args(None, mode, rank)
    nrows = len(factors) - 1
    C = _scan_chunk(lay.nblocks, lay.seg_width, BLOCK, rank, nrows,
                    jnp.float32, True, _SCAN_TARGET)
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
        *[("f32", f"{C},{BLOCK},{rank}")] * nrows]


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
    from splatt_tpu.ops.mttkrp import _SCAN_TARGET, _scan_chunk
    from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                               onehot_reduce_sorted,
                                               onehot_vmem_request,
                                               vmem_chunk)

    C = _scan_chunk(-(-NNZ // BLOCK), width, BLOCK, rank, 2, jnp.float32,
                    True, _SCAN_TARGET)
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
