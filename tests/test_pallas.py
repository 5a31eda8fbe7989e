"""Pallas kernel differential tests (interpret mode on CPU).

≙ the reference's practice of running the real optimized kernels in
tests (tests/mttkrp_test.c) — interpret mode executes the exact kernel
semantics that Mosaic compiles on TPU.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import BlockAlloc, Options
from splatt_tpu.ops import pallas_kernels as pk
from splatt_tpu.ops.mttkrp import mttkrp, mttkrp_blocked
from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                           onehot_reduce_sorted)
from tests import gen
from tests.test_mttkrp import make_factors, np_mttkrp

TOL = 1e-10


def _np_onehot_sorted(local, prod, S):
    nb, B = local.shape
    out = np.zeros((nb, S, prod.shape[-1]), dtype=np.float64)
    for b in range(nb):
        for j in range(B):
            s = local[b, j]
            if 0 <= s < S:
                out[b, s] += prod[b, j]
    return out


def _kernel_inputs(rng, nb, B, R, nrows=2, dtype=np.float64):
    """Values (nb, B) and `nrows` gathered row arrays (nb, B, R), and
    their products ``vals · ⊛ rows`` in float64 from the values as
    passed."""
    vals = jnp.asarray(rng.random((nb, B)), dtype=dtype)
    rows = [jnp.asarray(rng.random((nb, B, R)), dtype=dtype)
            for _ in range(nrows)]
    prod = np.asarray(vals, np.float64)[..., None]
    for r in rows:
        prod = prod * np.asarray(r, np.float64)
    return vals, rows, prod


def test_onehot_reduce_sorted_kernel():
    rng = np.random.default_rng(0)
    nb, B, S, R = 5, 128, 16, 8
    local = rng.integers(-1, S + 3, size=(nb, B)).astype(np.int32)
    vals, rows, prod = _kernel_inputs(rng, nb, B, R)
    got = onehot_reduce_sorted(jnp.asarray(local), vals, rows, S,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               _np_onehot_sorted(local, prod, S), atol=TOL)


def test_onehot_reduce_full_kernel():
    rng = np.random.default_rng(1)
    nb, B, W, R = 9, 128, 24, 8  # nb not divisible by the chunk size
    local = rng.integers(0, W, size=(nb, B)).astype(np.int32)
    vals, rows, prod = _kernel_inputs(rng, nb, B, R)
    got = onehot_reduce_full(jnp.asarray(local), vals, rows, W,
                             interpret=True)
    want = _np_onehot_sorted(local, prod, W).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


#: (values, factor rows) dtypes: f32 throughout; bf16 storage of the
#: values beside f32 factors (the cells' ``val_storage="bf16"``); bf16
#: throughout
_KERNEL_DTYPES = {"f32": (jnp.float32, jnp.float32),
                  "bf16vals": (jnp.bfloat16, jnp.float32),
                  "bf16": (jnp.bfloat16, jnp.bfloat16)}


def _stream_case(nmodes, rank, val_dtype, dtype, B=128, nblocks=4):
    """A tensor with one output mode sorted into `nblocks` blocks of B
    nonzeros, as the scan step hands them to the kernels: the local
    ids, values and gathered rows per block, the blocks' first rows,
    and the stream oracle over the same (rounded) values and factors.
    The last real block is ragged (its tail ids -1, values 0: the
    sentinel), and an all-zero block follows it."""
    from splatt_tpu.ops.mttkrp import mttkrp_stream

    rng = np.random.default_rng(nmodes * 1000 + rank)
    dims = (40, 23, 31, 17)[:nmodes]
    nnz = (nblocks - 1) * B - B // 3
    inds = np.stack([np.sort(rng.integers(0, dims[0], nnz))]
                    + [rng.integers(0, d, nnz) for d in dims[1:]])
    vals = jnp.asarray(rng.random(nnz), dtype=val_dtype)
    factors = [jnp.asarray(rng.random((d, rank)), dtype=dtype)
               for d in dims]
    gold = mttkrp_stream(jnp.asarray(inds),
                         jnp.asarray(vals, jnp.float64),
                         [jnp.asarray(f, jnp.float64) for f in factors],
                         0, dims[0])
    pad = nblocks * B - nnz
    ids = np.concatenate([inds[0], np.full(pad, -1)]).reshape(nblocks, B)
    row_start = np.where(ids >= 0, ids, dims[0]).min(axis=1)
    local = np.where(ids >= 0, ids - row_start[:, None], -1)
    vals_b = jnp.pad(vals, (0, pad)).reshape(nblocks, B)
    rows = [jnp.pad(factors[k][inds[k]], ((0, pad), (0, 0)))
            .reshape(nblocks, B, rank) for k in range(1, nmodes)]
    return (jnp.asarray(local, jnp.int32), vals_b, rows, row_start,
            np.asarray(ids), np.asarray(gold), dims[0])


@pytest.mark.parametrize("rank", [50, 200])
@pytest.mark.parametrize("nmodes", [3, 4])
@pytest.mark.parametrize("dtypes", sorted(_KERNEL_DTYPES))
@pytest.mark.parametrize("kernel", ["sorted", "full"])
def test_onehot_kernels_match_stream(kernel, dtypes, nmodes, rank):
    """The kernels, fed the ids, values and nmodes - 1 gathered row
    arrays of a sorted mode (a ragged sentinel tail and an all-zero
    block included), match mttkrp_stream over the same values and
    factors: the per-block partials merged at their first rows, or the
    full-width total.  bf16 inputs accumulate in f32 (SPL024)."""
    val_dtype, dtype = _KERNEL_DTYPES[dtypes]
    local, vals, rows, row_start, ids, gold, dim = _stream_case(
        nmodes, rank, val_dtype, dtype)
    assert len(rows) == nmodes - 1
    if kernel == "sorted":
        S = int(np.asarray(local).max()) + 1
        parts = onehot_reduce_sorted(local, vals, rows, S, interpret=True,
                                     chunk=2)
        assert parts.dtype == jnp.float32
        got = np.zeros((dim + S + 1, rank))
        np.add.at(got, (row_start[:, None] + np.arange(S)).reshape(-1),
                  np.asarray(parts, np.float64).reshape(-1, rank))
    else:
        W = -(-(dim + 1) // 8) * 8
        got = onehot_reduce_full(jnp.asarray(np.where(ids >= 0, ids, dim),
                                             jnp.int32),
                                 vals, rows, W, interpret=True, chunk=3)
        assert got.dtype == jnp.float32
        got = np.asarray(got, np.float64)
    err = np.abs(got[:dim] - gold).max() / np.abs(gold).max()
    assert err < (1e-6 if dtype == jnp.float32 else 1e-2), err


def test_onehot_vmem_request_counts_tile_padding():
    # rank 200 pads to 256 lanes: two (1, 4096, 200) f32 row blocks,
    # double-buffered, are 16 MiB on their own
    assert pk.padded_bytes((1, 4096, 200), 4) == 4096 * 256 * 4
    assert pk.padded_bytes((5, 1, 4096), 4) == 5 * 8 * 4096 * 4
    assert pk.padded_bytes((5, 1, 4096), 2) == 5 * 8 * 4096 * 4
    assert pk.padded_bytes((3, 16), 2) == 16 * 128 * 2
    req = pk.onehot_vmem_request(1, 8, 4096, 200, 2, jnp.float32)
    assert req >= 2 * 2 * pk.padded_bytes((1, 4096, 200), 4) >= 16 << 20
    # ...and asks for less than the flat limit it replaces
    assert req < pk._vmem_limit()


#: the request the kernels made at NELL-2's scan steps (10 blocks of
#: 4096 at rank 200 as 2 grid steps of 5, 40 at rank 50 as 5 of 8) when
#: they read one (C, B, R) products block: (rank, seg_width) → bytes
_PRODUCTS_KERNEL_REQUEST = {(200, 8): 48_881_664, (50, 8): 42_041_344,
                            (50, 16): 44_236_800}


@pytest.mark.parametrize("rank,seg", sorted(_PRODUCTS_KERNEL_REQUEST))
def test_onehot_vmem_request_at_nell2_steps(rank, seg):
    """At NELL-2's scan steps (block 4096; 10 blocks at rank 200, 40 at
    rank 50; seg widths 8 and 16) the kernels reading two gathered row
    arrays, one block per grid step, ask for no more VMEM than the
    products kernel did, so XLA keeps the room it had for the gathered
    tables."""
    from splatt_tpu.ops.mttkrp import _scan_chunk

    C = _scan_chunk(18770, seg, 4096, rank, 2, jnp.float32, True)
    assert C == {200: 10, 50: 40}[rank]
    chunk = pk.vmem_chunk(seg, 4096, rank, 2, jnp.float32)
    assert chunk == 1
    req = pk.onehot_vmem_request(chunk, seg, 4096, rank, 2, jnp.float32)
    assert req <= _PRODUCTS_KERNEL_REQUEST[rank, seg]


@pytest.mark.parametrize("rank,nrows,nblocks,dtype,want", [
    (25, 3, 13233, jnp.float32, 26),
    (50, 2, 18770, jnp.float32, 40),
    (200, 2, 18770, jnp.float32, 10),
    (25, 2, 18770, jnp.float32, 40),
    (25, 3, 13233, jnp.bfloat16, 53),
    (256, 7, 18770, jnp.float32, 5),
    (50, 2, 12, jnp.float32, 12)],
    ids=["enron-r25", "nell2-r50", "nell2-r200", "3mode-r25",
         "enron-r25-bf16", "8mode-r256", "short-mode"])
def test_scan_chunk_counts_padded_row_arrays(rank, nrows, nblocks, dtype,
                                             want):
    """The unfused Pallas scan step holds each (C, 4096, R) row array to
    2^23 elements and all of them, tile-padded to 128 lanes, to 160
    MiB: Enron's rank 25 pads to 128 lanes, so three row arrays take 26
    blocks (81 by elements alone); NELL-2's steps stay at 40 (rank 50,
    both bounds) and 10 (rank 200, the elements); bf16 rows pad to half
    the bytes; a mode shorter than a step takes all its blocks."""
    from splatt_tpu.ops.mttkrp import (_SCAN_TARGET, _block_chunks,
                                       _scan_chunk, _scan_row_bytes)

    C = _scan_chunk(nblocks, 8, 4096, rank, nrows, dtype, True)
    assert C == want
    lanes = -(-rank // 128) * 128
    step = C * nrows * 4096 * lanes * jnp.dtype(dtype).itemsize
    assert _scan_row_bytes() == 160 << 20
    assert step <= _scan_row_bytes() and C * rank * 4096 <= _SCAN_TARGET
    # the XLA engine's step is its one-hot's, as before
    assert (_scan_chunk(nblocks, 8, 4096, rank, nrows, dtype, False)
            == _block_chunks(nblocks, 8 * 4096))


def test_tables_hbm_fills_vmem_smallest_first():
    """``tables_hbm`` reckons the gathered tables, smallest first, into
    what VMEM holds beside the kernel's request: Enron's 244,268-row
    table (119.3 MiB padded at rank 25) fits no 128 MiB VMEM beside an
    18.5 MiB kernel; beside a kernel asking for the whole 100 MiB
    vmem_limit only the small tables fit.  A step whose padded row
    arrays pass the 160 MiB bound (81 blocks of three rank-25 row
    arrays: 486 MiB) leaves every table in HBM; one at the bound (26
    blocks: 156 MiB) does not change the reckoning."""
    from splatt_tpu.ops.mttkrp import _tables_hbm

    f = [jnp.zeros((d, 25), jnp.float32) for d in (6066, 5699, 244268, 1176)]
    mib = 1 << 20
    assert _tables_hbm(f, 0, 18 * mib + mib // 2) == [2]
    assert _tables_hbm(f, 2, 18 * mib + mib // 2) == []
    assert _tables_hbm(f, 0, 0) == []
    assert _tables_hbm(f, 3, 100 * mib) == [2]
    assert _tables_hbm(f, 3, 127 * mib) == [0, 1, 2]
    row = 4096 * 128 * 4
    assert _tables_hbm(f, 0, 18 * mib, 26 * 3 * row) == [2]
    assert _tables_hbm(f, 0, 18 * mib, 160 * mib) == [2]
    assert _tables_hbm(f, 0, 18 * mib, 81 * 3 * row) == [1, 2, 3]
    assert _tables_hbm(f, 2, 18 * mib, 160 * mib + 1) == [0, 1, 3]


def test_vmem_chunk_counts_every_row_array():
    """Each gathered row array is one more double-buffered (1, B, R)
    block: the request grows by exactly two padded row blocks per row
    array, and the gate closes once they no longer fit."""
    for rank in (50, 200):
        reqs = [pk.onehot_vmem_request(1, 8, 4096, rank, n, jnp.float32)
                for n in (1, 2, 3)]
        step = 2 * pk.padded_bytes((1, 4096, rank), 4)
        assert reqs[1] - reqs[0] == reqs[2] - reqs[1] == step
        assert [pk.vmem_chunk(8, 4096, rank, n, jnp.float32)
                for n in (1, 2, 3, 7)] == [1, 1, 1, 1]
    # 16 MiB row blocks: two row arrays fit the kernel budget, three not
    assert [pk.vmem_chunk(8, 16384, 256, n, jnp.float32)
            for n in (1, 2, 3)] == [1, 1, 0]


#: the chain head of a privatized mode (block 4096, three modes) by
#: rank and mode length.  At rank 50 the dot streams the one-hot, and
#: even a 4080-row mode needs little VMEM; at rank 200 Mosaic keeps
#: whole one-hot copies, and past about 1150 rows one block needs more
#: than the kernel budget (AOT compile for a v5e).  The products kernel
#: before the in-kernel products gave the same heads, except for rank
#: 50 at 4080 rows (xla_scan: it counted the one-hot at every rank) and
#: rank 200 at 1717 and 2482 rows (unfused_pallas, whose compile then
#: failed: its one-hot copies alone pass the device's vmem_limit).
_PRIVATIZED_HEADS = {(50, 1140): "unfused_pallas", (50, 1717): "unfused_pallas",
                     (50, 2482): "unfused_pallas", (50, 2862): "unfused_pallas",
                     (50, 4080): "unfused_pallas",
                     (200, 1024): "unfused_pallas",
                     (200, 1140): "unfused_pallas", (200, 1717): "xla_scan",
                     (200, 2482): "xla_scan", (200, 2862): "xla_scan"}


@pytest.mark.parametrize("rank,dim", sorted(_PRIVATIZED_HEADS))
def test_engine_chain_at_privatized_widths(rank, dim):
    from types import SimpleNamespace

    from splatt_tpu.ops.mttkrp import engine_chain

    facs = [jax.ShapeDtypeStruct((d, rank), jnp.float32)
            for d in (dim, 9184, 28818)]
    lay = SimpleNamespace(block=4096, seg_width=8, nnz_pad=4096)
    chain = engine_chain(lay, facs, 0, "privatized", "pallas")
    assert chain[0] == _PRIVATIZED_HEADS[rank, dim]
    assert chain[-2:] == ["xla_scan", "xla"]


@pytest.mark.parametrize("width,dtype", [(8, jnp.float32),
                                         (16, jnp.bfloat16)])
def test_onehot_vmem_request_grows_with_chunk_and_rank(width, dtype):
    by_chunk = [pk.onehot_vmem_request(c, width, 4096, 50, 2, dtype)
                for c in range(1, 9)]
    assert by_chunk == sorted(set(by_chunk))
    # 50, 128, 200 and 256 lanes pad to 128, 128, 256 and 256
    by_rank = [pk.onehot_vmem_request(4, width, 4096, r, 2, dtype)
               for r in (50, 128, 200, 256, 300)]
    assert by_rank[0] == by_rank[1] < by_rank[2] == by_rank[3] < by_rank[4]


def test_onehot_vmem_request_is_capped_at_vmem_limit(monkeypatch):
    limit = pk._vmem_limit()
    assert pk.onehot_vmem_request(8, 2048, 4096, 1024, 2,
                                  jnp.float32) == limit
    monkeypatch.setattr(pk, "_vmem_limit", lambda: 8 << 20)
    assert pk.onehot_vmem_request(5, 8, 4096, 200, 2, jnp.float32) == 8 << 20
    assert pk.onehot_vmem_request(1, 8, 128, 8, 2, jnp.float32) < 8 << 20


@pytest.mark.parametrize("name", ["med", "med4"])
def test_pallas_mttkrp_matches_oracle(name):
    """Full blocked MTTKRP with the Pallas engine (interpret) on every
    mode/path where a one-hot reduction runs."""
    tt = gen.fixture_tensor(name)
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    for mode in range(tt.nmodes):
        want = np_mttkrp(tt, factors, mode)
        got = mttkrp_blocked(bs.layout_for(mode), factors, mode,
                             path="sorted_onehot", impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=f"sorted_onehot mode={mode}")
        other = bs.layout_for((mode + 1) % tt.nmodes)
        if other.mode != mode:
            got = mttkrp_blocked(other, factors, mode,
                                 path="privatized", impl="pallas_interpret")
            np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                       err_msg=f"privatized mode={mode}")


def test_public_mttkrp_forced_pallas():
    tt = gen.fixture_tensor("med")
    opts = Options(val_dtype=np.float64, use_pallas=True, nnz_block=256)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    got = mttkrp(bs, factors, bs.layouts[0].mode)
    want = np_mttkrp(tt, factors, bs.layouts[0].mode)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


def test_unfused_pallas_engine_direct():
    """The chip's main-path engine (scan over block chunks: XLA gather,
    Mosaic Hadamard product and one-hot reduce) — sorted partials +
    privatized totals vs the numpy brute force."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import _scan_fused

    tt = gen.fixture_tensor("med")
    factors = make_factors(tt.dims)
    for mode in range(tt.nmodes):
        lay = build_layout(tt, mode, block=128, val_dtype=np.float64)
        want = np_mttkrp(tt, factors, mode)
        S = lay.seg_width
        # a small step target forces several scan steps and a padded tail
        parts = _scan_fused(lay, factors, mode, S, accumulate=False,
                            target_elems=3 * 128 * 8, pallas=True,
                            interpret=True)
        idx = (np.asarray(lay.row_start)[:, None] + np.arange(S)).reshape(-1)
        out = np.zeros((tt.dims[mode] + S + 1, factors[0].shape[1]))
        np.add.at(out, idx, np.asarray(parts).reshape(-1, factors[0].shape[1]))
        np.testing.assert_allclose(out[:tt.dims[mode]], want, atol=TOL,
                                   err_msg=f"unfused sorted mode={mode}")
        W = -(-(tt.dims[mode] + 1) // 8) * 8
        tot = _scan_fused(lay, factors, mode, W, accumulate=True,
                          target_elems=3 * 128 * 8, pallas=True,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(tot)[:tt.dims[mode]], want,
                                   atol=TOL,
                                   err_msg=f"unfused privatized mode={mode}")


def test_pallas_unfused_fallback_matches():
    """The Pallas engine (interpret mode) plans unfused_pallas, the
    engine the chip runs, for every mode with no gate patched — the
    same answer as the brute force."""
    from splatt_tpu.ops.mttkrp import engine_plan

    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    for mode in range(tt.nmodes):
        lay = bs.layout_for(mode)
        assert engine_plan(lay, factors, mode, "sorted_onehot",
                           "pallas_interpret") == "unfused_pallas"
        want = np_mttkrp(tt, factors, mode)
        got = mttkrp_blocked(lay, factors, mode,
                             path="sorted_onehot", impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=f"unfused fallback mode={mode}")


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret", "xla"])
@pytest.mark.parametrize("path", ["sorted_onehot", "privatized"])
def test_engine_chain_is_the_chips(impl, path):
    """The one-hot paths' chain is exactly unfused_pallas → xla_scan →
    xla under a Pallas impl, on the chip and in interpret mode alike,
    and xla_scan → xla under the XLA impl: the CPU tests that force
    Pallas run the engine the chip runs."""
    from splatt_tpu.ops.mttkrp import engine_chain

    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    lay = bs.layout_for(0 if path == "sorted_onehot" else 1)
    want = (["unfused_pallas", "xla_scan", "xla"] if impl != "xla"
            else ["xla_scan", "xla"])
    assert engine_chain(lay, factors, 0, path, impl) == want


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_engine_chain_skips_unfused_pallas_past_vmem(impl):
    """When not even one block's one-hot, gathered rows and partials fit
    the kernel's VMEM budget (vmem_chunk is 0), the chain starts at
    xla_scan, which streams the one-hot through HBM instead."""
    from types import SimpleNamespace

    from splatt_tpu.ops.mttkrp import engine_chain

    facs = [jax.ShapeDtypeStruct((d, 256), jnp.float32)
            for d in (4096, 48, 80)]
    lay = SimpleNamespace(block=16384, seg_width=4096, nnz_pad=16384)
    assert pk.vmem_chunk(4096, 16384, 256, 2, jnp.float32) == 0
    assert engine_chain(lay, facs, 0, "sorted_onehot",
                        impl) == ["xla_scan", "xla"]


def test_demoted_unfused_pallas_lands_on_xla_scan():
    """A per-shape (RESOURCE) demotion of unfused_pallas leaves xla_scan
    at the head of the chain for that shape, and dispatch through it
    matches the brute force."""
    from splatt_tpu import resilience
    from splatt_tpu.ops.mttkrp import _engine_shape_key, engine_plan

    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    lay = bs.layout_for(0)
    key = _engine_shape_key(lay, factors, 0)
    resilience.reset_demotions()
    try:
        resilience.demote_engine(
            "unfused_pallas", RuntimeError("RESOURCE_EXHAUSTED: vmem"),
            shape_key=key)
        assert engine_plan(lay, factors, 0, "sorted_onehot",
                           "pallas_interpret") == "xla_scan"
        got = mttkrp_blocked(lay, factors, 0, path="sorted_onehot",
                             impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got),
                                   np_mttkrp(tt, factors, 0), atol=TOL)
        assert resilience.demotions()[0].shape_key == key
    finally:
        resilience.reset_demotions()


def test_unfused_pallas_bf16_accumulates_f32():
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import _scan_fused

    tt = gen.fixture_tensor("med")
    factors = [jnp.asarray(np.asarray(f), dtype=jnp.bfloat16)
               for f in make_factors(tt.dims)]
    lay = build_layout(tt, 0, block=128, val_dtype=jnp.bfloat16)
    W = -(-(tt.dims[0] + 1) // 8) * 8
    tot = _scan_fused(lay, factors, 0, W, accumulate=True, pallas=True,
                      interpret=True)
    assert tot.dtype == jnp.float32
    want = np_mttkrp(tt, [np.asarray(f, np.float64) for f in factors], 0)
    np.testing.assert_allclose(np.asarray(tot)[:tt.dims[0]], want, atol=0.6,
                               rtol=0.1)


def test_vmem_chunk_bounds():
    from splatt_tpu.ops.pallas_kernels import vmem_chunk

    f32 = jnp.float32
    assert vmem_chunk(64, 512, 128, 2, f32) >= 1      # typical config fits
    assert vmem_chunk(4096, 4096, 256, 2, f32) == 0   # pathological: fall back
    assert 1 <= vmem_chunk(8, 128, 8, 2, f32) <= 8
    assert 1 <= vmem_chunk(8, 128, 8, 7, f32) <= 8    # an 8-mode tensor


def test_pallas_bf16_accumulates_f32():
    """bf16 inputs through the Pallas kernels produce f32 outputs that
    match the f64 brute force at bf16 tolerance."""
    rng = np.random.default_rng(3)
    nb, B, S, R = 4, 128, 16, 8
    local = rng.integers(-1, S + 2, size=(nb, B)).astype(np.int32)
    vals16, rows16, prod = _kernel_inputs(rng, nb, B, R,
                                          dtype=jnp.bfloat16)
    got = onehot_reduce_sorted(jnp.asarray(local), vals16, rows16, S,
                               interpret=True)
    assert got.dtype == jnp.float32
    want = _np_onehot_sorted(local, prod, S)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float64), want,
                               atol=3e-2)
    got2 = onehot_reduce_full(jnp.asarray(local), vals16, rows16, S + 8,
                              interpret=True)
    assert got2.dtype == jnp.float32
    want2 = _np_onehot_sorted(local, prod, S + 8).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got2, dtype=np.float64), want2,
                               atol=3e-2)


@pytest.mark.parametrize("path,seg", [("sorted_onehot", 8),
                                      ("sorted_onehot", 16),
                                      ("privatized", None)])
def test_unfused_pallas_production_dims_interpret(path, seg):
    """unfused_pallas at NELL-2's gathered widths (9184 and 28818
    rows), block 4096 and rank 50, through dispatch in interpret mode:
    sorted blocks spanning 8 or 16 output rows (the cells' seg_width),
    and a privatized short mode, match the stream oracle."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.coo import SparseTensor
    from splatt_tpu.ops.mttkrp import engine_plan, mttkrp_stream

    rng = np.random.default_rng(0)
    block, rank = 4096, 50
    nnz = 2 * block
    if path == "sorted_onehot":
        dims = (12092, 9184, 28818)
        # block b holds rows [b*seg, (b+1)*seg): seg_width is seg
        i0 = np.arange(nnz) * seg // block
    else:
        # a short target mode, privatized against mode 1's layout
        dims = (1000, 9184, 28818)
        i0 = rng.integers(0, dims[0], nnz)
    inds = np.stack([i0] + [rng.integers(0, d, nnz)
                            for d in dims[1:]]).astype(np.int64)
    tt = SparseTensor(inds=inds, vals=rng.standard_normal(nnz), dims=dims)
    fac = [jnp.asarray(rng.standard_normal((d, rank)).astype(np.float32))
           for d in dims]
    lay = build_layout(tt, 0 if path == "sorted_onehot" else 1,
                       block=block, val_dtype=np.float32)
    if seg is not None:
        assert lay.seg_width == seg
    assert engine_plan(lay, fac, 0, path,
                       "pallas_interpret") == "unfused_pallas"
    got = np.asarray(mttkrp_blocked(lay, fac, 0, path=path,
                                    impl="pallas_interpret"))
    gold = np.asarray(mttkrp_stream(jnp.asarray(tt.inds),
                                    jnp.asarray(tt.vals), fac, 0, dims[0]))
    err = np.abs(got - gold).max() / max(np.abs(gold).max(), 1e-9)
    assert err < 5e-5, err


#: scan steps of 7, 10 and 11 blocks: none a multiple of the kernel's
#: largest chunk (8), so each needs a kernel chunk dividing the step
_UNEVEN_STEPS = (7, 10, 11)


@pytest.mark.parametrize("engine,path", [("xla", "sorted_onehot"),
                                         ("unfused_pallas", "sorted_onehot"),
                                         ("unfused_pallas", "privatized")])
def test_scan_target_knob_changes_chunking_not_results(engine, path):
    """scan_target tunes the scan engines' step size (the hardware
    sweep knob; default from SPLATT_SCAN_TARGET_ELEMS) as a static jit
    argument — distinct values re-trace — without changing the computed
    MTTKRP.  Under the Mosaic kernels (interpret mode) the steps are
    sized to 7, 10 and 11 blocks, and no step's products are padded up
    to a larger kernel chunk."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import _mttkrp_blocked_jit, mttkrp_stream

    tt = gen.fixture_tensor("med")
    factors = make_factors(tt.dims)
    R, B = factors[0].shape[1], 128
    lay = build_layout(tt, 0 if path == "sorted_onehot" else 1, block=B,
                       val_dtype=np.float64)
    want = mttkrp_stream(jnp.asarray(tt.inds), jnp.asarray(tt.vals),
                         factors, 0, tt.dims[0])
    if engine == "xla":
        targets = (1 << 10, 1 << 16, 1 << 24)
    else:
        targets = tuple(c * R * B for c in _UNEVEN_STEPS)
    for target in targets:
        if engine == "xla":
            got = mttkrp_blocked(lay, factors, 0, path=path, impl="xla",
                                 scan_target=target)
        else:
            args = (lay, factors, 0, path, "pallas_interpret", target,
                    engine)
            hlo = _mttkrp_blocked_jit.lower(*args).as_text()
            assert not re.search(
                rf"stablehlo\.pad .*-> tensor<\d+x{B}x{R}xf64>", hlo), target
            got = _mttkrp_blocked_jit(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL, err_msg=str(target))
