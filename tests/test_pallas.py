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


def test_onehot_reduce_sorted_kernel():
    rng = np.random.default_rng(0)
    nb, B, S, R = 5, 128, 16, 8
    local = rng.integers(-1, S + 3, size=(nb, B)).astype(np.int32)
    prod = rng.random((nb, B, R))
    got = onehot_reduce_sorted(jnp.asarray(local), jnp.asarray(prod), S,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               _np_onehot_sorted(local, prod, S), atol=TOL)


def test_onehot_reduce_full_kernel():
    rng = np.random.default_rng(1)
    nb, B, W, R = 9, 128, 24, 8  # nb not divisible by the chunk size
    local = rng.integers(0, W, size=(nb, B)).astype(np.int32)
    prod = rng.random((nb, B, R))
    got = onehot_reduce_full(jnp.asarray(local), jnp.asarray(prod), W,
                             interpret=True)
    want = _np_onehot_sorted(local, prod, W).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


def test_onehot_vmem_request_counts_tile_padding():
    # rank 200 pads to 256 lanes: a (5, 4096, 200) f32 products block,
    # double-buffered, is 40 MiB on its own
    assert pk.padded_bytes((5, 4096, 200), 4) == 5 * 4096 * 256 * 4
    assert pk.padded_bytes((5, 1, 4096), 4) == 5 * 8 * 4096 * 4
    assert pk.padded_bytes((3, 16), 2) == 16 * 128 * 2
    req = pk.onehot_vmem_request(5, 8, 4096, 200, jnp.float32)
    assert req >= 2 * pk.padded_bytes((5, 4096, 200), 4) >= 40 << 20
    # ...and asks for less than the flat limit it replaces
    assert req < pk._vmem_limit()


@pytest.mark.parametrize("width,dtype", [(8, jnp.float32),
                                         (16, jnp.bfloat16)])
def test_onehot_vmem_request_grows_with_chunk_and_rank(width, dtype):
    by_chunk = [pk.onehot_vmem_request(c, width, 4096, 50, dtype)
                for c in range(1, 9)]
    assert by_chunk == sorted(set(by_chunk))
    # 50, 128, 200 and 256 lanes pad to 128, 128, 256 and 256
    by_rank = [pk.onehot_vmem_request(4, width, 4096, r, dtype)
               for r in (50, 128, 200, 256, 300)]
    assert by_rank[0] == by_rank[1] < by_rank[2] == by_rank[3] < by_rank[4]


def test_onehot_vmem_request_is_capped_at_vmem_limit(monkeypatch):
    limit = pk._vmem_limit()
    assert pk.onehot_vmem_request(8, 2048, 4096, 1024, jnp.float32) == limit
    monkeypatch.setattr(pk, "_vmem_limit", lambda: 8 << 20)
    assert pk.onehot_vmem_request(5, 8, 4096, 200, jnp.float32) == 8 << 20
    assert pk.onehot_vmem_request(1, 8, 128, 8, jnp.float32) < 8 << 20


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
    """The chip's main-path engine (scan over block chunks: XLA gather
    + Hadamard, Mosaic one-hot reduce) — sorted partials + privatized
    totals vs the numpy brute force."""
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
    """When not even one block's one-hot, products and partials fit the
    kernel's VMEM budget (vmem_chunk is 0), the chain starts at
    xla_scan, which streams the one-hot through HBM instead."""
    from types import SimpleNamespace

    from splatt_tpu.ops.mttkrp import engine_chain

    facs = [jax.ShapeDtypeStruct((d, 256), jnp.float32)
            for d in (4096, 48, 80)]
    lay = SimpleNamespace(block=16384, seg_width=4096, nnz_pad=16384)
    assert pk.vmem_chunk(4096, 16384, 256) == 0
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

    assert vmem_chunk(64, 512, 128) >= 1          # typical config fits
    assert vmem_chunk(4096, 4096, 128) == 0       # pathological: fall back
    assert 1 <= vmem_chunk(8, 128, 8) <= 8


def test_pallas_bf16_accumulates_f32():
    """bf16 inputs through the Pallas kernels produce f32 outputs that
    match the f64 brute force at bf16 tolerance."""
    rng = np.random.default_rng(3)
    nb, B, S, R = 4, 128, 16, 8
    local = rng.integers(-1, S + 2, size=(nb, B)).astype(np.int32)
    prod16 = jnp.asarray(rng.random((nb, B, R)), dtype=jnp.bfloat16)
    got = onehot_reduce_sorted(jnp.asarray(local), prod16, S, interpret=True)
    assert got.dtype == jnp.float32
    want = _np_onehot_sorted(local, np.asarray(prod16, dtype=np.float64), S)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float64), want,
                               atol=3e-2)
    got2 = onehot_reduce_full(jnp.asarray(local), prod16, S + 8,
                              interpret=True)
    assert got2.dtype == jnp.float32
    want2 = _np_onehot_sorted(local, np.asarray(prod16, dtype=np.float64),
                              S + 8).sum(axis=0)
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


@pytest.mark.parametrize("C,expect", [(10, 5), (40, 8), (81, 3), (7, 7),
                                      (11, 1), (1, 1)])
def test_dividing_chunk(C, expect):
    """The kernel chunk of a C-block scan step: the largest divisor of C
    within vmem_chunk's cap (8 at rank 200, block 4096), and 0 exactly
    where vmem_chunk is 0 (the engine does not fit; dispatch falls back
    to XLA)."""
    from splatt_tpu.ops.pallas_kernels import dividing_chunk, vmem_chunk

    assert vmem_chunk(8, 4096, 200) == 8
    got = dividing_chunk(C, 8, 4096, 200)
    assert got == expect
    assert C % got == 0 and got <= 8
    assert vmem_chunk(4096, 4096, 128) == 0
    assert dividing_chunk(C, 4096, 4096, 128) == 0
