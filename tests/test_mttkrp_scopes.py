"""The MTTKRP's observability surface (docs/observability.md).

- Tracing off costs the dispatch path nothing: ``mttkrp.dispatch``
  builds its attributes (``enc``, ``kernel_vmem``, ``tables_vmem`` and
  the rest) only when tracing is on, and a layout's format summary
  reads dtypes, never a device array.
- Every path and engine names its phases: the lowered program carries
  ``mttkrp.mode<m>`` and the ``mttkrp.operands`` / ``mttkrp.reduce`` /
  ``mttkrp.merge`` scopes in its op locations, which is what a device
  trace's ``tf_op`` paths are split by.
"""

import dataclasses
import importlib
import re

import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu import trace
from splatt_tpu.blocked import BlockedSparse, ModeLayout, build_dense_layout
from splatt_tpu.config import BlockAlloc, Options
from splatt_tpu.coo import SparseTensor
from tests import gen
from tests.test_mttkrp import make_factors

# the module (the package re-exports the function under the same name)
mk = importlib.import_module("splatt_tpu.ops.mttkrp")


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.set_enabled(None)
    trace.reset()
    yield
    trace.set_enabled(None)
    trace.reset()


def _blocked(idx_width=None):
    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   idx_width=idx_width)
    return tt, BlockedSparse.from_coo(tt, opts)


def test_dispatch_with_tracing_off_never_builds_attributes(monkeypatch):
    tt, bs = _blocked()
    lay = bs.layout_for(0)
    factors = make_factors(tt.dims)
    want = mk.mttkrp_blocked(lay, factors, 0, path="sorted_onehot")

    def refuse(self):
        raise AssertionError("format_desc ran with tracing off")

    monkeypatch.setattr(ModeLayout, "format_desc", refuse)
    assert not trace.enabled()
    got = mk.mttkrp_blocked(lay, factors, 0, path="sorted_onehot")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert trace.spans() == []
    monkeypatch.undo()

    # tracing on: the span keeps every attribute it always recorded
    trace.set_enabled(True)
    mk.mttkrp_blocked(lay, factors, 0, path="sorted_onehot")
    (span,) = trace.spans("mttkrp.dispatch")
    engine = span["args"]["engine"]
    assert span["args"] == {
        "mode": 0, "path": "sorted_onehot", "engine": engine,
        "block": lay.block, "enc": lay.format_desc(),
        **mk._vmem_attrs(lay, factors, 0, "sorted_onehot", engine,
                         mk._SCAN_TARGET)}
    assert engine in mk.engine_chain(lay, factors, 0)


@pytest.mark.parametrize("path,layout_mode", [("sorted_onehot", 0),
                                              ("privatized", 1)])
def test_dispatch_vmem_attributes_only_with_tracing_on(monkeypatch, path,
                                                       layout_mode):
    """``kernel_vmem`` is the one-hot kernel's reservation at the chunk
    the scan gives it; ``tables_vmem`` the tile-padded bytes of the
    tables the mode gathers; ``step_blocks`` the scan step's blocks;
    ``tables_hbm`` the gathered tables that do not fit VMEM beside the
    kernel (none, at this size).  None is computed with tracing off."""
    from splatt_tpu.ops import pallas_kernels as pk

    tt, bs = _blocked()
    lay = bs.layout_for(layout_mode)
    factors = make_factors(tt.dims)
    monkeypatch.setattr(mk, "engine_chain",
                        lambda *a, **k: ["unfused_pallas", "xla"])

    def refuse(*a, **k):
        raise AssertionError("a VMEM attribute was built with tracing off")

    with monkeypatch.context() as mp:
        for name in ("_kernel_vmem", "_tables_vmem", "_step_blocks",
                     "_tables_hbm"):
            mp.setattr(mk, name, refuse)
        mk.mttkrp_blocked(lay, factors, 0, path=path,
                          impl="pallas_interpret")
    assert trace.spans() == []

    trace.set_enabled(True)
    mk.mttkrp_blocked(lay, factors, 0, path=path, impl="pallas_interpret")
    (span,) = trace.spans("mttkrp.dispatch")
    R, B = factors[0].shape[1], lay.block
    width = (lay.seg_width if path == "sorted_onehot"
             else -(-(tt.dims[0] + 1) // 8) * 8)
    chunk = pk.vmem_chunk(width, B, R, 2, factors[0].dtype)
    assert span["args"]["engine"] == "unfused_pallas"
    assert span["args"]["kernel_vmem"] == pk.onehot_vmem_request(
        chunk, width, B, R, 2, factors[0].dtype) > 0
    assert span["args"]["tables_vmem"] == sum(
        -(-d // 8) * 8 * -(-R // 128) * 128 * 8 for d in tt.dims[1:])
    assert span["args"]["step_blocks"] == mk._scan_chunk(
        lay.nblocks, width, B, R, 2, factors[0].dtype, True)
    assert span["args"]["tables_hbm"] == []


class _NoIndexing:
    """A stacked v1 index array that refuses to be indexed: any slice
    of it would be a device op."""

    def __init__(self, arr):
        self.dtype, self.shape = arr.dtype, arr.shape

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, key):
        raise AssertionError("the index array was indexed")


def test_format_summary_reads_dtypes_only():
    _, bs = _blocked()
    lay = bs.layout_for(0)
    assert lay.encoding == "v1"
    blind = dataclasses.replace(lay, inds=_NoIndexing(lay.inds))
    assert blind.idx_widths() == ["i32"] * lay.nmodes
    assert blind.format_desc() == lay.format_desc() == "i32/glob/f64"
    # the v2 tuple holds one array per mode, each with its own width
    _, bs2 = _blocked(idx_width="u16")
    v2 = bs2.layout_for(0)
    assert v2.encoding == "v2"
    assert v2.idx_widths() == [
        {"uint8": "u8", "uint16": "u16"}.get(a.dtype.name, "i32")
        for a in v2.inds]


def _dense_case():
    rng = np.random.default_rng(3)
    dims = (16, 32, 32)
    lin = rng.choice(int(np.prod(dims)), size=4000, replace=False)
    inds = np.stack(np.unravel_index(lin, dims)).astype(np.int64)
    tt = SparseTensor(inds, rng.random(4000) + 0.1, dims)
    return build_dense_layout(tt, 0), make_factors(dims), 0


def _sparse_case(path, idx_width=None):
    tt, bs = _blocked(idx_width)
    factors = make_factors(tt.dims)
    if path == "privatized":
        return bs.layout_for(1), factors, 0
    return bs.layout_for(0), factors, 0


ENGINES = [("xla_scan", "xla"), ("unfused_pallas", "pallas_interpret"),
           ("xla", "xla")]
CASES = ([("sorted_onehot", e, i, None) for e, i in ENGINES]
         + [("privatized", e, i, None) for e, i in ENGINES]
         + [("scatter", "xla", "xla", None),
            ("sorted_scatter", "xla", "xla", None),
            ("sorted_onehot", "xla_scan", "xla", "u16"),
            ("sorted_onehot", "unfused_pallas", "pallas_interpret", "u16"),
            ("sorted_onehot", "xla_scan", "xla", "u8"),
            ("sorted_onehot", "unfused_pallas", "pallas_interpret", "u8"),
            ("privatized", "xla_scan", "xla", "u16"),
            ("privatized", "unfused_pallas", "pallas_interpret", "u16"),
            ("dense", "dense_xla", "xla", None),
            ("dense", "fused_dense", "pallas_interpret", None)])


@pytest.mark.parametrize("path,engine,impl,idx_width", CASES)
def test_lowered_program_names_every_phase(path, engine, impl, idx_width):
    if path == "dense":
        layout, factors, mode = _dense_case()
    else:
        layout, factors, mode = _sparse_case(path, idx_width)
    text = mk._mttkrp_blocked_jit.lower(
        layout, factors, mode=mode, path=path, impl=impl,
        scan_target=mk._SCAN_TARGET, engine=engine).as_text(
            debug_info=True)
    assert f"/mttkrp.mode{mode}/" in text
    phases = mk.PHASES
    if engine == "xla" and layout.mode != mode:
        # the segment sum is already dim rows long: no final slice, so
        # the program has no merge op to name
        phases = (mk.OPERANDS, mk.REDUCE)
    for phase in phases:
        # a scan body's locations are relative to its call site
        assert re.search(rf'["/]{re.escape(phase)}/', text), phase
    # the scopes are metadata: the program without its locations names
    # none of them
    bare = mk._mttkrp_blocked_jit.lower(
        layout, factors, mode=mode, path=path, impl=impl,
        scan_target=mk._SCAN_TARGET, engine=engine).as_text()
    assert "mttkrp.operands" not in bare
    out = mk._mttkrp_blocked_jit(layout, factors, mode, path, impl,
                                 mk._SCAN_TARGET, engine)
    assert out.shape == (factors[mode].shape[0], factors[mode].shape[1])
    assert bool(jnp.isfinite(out).all())
