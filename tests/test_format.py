"""Compact blocked format v2 (docs/format.md).

Contract under test:

- **bit parity**: the v2 encoding (local narrow indices + per-block
  bases, segment ids for the sorted mode) is a pure relabeling — every
  execution path/engine produces BIT-IDENTICAL f32 MTTKRP outputs to
  the v1 i32 layout (same gathers, same one-hot compares, same
  accumulation order);
- **fit parity**: bf16 value storage (factors in bf16, f32
  accumulation through the existing _acc_dtype path) reaches the f32
  baseline's fit-residual within bf16 tolerance on the seeded
  synthetic CPD, under the donated sweep;
- **resilient encode**: a failed v2 encode (the ``format.encode``
  fault site) degrades CLASSIFIED to v1 — a ``format_fallback``
  run-report event, never a failed build;
- **registries**: the new env vars / run-report events / fault site
  are declared (splint SPL006/SPL007/SPL012 stay at zero);
- **tuner integration**: formats are candidates, plans carry the
  encoding, and the strict match means a v2 plan never steers a v1
  layout (and demotions are scoped per encoding).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import splatt_tpu.tune as tune
from splatt_tpu import resilience
from splatt_tpu.blocked import (BlockedSparse, build_layout,
                                reencode_layout)
from splatt_tpu.config import (BlockAlloc, LayoutFormat, Options, Verbosity,
                               layout_format, resolve_storage_dtype)
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import cpd_als, init_factors
from splatt_tpu.ops.mttkrp import (_engine_shape_key, _mttkrp_blocked_jit,
                                   _tuned_plan_for, mttkrp_blocked)
from splatt_tpu.utils import faults
from tests import gen
from tests.test_cpd import lowrank_tensor


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv(tune._CACHE_ENV, str(tmp_path / "tune_cache.json"))
    tune.reset_memo()
    resilience.reset_demotions()
    resilience.run_report().clear()
    yield
    tune.reset_memo()
    resilience.reset_demotions()
    resilience.run_report().clear()
    faults.reset()


def _tensor():
    return gen.fixture_tensor("med")


def _wide_tensor():
    """One mode beyond uint16 range, so per-mode width selection is
    exercised (the sorted mode's SEGMENT ids still fit u16; the same
    mode gathered from another layout needs i32)."""
    rng = np.random.default_rng(7)
    dims = (23, 70000, 31)
    nnz = 2500
    inds = np.stack([rng.integers(0, d, nnz) for d in dims])
    return SparseTensor(inds.astype(np.int64), rng.random(nnz) + 0.1, dims)


V2 = LayoutFormat(idx="auto", val="auto")


# -- bit-parity properties ---------------------------------------------------

@pytest.mark.parametrize("tt_name", ["med", "med4", "wide"])
def test_v2_bitparity_all_paths(tt_name):
    """u16/seg layouts produce BIT-IDENTICAL f32 outputs to v1 i32 on
    every execution path (the encoding is a relabeling, not a numeric
    change)."""
    tt = _wide_tensor() if tt_name == "wide" else gen.fixture_tensor(tt_name)
    facs = init_factors(tt.dims, 5, 3, dtype=jnp.float32)
    for mode in range(tt.nmodes):
        l1 = build_layout(tt, mode, block=128, val_dtype=np.float32)
        l2 = build_layout(tt, mode, block=128, val_dtype=np.float32,
                          fmt=V2)
        assert l2.encoding == "v2" and l1.encoding == "v1"
        for path in ("sorted_onehot", "sorted_scatter"):
            a = np.asarray(mttkrp_blocked(l1, facs, mode, path=path,
                                          impl="xla"))
            b = np.asarray(mttkrp_blocked(l2, facs, mode, path=path,
                                          impl="xla"))
            np.testing.assert_array_equal(a, b, err_msg=f"{path}/{mode}")
        other = (mode + 1) % tt.nmodes
        a = np.asarray(mttkrp_blocked(l1, facs, other, path="scatter",
                                      impl="xla"))
        b = np.asarray(mttkrp_blocked(l2, facs, other, path="scatter",
                                      impl="xla"))
        np.testing.assert_array_equal(a, b)


def test_v2_bitparity_forced_engines():
    """The xla_scan engine (per-chunk decode inside the scan) and the
    interpret-mode Pallas engines agree bit-for-bit across encodings."""
    tt = _tensor()
    facs = init_factors(tt.dims, 4, 1, dtype=jnp.float32)
    for mode in range(tt.nmodes):
        l1 = build_layout(tt, mode, block=128, val_dtype=np.float32)
        l2 = build_layout(tt, mode, block=128, val_dtype=np.float32,
                          fmt=V2)
        for engine, impl in (("xla_scan", "xla"),
                             ("fused_t", "pallas_interpret"),
                             ("fused_tg", "pallas_interpret"),
                             ("unfused_pallas", "pallas_interpret")):
            a = np.asarray(_mttkrp_blocked_jit(l1, facs, mode,
                                               "sorted_onehot", impl,
                                               1 << 21, engine))
            b = np.asarray(_mttkrp_blocked_jit(l2, facs, mode,
                                               "sorted_onehot", impl,
                                               1 << 21, engine))
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{engine}/{mode}")
        # privatized (global-width accumulate) via the scan engine
        other = (mode + 1) % tt.nmodes
        a = np.asarray(_mttkrp_blocked_jit(l1, facs, other, "privatized",
                                           "xla", 1 << 21, "xla_scan"))
        b = np.asarray(_mttkrp_blocked_jit(l2, facs, other, "privatized",
                                           "xla", 1 << 21, "xla_scan"))
        np.testing.assert_array_equal(a, b)


def test_v2_cpd_bitparity_and_donation():
    """End to end: a full CPD over v2 layouts equals the v1 run bit for
    bit, donated sweep on or off (SPL008 era: v2 decode is trace-safe
    under donation)."""
    tt = _tensor()
    init = init_factors(tt.dims, 3, 11, dtype=jnp.float32)
    outs = {}
    for name, fmt_kw in (("v1", {}),
                         ("v2", dict(idx_width="auto")),
                         ("v2_nodonate", dict(idx_width="auto",
                                              donate_sweep=False))):
        opts = Options(random_seed=42, max_iterations=5,
                       verbosity=Verbosity.NONE, use_pallas=False,
                       autotune=False, nnz_block=256,
                       block_alloc=BlockAlloc.ALLMODE, **fmt_kw)
        outs[name] = cpd_als(BlockedSparse.from_coo(tt, opts), 3,
                             opts=opts, init=init)
    assert float(outs["v1"].fit) == float(outs["v2"].fit)
    assert float(outs["v2"].fit) == float(outs["v2_nodonate"].fit)
    for ua, ub in zip(outs["v1"].factors, outs["v2"].factors):
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))
    # the caller's init survives the donated v2 run
    assert not any(u.is_deleted() for u in init)


def test_empty_tensor_v2_layout():
    """nnz=0: all-pad blocks carry the sentinel in the BASE, locals
    stay zero, and the layout still dispatches."""
    tt = SparseTensor(inds=np.zeros((3, 0), dtype=np.int64),
                      vals=np.zeros(0), dims=(5, 6, 7))
    lay = build_layout(tt, 0, block=128, val_dtype=np.float32, fmt=V2)
    assert lay.encoding == "v2" and lay.nnz == 0
    assert int(np.asarray(lay.mode_ids(0)).min()) == 5  # decoded sentinel
    facs = init_factors(tt.dims, 2, 0, dtype=jnp.float32)
    out = np.asarray(mttkrp_blocked(lay, facs, 0, path="sorted_onehot",
                                    impl="xla"))
    np.testing.assert_array_equal(out, np.zeros((5, 2), dtype=np.float32))


# -- encoded structure / reporting ------------------------------------------

def test_widths_and_storage_bytes_shrink():
    """The encoded layout really is narrower: u16 streams where the
    extent fits, i32 where it does not — and storage_bytes reports the
    ENCODED bytes (what bench's bytes/iteration model reads)."""
    tt = _wide_tensor()
    l1 = build_layout(tt, 0, block=128, val_dtype=np.float32)
    l2 = build_layout(tt, 0, block=128, val_dtype=np.float32, fmt=V2)
    widths = l2.idx_widths()
    assert widths[0] == "u16"          # sorted mode: segment ids
    assert widths[1] == "i32"          # 70000-wide gather mode
    assert widths[2] == "u16"
    assert l2.storage_bytes() < l1.storage_bytes()
    # bf16 storage halves the value stream on top
    l3 = build_layout(tt, 0, block=128, val_dtype=jnp.bfloat16,
                      fmt=LayoutFormat(idx="auto", val="bf16"))
    assert l3.vals.dtype == jnp.bfloat16
    assert l3.storage_bytes() < l2.storage_bytes()
    assert "seg" in l2.format_desc() and "bf16" in l3.format_desc()
    # the repr distinguishes the encodings (demotion/tune log lines)
    assert "enc=v2" in repr(l2) and "enc=v1" in repr(l1)


def test_reencode_matches_direct_build():
    """reencode_layout (the tuner's no-resort derivation) produces the
    same encoded streams as building at the format directly."""
    tt = _tensor()
    direct = build_layout(tt, 1, block=256, val_dtype=np.float32, fmt=V2)
    re = reencode_layout(build_layout(tt, 1, block=256,
                                      val_dtype=np.float32), V2)
    assert re.encoding == "v2"
    for k in range(tt.nmodes):
        np.testing.assert_array_equal(np.asarray(direct.inds[k]),
                                      np.asarray(re.inds[k]))
        np.testing.assert_array_equal(np.asarray(direct.base[k]),
                                      np.asarray(re.base[k]))


def test_format_v2_event_and_summary():
    """from_coo at a non-default format records the achieved encoding
    (format_v2 event) — silent formats would be as unobservable as the
    silent engine fallback."""
    tt = _tensor()
    opts = Options(verbosity=Verbosity.NONE, idx_width="auto",
                   block_alloc=BlockAlloc.ALLMODE, use_pallas=False)
    bs = BlockedSparse.from_coo(tt, opts)
    evs = resilience.run_report().events("format_v2")
    assert evs and all("seg" in d for d in evs[-1]["modes"].values())
    assert "mode0=" in bs.format_summary()


def test_block_clamp_event_carries_format():
    """The clamp event names the requested format, so clamp/tune log
    lines distinguish v1 from v2 plans (ISSUE 7 satellite)."""
    tt = _tensor()
    build_layout(tt, 0, block=1 << 20, val_dtype=np.float32, fmt=V2)
    ev = resilience.run_report().events("block_clamp")[-1]
    assert ev["idx_width"] == "auto" and "val_storage" in ev


# -- bf16 fit parity ---------------------------------------------------------

def test_bf16_storage_fit_parity():
    """bf16 value storage (factors bf16, f32 accumulation) reaches
    fit-residual parity with the f32/i32 baseline within bf16
    tolerance on the seeded synthetic CPD — the 'correct' half of the
    cheapest-correct-format contract."""
    tt = lowrank_tensor((15, 12, 10), rank=3)
    fits = {}
    for name, kw in (("f32", {}),
                     ("bf16", dict(idx_width="auto", val_storage="bf16"))):
        opts = Options(random_seed=42, max_iterations=40, tolerance=1e-7,
                       verbosity=Verbosity.NONE, use_pallas=False,
                       autotune=False, block_alloc=BlockAlloc.ALLMODE,
                       **kw)
        out = cpd_als(BlockedSparse.from_coo(tt, opts), 5, opts=opts)
        fits[name] = float(out.fit)
    assert fits["bf16"] > 0.97
    assert abs(fits["bf16"] - fits["f32"]) < 0.03


# -- resilient encode (the format.encode fault site) ------------------------

def test_encode_fault_degrades_to_v1():
    """Chaos drill: a raised fault at format.encode degrades the build
    CLASSIFIED to v1 — format_fallback event, never a failed build."""
    tt = _tensor()
    with faults.inject("format.encode", "runtime", times=1):
        lay = build_layout(tt, 0, block=128, val_dtype=np.float32,
                           fmt=V2)
    assert lay.encoding == "v1"          # degraded, not dead
    evs = resilience.run_report().events("format_fallback")
    assert evs and evs[-1]["failure_class"]
    assert any("compact-format encode failed" in ln
               for ln in resilience.run_report().summary())
    # and the degraded layout still computes
    facs = init_factors(tt.dims, 3, 0, dtype=jnp.float32)
    ref = np.asarray(mttkrp_blocked(
        build_layout(tt, 0, block=128, val_dtype=np.float32), facs, 0,
        path="sorted_onehot", impl="xla"))
    got = np.asarray(mttkrp_blocked(lay, facs, 0, path="sorted_onehot",
                                    impl="xla"))
    np.testing.assert_array_equal(got, ref)


def test_forced_u16_overflow_degrades_classified():
    """idx_width=u16 on a mode whose per-block extent cannot fit is an
    encode failure: degraded to v1 with a classified event (the build
    survives; the caller sees why the format is not what was asked)."""
    tt = _wide_tensor()
    lay = build_layout(tt, 0, block=128, val_dtype=np.float32,
                       fmt=LayoutFormat(idx="u16", val="auto"))
    assert lay.encoding == "v1"
    evs = resilience.run_report().events("format_fallback")
    assert evs and evs[-1]["idx_width"] == "u16"


def test_from_coo_survives_encode_fault():
    """The whole-tensor build under an always-armed encode fault: every
    layout degrades to v1, the tensor still factorizes."""
    tt = _tensor()
    opts = Options(verbosity=Verbosity.NONE, idx_width="auto",
                   use_pallas=False, autotune=False,
                   block_alloc=BlockAlloc.ALLMODE, random_seed=1,
                   max_iterations=2)
    with faults.inject("format.encode", "runtime", times=99):
        bs = BlockedSparse.from_coo(tt, opts)
    assert all(l.encoding == "v1" for l in bs.layouts)
    out = cpd_als(bs, 3, opts=opts)
    assert np.isfinite(float(out.fit))


# -- registries (SPL006/SPL007/SPL012 companions) ---------------------------

def test_registries_declare_format_knobs():
    from splatt_tpu.resilience import RUN_REPORT_EVENTS
    from splatt_tpu.utils.env import ENV_VARS
    from splatt_tpu.utils.faults import SITES

    assert "SPLATT_IDX_WIDTH" in ENV_VARS
    assert "SPLATT_VAL_STORAGE" in ENV_VARS
    assert "format_v2" in RUN_REPORT_EVENTS
    assert "format_fallback" in RUN_REPORT_EVENTS
    assert "format.encode" in SITES


def test_env_defaults_resolve():
    """The env defaults are the conservative v1 format; Options pins
    win over them."""
    fmt = layout_format(Options())
    assert fmt == LayoutFormat(idx="i32", val="auto")
    fmt = layout_format(Options(idx_width="auto", val_storage="bf16"))
    assert fmt.v2 and fmt.val == "bf16"
    assert resolve_storage_dtype("bf16", jnp.float32) == jnp.bfloat16
    with pytest.raises(ValueError):
        Options(idx_width="nope").validate()
    with pytest.raises(ValueError):
        Options(val_storage="f8").validate()


# -- tuner integration -------------------------------------------------------

def test_tuner_measures_format_candidates():
    """The candidate matrix spans encodings: with nothing pinned, both
    index widths are measured and the winning plan carries its
    format."""
    tt = _tensor()
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float64, use_pallas=False)
    seen = []
    real = tune._measure_candidate

    def recording(layout, factors, mode, path, impl, engine, st, **kw):
        seen.append((layout.idx_width, layout.val_storage))
        return real(layout, factors, mode, path, impl, engine, st, **kw)

    orig = tune._measure_candidate
    tune._measure_candidate = recording
    try:
        res = tune.tune(tt, 3, opts=opts, blocks=(512,),
                        scan_targets=(1 << 21,), reps=1)
    finally:
        tune._measure_candidate = orig
    assert {"i32", "auto"} <= {iw for iw, _ in seen}
    # the winner is whichever measured candidate timed fastest — any
    # member of the matrix is legitimate, the plan just has to carry it
    assert res.plans and all(p.idx_width in tune.IDX_CANDIDATES
                             for p in res.plans.values())


def test_pinned_format_measures_only_that():
    """A pinned Options.idx_width/val_storage narrows the candidate
    matrix to exactly that format."""
    tt = _tensor()
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float64, use_pallas=False,
                   idx_width="auto", val_storage="auto")
    seen = set()
    real = tune._measure_candidate

    def recording(layout, factors, mode, path, impl, engine, st, **kw):
        seen.add((layout.idx_width, layout.val_storage))
        return real(layout, factors, mode, path, impl, engine, st, **kw)

    orig = tune._measure_candidate
    tune._measure_candidate = recording
    try:
        tune.tune(tt, 3, opts=opts, modes=(0,), blocks=(512,),
                  scan_targets=(1 << 21,), reps=1)
    finally:
        tune._measure_candidate = orig
    assert seen == {("auto", "auto")}


def test_v2_plan_never_steers_v1_layout():
    """Strict plan match: a plan measured for the v2 encoding does not
    apply to a v1 layout (and vice versa) — the tuner can make
    dispatch faster, never wronger."""
    tt = _tensor()
    lay_v1 = build_layout(tt, 0, block=512, val_dtype=np.float64)
    lay_v2 = build_layout(tt, 0, block=512, val_dtype=np.float64, fmt=V2)
    facs = init_factors(tt.dims, 4, 0, dtype=jnp.float64)
    plan = tune.TunedPlan(path="sorted_scatter", engine="xla",
                          nnz_block=512, scan_target=1 << 21, sec=0.001,
                          idx_width="auto", val_storage="auto")
    tune._entry_store(tune.plan_key(tt.dims, tt.nnz, 0, 4, jnp.float64,
                                    skew=tune.skew_of(tt, 0)),
                      {"plan": dataclasses.asdict(plan)})
    assert _tuned_plan_for(lay_v2, facs, 0, "sorted_scatter",
                           autotune=True) is not None
    assert _tuned_plan_for(lay_v1, facs, 0, "sorted_scatter",
                           autotune=True) is None


def test_v2_demotion_scoped_away_from_v1():
    """An engine demoted under the v2 encoding keeps running for v1:
    the shape key carries the encoding (a v2 OOM demotes the v2 plan,
    never the v1 path)."""
    tt = _tensor()
    lay_v1 = build_layout(tt, 0, block=512, val_dtype=np.float64)
    lay_v2 = build_layout(tt, 0, block=512, val_dtype=np.float64, fmt=V2)
    facs = init_factors(tt.dims, 4, 0, dtype=jnp.float64)
    k1 = _engine_shape_key(lay_v1, facs, 0)
    k2 = _engine_shape_key(lay_v2, facs, 0)
    assert k1 != k2 and k2.endswith(":v2") and ":v2" not in k1
    resilience.demote_engine("xla_scan", MemoryError("injected v2 OOM"),
                             shape_key=k2)
    assert resilience.is_demoted("xla_scan", k2)
    assert not resilience.is_demoted("xla_scan", k1)


def test_compile_builds_layouts_at_tuned_format():
    """BlockedSparse.compile applies the plan's encoding, and a
    bf16-storage winner is aliased under the storage dtype's key so
    dispatch steering survives the factor-dtype change."""
    tt = _tensor()
    plan = tune.TunedPlan(path="sorted_scatter", engine="xla",
                          nnz_block=512, scan_target=1 << 23, sec=0.001,
                          idx_width="auto", val_storage="bf16")
    for m in range(tt.nmodes):
        tune._entry_store(
            tune.plan_key(tt.dims, tt.nnz, m, 4, jnp.float32,
                          skew=tune.skew_of(tt, m)),
            {"plan": dataclasses.asdict(plan)})
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float32, use_pallas=False, autotune=True)
    bs = BlockedSparse.compile(tt, opts, rank=4)
    assert all(l.block == 512 and l.encoding == "v2"
               and l.val_storage == "bf16" for l in bs.layouts)
    assert bs.layouts[0].vals.dtype == jnp.bfloat16
    # dispatch with bf16 factors (what cpd_als will derive) matches the
    # plan through the storage-dtype key the tuner aliases
    out = cpd_als(bs, 4, opts=Options(random_seed=42, max_iterations=2,
                                      verbosity=Verbosity.NONE,
                                      use_pallas=False, autotune=True))
    assert out.factors[0].dtype == jnp.bfloat16
    assert np.isfinite(float(out.fit))


def test_mixed_storage_verdicts_drop_plan_whole():
    """Non-unanimous per-mode storage verdicts: the modes whose plan
    cannot follow the resolved whole-tensor policy drop their tuned
    block/format WHOLE (a half-applied plan would build a never-
    measured configuration dispatch silently rejects) — recorded as
    tuner_degraded, and the layouts stay at the default format."""
    tt = _tensor()
    mk = dict(path="sorted_scatter", engine="xla", scan_target=1 << 23,
              sec=0.001)
    plans = {0: tune.TunedPlan(nnz_block=512, idx_width="auto",
                               val_storage="bf16", **mk),
             1: tune.TunedPlan(nnz_block=1024, idx_width="i32",
                               val_storage="auto", **mk),
             2: tune.TunedPlan(nnz_block=1024, idx_width="i32",
                               val_storage="auto", **mk)}
    for m, p in plans.items():
        tune._entry_store(tune.plan_key(tt.dims, tt.nnz, m, 4,
                                        jnp.float32,
                                        skew=tune.skew_of(tt, m)),
                          {"plan": dataclasses.asdict(p)})
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float32, use_pallas=False, autotune=True,
                   block_alloc=BlockAlloc.ALLMODE)
    bs = BlockedSparse.compile(tt, opts, rank=4)
    # verdicts {bf16, auto} are not unanimous: storage stays "auto",
    # mode 0's bf16 plan is dropped whole (default block, v1 encoding)
    lay0 = bs.layout_for(0)
    assert lay0.encoding == "v1" and lay0.block != 512
    assert bs.layouts[0].vals.dtype == jnp.float32
    # the majority plans still apply
    assert bs.layout_for(1).block == 1024
    evs = resilience.run_report().events("tuner_degraded")
    assert evs and evs[-1]["reason"]
    assert any("could not apply" in ln
               for ln in resilience.run_report().summary())


def test_tuner_bf16_alias_key_written():
    """A bf16-storage winner lands under BOTH the requested-dtype key
    and the bf16 key (dispatch-time steering)."""
    tt = _tensor()
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float32, use_pallas=False,
                   idx_width="auto", val_storage="bf16")
    res = tune.tune(tt, 3, opts=opts, modes=(0,), blocks=(512,),
                    scan_targets=(1 << 21,), reps=1)
    assert res.plans[0].val_storage == "bf16"
    assert tune.cached_plan(tt.dims, tt.nnz, 0, 3, jnp.float32,
                            skew=tune.skew_of(tt, 0)) is not None
    assert tune.cached_plan(tt.dims, tt.nnz, 0, 3, jnp.bfloat16,
                            skew=tune.skew_of(tt, 0)) is not None


# -- u8 segment-id streams (ISSUE 8 satellite, ROADMAP open item 2) ----------


def test_u8_segment_stream_bit_parity_all_engines():
    """idx_width="u8" narrows the sorted mode's segment ids to uint8 —
    a pure relabeling: bit-identical MTTKRP on every engine family."""
    from splatt_tpu.config import LayoutFormat as LF

    tt = _tensor()
    facs = [jnp.asarray(f)
            for f in init_factors(tt.dims, 5, 0, dtype=jnp.float64)]
    v1 = build_layout(tt, 0, block=256, val_dtype=np.float64)
    u8 = build_layout(tt, 0, block=256, val_dtype=np.float64,
                      fmt=LF(idx="u8"))
    assert u8.encoding == "v2"
    assert u8.idx_widths()[0] == "u8"          # the segment stream
    assert u8.inds[0].dtype == jnp.uint8
    assert "u8" in u8.format_desc() and "/seg/" in u8.format_desc()
    assert u8.storage_bytes() < v1.storage_bytes()
    for path in ("sorted_onehot", "sorted_scatter", "scatter"):
        a = mttkrp_blocked(v1, facs, 0, path=path, impl="xla")
        b = mttkrp_blocked(u8, facs, 0, path=path, impl="xla")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for impl, engine in (("xla", "xla_scan"), ("xla", "xla"),
                         ("pallas_interpret", "unfused_pallas")):
        a = _mttkrp_blocked_jit(v1, facs, 0, "sorted_onehot", impl,
                                1 << 21, engine)
        b = _mttkrp_blocked_jit(u8, facs, 0, "sorted_onehot", impl,
                                1 << 21, engine)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_u8_overflow_degrades_classified_to_v1():
    """A block span > 255 under a forced u8 is an encode failure —
    degraded CLASSIFIED to v1 (format_fallback event), never a crash;
    "auto" keeps its u16/i32 widths for the same tensor."""
    from splatt_tpu.config import LayoutFormat as LF

    inds = np.stack([np.arange(1000)] * 3)
    diag = SparseTensor(inds, np.ones(1000), (1000, 1000, 1000))
    lay = build_layout(diag, 0, block=1024, val_dtype=np.float64,
                       fmt=LF(idx="u8"))
    assert lay.encoding == "v1"
    evs = resilience.run_report().events("format_fallback")
    assert evs and evs[-1]["idx_width"] == "u8"
    auto = build_layout(diag, 0, block=1024, val_dtype=np.float64,
                        fmt=LF(idx="auto"))
    assert auto.encoding == "v2" and auto.idx_widths()[0] == "u16"


def test_u8_reencode_and_plan_match():
    """reencode_layout derives the u8 candidate without re-sorting, the
    requested policy is part of the strict plan match, and the shape
    key stays v2-scoped."""
    from splatt_tpu.config import LayoutFormat as LF

    tt = _tensor()
    facs = [jnp.asarray(f)
            for f in init_factors(tt.dims, 5, 0, dtype=jnp.float64)]
    v1 = build_layout(tt, 0, block=256, val_dtype=np.float64)
    u8 = reencode_layout(v1, LF(idx="u8"))
    assert u8.encoding == "v2" and u8.idx_width == "u8"
    assert u8.inds[0].dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(mttkrp_blocked(v1, facs, 0, path="sorted_onehot",
                                  impl="xla")),
        np.asarray(mttkrp_blocked(u8, facs, 0, path="sorted_onehot",
                                  impl="xla")))
    # strict match: a u8 plan never steers an "auto" layout
    mk = dict(path="sorted_onehot", engine="xla", scan_target=1 << 21,
              sec=0.001)
    plan = tune.TunedPlan(nnz_block=256, idx_width="u8",
                          val_storage="auto", **mk)
    auto = reencode_layout(v1, LF(idx="auto"))
    assert _engine_shape_key(u8, facs, 0).endswith(":v2")
    tune._entry_store(tune.plan_key(tt.dims, tt.nnz, 0, 5, jnp.float64,
                                    skew=tune.skew_of(tt, 0)),
                      {"plan": dataclasses.asdict(plan)})
    assert _tuned_plan_for(u8, facs, 0, "sorted_onehot",
                           autotune=True) is not None
    assert _tuned_plan_for(auto, facs, 0, "sorted_onehot",
                           autotune=True) is None


def test_u8_tuner_candidate_and_compile():
    """"u8" sits in the unpinned candidate matrix, a pinned u8 tune
    stores a u8 plan, and BlockedSparse.compile builds at it."""
    assert "u8" in tune.IDX_CANDIDATES
    tt = _tensor()
    opts = Options(random_seed=42, verbosity=Verbosity.NONE,
                   val_dtype=np.float64, use_pallas=False,
                   idx_width="u8", val_storage="auto")
    res = tune.tune(tt, 3, opts=opts, modes=(0,), blocks=(256,),
                    scan_targets=(1 << 21,), reps=1)
    assert res.plans[0].idx_width == "u8"
    bs = BlockedSparse.compile(tt, Options(
        random_seed=42, verbosity=Verbosity.NONE, val_dtype=np.float64,
        use_pallas=False, autotune=True, block_alloc=BlockAlloc.ALLMODE),
        rank=3)
    lay = bs.layout_for(0)
    assert lay.idx_width == "u8" and lay.inds[0].dtype == jnp.uint8
    evs = resilience.run_report().events("format_v2")
    assert evs and "u8" in evs[-1]["modes"]["0"]


def test_u8_registry_and_validation():
    from splatt_tpu.config import IDX_WIDTHS

    assert "u8" in IDX_WIDTHS
    Options(idx_width="u8").validate()
    from splatt_tpu.utils.env import ENV_VARS

    assert "u8" in ENV_VARS["SPLATT_IDX_WIDTH"].doc


# -- decode placement + the delta/RLE catalog (ISSUE 13) ---------------------

ALL_V2 = ("auto", "u8", "delta", "rle")


def _enc_layouts(tt, mode, block=128, dtype=np.float32):
    v1 = build_layout(tt, mode, block=block, val_dtype=dtype)
    encoded = {idx: build_layout(tt, mode, block=block, val_dtype=dtype,
                                 fmt=LayoutFormat(idx=idx))
               for idx in ALL_V2}
    return v1, encoded


@pytest.mark.parametrize("idx", ["delta", "rle"])
def test_delta_rle_bitparity_all_paths(idx):
    """The delta and RLE catalog entries are pure relabelings: BIT-
    IDENTICAL f32 MTTKRP to the v1 layout on every path and the
    forced xla_scan per-chunk decode, for every mode."""
    tt = _tensor()
    facs = init_factors(tt.dims, 5, 3, dtype=jnp.float32)
    for mode in range(tt.nmodes):
        l1 = build_layout(tt, mode, block=128, val_dtype=np.float32)
        l2 = build_layout(tt, mode, block=128, val_dtype=np.float32,
                          fmt=LayoutFormat(idx=idx))
        assert l2.encoding == "v2" and l2.idx_width == idx
        for path in ("sorted_onehot", "sorted_scatter"):
            a = np.asarray(mttkrp_blocked(l1, facs, mode, path=path,
                                          impl="xla"))
            b = np.asarray(mttkrp_blocked(l2, facs, mode, path=path,
                                          impl="xla"))
            np.testing.assert_array_equal(a, b, err_msg=f"{path}/{mode}")
        other = (mode + 1) % tt.nmodes
        for eng in ("xla", "xla_scan"):
            a = np.asarray(_mttkrp_blocked_jit(l1, facs, other, "scatter"
                                               if eng == "xla"
                                               else "privatized",
                                               "xla", 1 << 21, eng))
            b = np.asarray(_mttkrp_blocked_jit(l2, facs, other, "scatter"
                                               if eng == "xla"
                                               else "privatized",
                                               "xla", 1 << 21, eng))
            np.testing.assert_array_equal(a, b, err_msg=f"{eng}/{other}")


def test_decode_prep_lever(monkeypatch):
    """SPLATT_DECODE=prep (the operand-prep A/B lever) materializes the
    decoded v1 form up front for every engine and stays bit-identical;
    an unknown policy fails with one clear message."""
    tt = _tensor()
    facs = init_factors(tt.dims, 4, 0, dtype=jnp.float32)
    v1, encoded = _enc_layouts(tt, 0)
    ref = np.asarray(mttkrp_blocked(v1, facs, 0, path="sorted_onehot",
                                    impl="xla"))
    monkeypatch.setenv("SPLATT_DECODE", "prep")
    for idx, lay in encoded.items():
        got = np.asarray(mttkrp_blocked(lay, facs, 0,
                                        path="sorted_onehot", impl="xla"))
        np.testing.assert_array_equal(ref, got, err_msg=idx)
    monkeypatch.setenv("SPLATT_DECODE", "nope")
    with pytest.raises(ValueError, match="SPLATT_DECODE"):
        mttkrp_blocked(encoded["auto"], facs, 0, path="sorted_onehot",
                       impl="xla")


def test_decode_fault_degrades_to_v1_path():
    """Chaos drill for the format.decode site: a decode failure at
    dispatch degrades CLASSIFIED to the materialized v1 path —
    format_fallback evidence with site=decode, bit-identical result,
    never a failed run; the next dispatch is native again."""
    tt = _tensor()
    facs = init_factors(tt.dims, 3, 0, dtype=jnp.float32)
    v1 = build_layout(tt, 0, block=128, val_dtype=np.float32)
    l2 = build_layout(tt, 0, block=128, val_dtype=np.float32, fmt=V2)
    ref = np.asarray(mttkrp_blocked(v1, facs, 0, path="sorted_onehot",
                                    impl="xla"))
    with faults.inject("format.decode", "runtime", times=1):
        got = np.asarray(mttkrp_blocked(l2, facs, 0,
                                        path="sorted_onehot", impl="xla"))
    np.testing.assert_array_equal(ref, got)
    evs = resilience.run_report().events("format_fallback")
    assert evs and evs[-1]["site"] == "decode" and evs[-1]["failure_class"]
    assert any("decode failed at" in ln.replace("\n", " ") or
               "decode failed" in ln
               for ln in resilience.run_report().summary())
    # fault exhausted: native consumption again, same bits
    got2 = np.asarray(mttkrp_blocked(l2, facs, 0, path="sorted_onehot",
                                     impl="xla"))
    np.testing.assert_array_equal(ref, got2)


def test_decode_to_v1_matches_streams():
    """decode_to_v1 (the degrade target) reproduces every mode's
    global ids exactly, for every catalog encoding."""
    from splatt_tpu.blocked import decode_to_v1

    tt = _tensor()
    _, encoded = _enc_layouts(tt, 1, block=256)
    for idx, lay in encoded.items():
        dv = decode_to_v1(lay)
        assert dv.encoding == "v1" and dv.idx_width == "i32"
        for k in range(tt.nmodes):
            np.testing.assert_array_equal(
                np.asarray(lay.mode_ids(k)), np.asarray(dv.mode_ids(k)),
                err_msg=f"{idx}/mode{k}")


def test_rle_inverted_compression_degrades_classified():
    """A layout whose seg_width exceeds its block would make the RLE
    counts BIGGER than the raw stream: encode error, degraded
    classified to v1 (format_fallback), never a crash."""
    inds = np.stack([np.arange(1000) * 2 % 2000,
                     np.arange(1000) % 7, np.arange(1000) % 5])
    inds[0].sort()
    tt = SparseTensor(inds.astype(np.int64), np.ones(1000),
                      (2000, 7, 5))
    lay = build_layout(tt, 0, block=128, val_dtype=np.float32,
                       fmt=LayoutFormat(idx="rle"))
    assert lay.encoding == "v1"
    evs = resilience.run_report().events("format_fallback")
    assert evs and evs[-1]["idx_width"] == "rle"


def test_delta_narrows_below_auto():
    """On per-block index runs that fit i8 deltas, the delta streams
    really are narrower than the auto u16 encoding — and still decode
    bit-exactly (covered by the parity tests above)."""
    tt = _tensor()
    auto = build_layout(tt, 0, block=128, val_dtype=np.float32, fmt=V2)
    delta = build_layout(tt, 0, block=128, val_dtype=np.float32,
                         fmt=LayoutFormat(idx="delta"))
    assert delta.idx_width == "delta"
    widths = delta.idx_widths()
    assert any(w == "i8" for w in widths), widths
    assert delta.storage_bytes() < auto.storage_bytes()
    assert "dlt" in delta.format_desc()


def test_rle_counts_shape_and_shrink():
    """The RLE sorted-mode stream is a per-block (seg_width,) count
    vector — fewer bytes than the per-nnz u16 stream on dense-ish
    blocks — and rle_expand round-trips it exactly."""
    from splatt_tpu.blocked import rle_expand

    tt = _tensor()
    auto = build_layout(tt, 0, block=256, val_dtype=np.float32, fmt=V2)
    rle = build_layout(tt, 0, block=256, val_dtype=np.float32,
                       fmt=LayoutFormat(idx="rle"))
    assert rle.inds[0].shape == (rle.nblocks, rle.seg_width)
    assert rle.storage_bytes() < auto.storage_bytes()
    np.testing.assert_array_equal(
        np.asarray(rle_expand(jnp.asarray(rle.inds[0]), rle.block)),
        np.asarray(auto.blocked_locals()))


def test_format_decode_event_names_strategy():
    """The first dispatch over a compact layout records WHERE decode
    ran: 'kernel' for the stream-native engines, 'prep' for the
    fused_t family (docs/format.md)."""
    from splatt_tpu.ops.mttkrp import _DEADLINE_ARMED

    tt = _tensor()
    l2 = build_layout(tt, 0, block=128, val_dtype=np.float32, fmt=V2)
    facs = init_factors(tt.dims, 3, 0, dtype=jnp.float32)
    _DEADLINE_ARMED.clear()
    mttkrp_blocked(l2, facs, 0, path="sorted_onehot", impl="xla")
    evs = resilience.run_report().events("format_decode")
    assert evs and evs[-1]["strategy"] == "kernel"
    assert "seg" in evs[-1]["enc"]
    n = len(evs)
    # warm dispatch: no second event for the same (engine, shape)
    mttkrp_blocked(l2, facs, 0, path="sorted_onehot", impl="xla")
    assert len(resilience.run_report().events("format_decode")) == n
    # the interpret-Pallas chain heads with fused_t, which decodes at
    # operand prep
    _DEADLINE_ARMED.clear()
    mttkrp_blocked(l2, facs, 0, path="sorted_onehot",
                   impl="pallas_interpret")
    evs = resilience.run_report().events("format_decode")
    assert evs[-1]["engine"] == "fused_t"
    assert evs[-1]["strategy"] == "prep"


def test_delta_rle_cpd_bitparity_under_donation():
    """End to end: CPD over delta and RLE layouts equals the v1 run
    bit for bit under the donated sweep — in-kernel/per-chunk decode
    is trace- and donation-safe."""
    tt = _tensor()
    init = init_factors(tt.dims, 3, 11, dtype=jnp.float32)
    fits = {}
    for name, kw in (("v1", {}), ("delta", dict(idx_width="delta")),
                     ("rle", dict(idx_width="rle"))):
        opts = Options(random_seed=42, max_iterations=4,
                       verbosity=Verbosity.NONE, use_pallas=False,
                       autotune=False, nnz_block=256,
                       block_alloc=BlockAlloc.ALLMODE, **kw)
        out = cpd_als(BlockedSparse.from_coo(tt, opts), 3, opts=opts,
                      init=init)
        fits[name] = (float(out.fit),
                      [np.asarray(u) for u in out.factors])
    assert fits["v1"][0] == fits["delta"][0] == fits["rle"][0]
    for name in ("delta", "rle"):
        for ua, ub in zip(fits["v1"][1], fits[name][1]):
            np.testing.assert_array_equal(ua, ub, err_msg=name)
    assert not any(u.is_deleted() for u in init)


def test_delta_rle_strict_plan_match_and_scope():
    """Plans carry the delta/RLE policy and the match stays strict —
    a delta plan never steers an RLE (or auto) layout; all compact
    encodings share the :v2 demotion scope suffix."""
    tt = _tensor()
    facs = init_factors(tt.dims, 4, 0, dtype=jnp.float64)
    lays = {idx: build_layout(tt, 0, block=512, val_dtype=np.float64,
                              fmt=LayoutFormat(idx=idx))
            for idx in ("auto", "delta", "rle")}
    plan = tune.TunedPlan(path="sorted_scatter", engine="xla",
                          nnz_block=512, scan_target=1 << 21, sec=0.001,
                          idx_width="delta", val_storage="auto")
    tune._entry_store(tune.plan_key(tt.dims, tt.nnz, 0, 4, jnp.float64,
                                    skew=tune.skew_of(tt, 0)),
                      {"plan": dataclasses.asdict(plan)})
    assert _tuned_plan_for(lays["delta"], facs, 0, "sorted_scatter",
                           autotune=True) is not None
    for idx in ("auto", "rle"):
        assert _tuned_plan_for(lays[idx], facs, 0, "sorted_scatter",
                               autotune=True) is None, idx
    for idx in ("delta", "rle"):
        assert _engine_shape_key(lays[idx], facs, 0).endswith(":v2")
    assert "delta" in tune.IDX_CANDIDATES
    assert "rle" in tune.IDX_CANDIDATES
    assert tune.PLAN_CACHE_VERSION >= 4


def test_decode_bytes_model():
    """bench_algs.mttkrp_decode_bytes: zero for v1 layouts and the
    stream-native engines; positive (the re-widened i32 streams +
    request tiles) for the prep-decoding kernels over compact
    layouts — what bench's decode_overhead ratio reads."""
    from splatt_tpu.bench_algs import mttkrp_bytes_encoded, \
        mttkrp_decode_bytes
    from splatt_tpu.ops.mttkrp import STREAM_NATIVE_ENGINES

    assert set(STREAM_NATIVE_ENGINES) == {"xla_scan", "xla"}
    tt = _tensor()
    opts_v1 = Options(verbosity=Verbosity.NONE, use_pallas=False,
                      autotune=False, block_alloc=BlockAlloc.ALLMODE)
    opts_v2 = Options(verbosity=Verbosity.NONE, use_pallas=False,
                      autotune=False, block_alloc=BlockAlloc.ALLMODE,
                      idx_width="auto")
    bs1 = BlockedSparse.from_coo(tt, opts_v1)
    bs2 = BlockedSparse.from_coo(tt, opts_v2)
    assert mttkrp_decode_bytes(bs1, 4, 0, "fused_t") == 0.0
    for eng in STREAM_NATIVE_ENGINES:
        assert mttkrp_decode_bytes(bs2, 4, 0, eng) == 0.0
    enc = mttkrp_bytes_encoded("blocked_pallas", bs2, 4, 0, 4)
    for eng in ("fused_t", "fused_tg", "unfused_pallas"):
        dec = mttkrp_decode_bytes(bs2, 4, 0, eng)
        assert dec > 0.0, eng
    # the transposed-table kernels' replicated request tiles dominate:
    # the achieved/encoded ratio is ~2x
    assert (enc + mttkrp_decode_bytes(bs2, 4, 0, "fused_t")) / enc > 1.3


def test_decode_registries_declared():
    from splatt_tpu.config import DECODES, IDX_WIDTHS, resolve_decode
    from splatt_tpu.resilience import RUN_REPORT_EVENTS
    from splatt_tpu.utils.env import ENV_VARS
    from splatt_tpu.utils.faults import SITES

    assert "SPLATT_DECODE" in ENV_VARS
    assert "format_decode" in RUN_REPORT_EVENTS
    assert "format.decode" in SITES
    assert "delta" in IDX_WIDTHS and "rle" in IDX_WIDTHS
    assert DECODES == ("kernel", "prep")
    assert resolve_decode() == "kernel"
    Options(idx_width="delta").validate()
    Options(idx_width="rle").validate()
