"""splatt-tune: the empirical autotuner (splatt_tpu/tune.py).

Contract under test (docs/autotune.md): plan-cache lifecycle
(write / hit / TTL-expire / source-hash-invalidate / corrupt-file
degrades to re-tune), candidate pruning (demoted engines are never
candidates, deterministic failures persist as negative entries,
transient failures retry in place), dispatch integration (a cached
plan heads the engine chain; an inapplicable or missing plan keeps
the heuristics), the donated-sweep fast path (cpd_als fit identical
with donation on and off), and the fault drill — a crashing
measurement degrades dispatch to the heuristic chain, never fails
the run.
"""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

import splatt_tpu.ops.pallas_kernels as pk
import splatt_tpu.tune as tune
from splatt_tpu import resilience
from splatt_tpu.blocked import BlockedSparse, build_layout
from splatt_tpu.config import BlockAlloc, Options, Verbosity
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import cpd_als, init_factors
from splatt_tpu.ops.mttkrp import engine_plan, mttkrp_blocked, mttkrp_stream
from splatt_tpu.utils import faults
from tests import gen

RANK = 4


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Every test gets its own plan cache, a clean demotion registry,
    a clean run report, and instant transient backoff."""
    monkeypatch.setenv(tune._CACHE_ENV, str(tmp_path / "tune_cache.json"))
    monkeypatch.setattr(resilience.time, "sleep", lambda s: None)
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


def _opts(**kw):
    kw.setdefault("random_seed", 42)
    kw.setdefault("verbosity", Verbosity.NONE)
    kw.setdefault("val_dtype", np.float64)
    kw.setdefault("use_pallas", False)
    return Options(**kw)


def _cache_file():
    import pathlib

    return pathlib.Path(str(tune.cache_path()))


# -- plan-cache lifecycle ---------------------------------------------------

def test_tune_writes_plan_and_warm_cache_skips_measurement():
    """The acceptance contract: a second run with a warm plan cache
    runs ZERO measurements."""
    tt = _tensor()
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert res.plans and set(res.plans) == set(range(tt.nmodes))
    assert res.measured > 0 and res.cache_hits == 0
    assert _cache_file().exists()
    res2 = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert res2.measured == 0, "warm cache must skip all measurement"
    assert res2.cache_hits == tt.nmodes
    assert res2.plans == res.plans


def test_plan_survives_process_restart():
    """A fresh process (simulated: memo reset) reloads the plan from
    disk — that is what makes `splatt tune` pre-tuning pay off."""
    tt = _tensor()
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    tune.reset_memo()
    plan = tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0))
    assert plan == res.plans[0]


def test_ttl_expiry_retunes(monkeypatch):
    """Even a proven plan expires after the (probe-cache) TTL: the
    winning configuration drifts with the infrastructure."""
    tt = _tensor()
    tune.tune(tt, RANK, opts=_opts(), reps=1)
    data = json.loads(_cache_file().read_text())
    for env in data["envs"].values():
        for entry in env.values():
            entry["ts"] = 1.0  # the distant past
    _cache_file().write_text(json.dumps(data))
    tune.reset_memo()
    assert tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0)) is None
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert res.measured > 0, "expired plans must be re-earned"


def test_kernel_source_hash_invalidates_plans(monkeypatch):
    """The plan cache shares the probe cache's environment key: editing
    a kernel source must invalidate every cached plan."""
    tt = _tensor()
    tune.tune(tt, RANK, opts=_opts(), reps=1)
    tune.reset_memo()
    monkeypatch.setattr(pk, "_kernel_src_hash", lambda: "edited123456")
    assert tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0)) is None


def test_corrupt_cache_degrades_to_retune():
    """A corrupt plan-cache file is an unusable cache, not a failed
    dispatch: reported through the taxonomy, then re-tuned."""
    tt = _tensor()
    _cache_file().parent.mkdir(parents=True, exist_ok=True)
    _cache_file().write_text("{ not json")
    assert tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0)) is None
    assert resilience.run_report().events("tune_cache_io_error")
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert res.plans and res.measured > 0
    # the re-tune replaced the corrupt file with a valid one
    tune.reset_memo()
    assert tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0)) is not None


def test_foreign_cache_version_is_retuned():
    """A cache written by a different tuner generation is re-tuned,
    never reinterpreted."""
    tt = _tensor()
    tune.tune(tt, RANK, opts=_opts(), reps=1)
    data = json.loads(_cache_file().read_text())
    data["version"] = tune.PLAN_CACHE_VERSION + 1
    _cache_file().write_text(json.dumps(data))
    tune.reset_memo()
    assert tune.cached_plan(tt.dims, tt.nnz, 0, RANK, jnp.float64,
                            skew=tune.skew_of(tt, 0)) is None


def test_plan_key_is_shape_regime_scoped():
    """Two tensors in the same power-of-two shape regime share plans;
    a different rank or dtype never does."""
    tt = _tensor()
    key = tune.plan_key(tt.dims, tt.nnz, 0, RANK, jnp.float64)
    # same power-of-two buckets (dims scaled < 2x, same nnz bucket)
    assert key == tune.plan_key(tt.dims, tt.nnz - 1, 0, RANK, jnp.float64)
    assert key != tune.plan_key([d * 4 for d in tt.dims], tt.nnz, 0,
                                RANK, jnp.float64)
    assert key != tune.plan_key(tt.dims, tt.nnz, 0, RANK + 1, jnp.float64)
    assert key != tune.plan_key(tt.dims, tt.nnz, 0, RANK, jnp.float32)
    assert key != tune.plan_key(tt.dims, tt.nnz, 1, RANK, jnp.float64)


# -- candidate handling -----------------------------------------------------

def test_demoted_engine_is_never_a_candidate(monkeypatch):
    tt = _tensor()
    resilience.demote_engine("xla_scan", RuntimeError("Mosaic crash"))
    measured = []

    def spy(layout, factors, mode, path, impl, engine, st, **kw):
        measured.append(engine)
        return 0.001

    monkeypatch.setattr(tune, "_measure_candidate", spy)
    # use_pallas forces the one-hot path so xla_scan WOULD be a
    # candidate if it were live
    res = tune.tune(tt, RANK, opts=_opts(use_pallas=True), reps=1)
    assert measured and "xla_scan" not in measured
    assert all(p.engine != "xla_scan" for p in res.plans.values())


def test_deterministic_failure_becomes_negative_entry(monkeypatch):
    """A Mosaic-class measurement failure persists as a negative entry:
    a later tune skips the candidate instead of re-paying the compile."""
    tt = _tensor()
    attempts = []

    def failing(layout, factors, mode, path, impl, engine, st, **kw):
        attempts.append(engine)
        if engine == "xla_scan":
            raise RuntimeError("Mosaic failed to compile the kernel")
        return 0.001

    monkeypatch.setattr(tune, "_measure_candidate", failing)
    res = tune.tune(tt, RANK, opts=_opts(use_pallas=True), reps=1)
    assert all(p.engine == "xla" for p in res.plans.values())
    assert resilience.run_report().events("tuner_negative")
    assert "neg:" in _cache_file().read_text()
    # a forced re-tune skips the negative candidates entirely
    first_scan_attempts = attempts.count("xla_scan")
    assert first_scan_attempts > 0
    res2 = tune.tune(tt, RANK, opts=_opts(use_pallas=True), reps=1,
                     force=True)
    assert attempts.count("xla_scan") == first_scan_attempts
    assert res2.skipped > 0


def test_transient_failure_is_retried_in_place(monkeypatch):
    """An HTTP-500-class timing failure retries with backoff inside
    the tuner (resilience.retry_transient) and is never persisted."""
    tt = _tensor()
    calls = {"n": 0}

    def flaky(layout, factors, mode, path, impl, engine, st, **kw):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("XLA compile: HTTP code 500 from the compile service")
        return 0.001

    monkeypatch.setattr(tune, "_measure_candidate", flaky)
    res = tune.tune(tt, RANK, opts=_opts(), modes=[0], reps=1)
    assert 0 in res.plans
    assert calls["n"] >= 3
    assert "neg:" not in _cache_file().read_text()


def test_all_candidates_failing_degrades_to_heuristics(monkeypatch):
    tt = _tensor()
    monkeypatch.setattr(
        tune, "_measure_candidate",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert not res.plans
    assert resilience.run_report().events("tuner_degraded")
    # and dispatch still works — the heuristic chain is intact
    bs = BlockedSparse.compile(tt, _opts(nnz_block=256), rank=RANK)
    out = cpd_als(bs, RANK, opts=_opts(max_iterations=3, nnz_block=256))
    assert np.isfinite(float(out.fit))


def test_fault_drill_env_armed_tuner_crash(monkeypatch):
    """The SPLATT_FAULTS=tuner.measure:* drill: every measurement
    crashes, tuning yields no plan, and the run degrades to the
    heuristic chain instead of failing."""
    monkeypatch.setenv("SPLATT_FAULTS", "tuner.measure:runtime:*")
    faults.reset()  # re-read the env spec
    tt = _tensor()
    res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    assert not res.plans
    assert resilience.run_report().events("tuner_degraded")
    out = cpd_als(BlockedSparse.compile(tt, _opts(nnz_block=256),
                                        rank=RANK),
                  RANK, opts=_opts(max_iterations=3, nnz_block=256))
    assert np.isfinite(float(out.fit))


# -- dispatch integration ---------------------------------------------------

def _store_plan(tt, mode, rank, dtype, **plan):
    plan.setdefault("sec", 0.001)
    tune._entry_store(tune.plan_key(tt.dims, tt.nnz, mode, rank, dtype,
                                    skew=tune.skew_of(tt, mode)),
                      {"plan": plan})


def test_cached_plan_heads_the_engine_chain():
    """A cached winner is dispatched FIRST — engine_plan reports it and
    mttkrp_blocked attempts it — while the heuristic head differs."""
    tt = _tensor()
    lay = build_layout(tt, 0, block=1024, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=False) == "xla_scan"
    _store_plan(tt, 0, RANK, jnp.float64, path="sorted_onehot",
                engine="xla", nnz_block=lay.block, scan_target=1 << 21)
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=True) == "xla"
    out = mttkrp_blocked(lay, facs, 0, path="sorted_onehot", impl="xla",
                         autotune=True)
    assert resilience.last_engine_attempt()[0] == "xla"
    # and the tuned engine computes the same numbers as the oracle
    ref = mttkrp_stream(jnp.asarray(tt.inds), jnp.asarray(tt.vals),
                        facs, 0, tt.dims[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-10, atol=1e-10)


def test_inapplicable_plan_keeps_heuristics():
    """A plan whose block or path disagrees with this dispatch is NOT
    applied — the tuner can never make dispatch wronger."""
    tt = _tensor()
    lay = build_layout(tt, 0, block=1024, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    _store_plan(tt, 0, RANK, jnp.float64, path="sorted_onehot",
                engine="xla", nnz_block=lay.block + 128,  # block mismatch
                scan_target=1 << 21)
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=True) == "xla_scan"


def test_demoted_plan_engine_keeps_heuristics():
    tt = _tensor()
    lay = build_layout(tt, 0, block=1024, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    _store_plan(tt, 0, RANK, jnp.float64, path="sorted_onehot",
                engine="xla", nnz_block=lay.block, scan_target=1 << 21)
    resilience.demote_engine("xla", RuntimeError("Mosaic crash"))
    # the demoted engine is still the chain's terminal fallback, but a
    # stale plan must not PROMOTE it over the live heuristic head
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=True) == "xla_scan"


def test_shape_scoped_demotion_blocks_plan_in_reporting():
    """A per-shape (OOM) demotion must also stop the plan in
    engine_plan's reporting path, which has no caller shape_key —
    otherwise benches would label results with an engine dispatch
    refuses to run."""
    import importlib

    mk = importlib.import_module("splatt_tpu.ops.mttkrp")

    tt = _tensor()
    lay = build_layout(tt, 0, block=1024, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    _store_plan(tt, 0, RANK, jnp.float64, path="sorted_onehot",
                engine="xla", nnz_block=lay.block, scan_target=1 << 21)
    shape_key = mk._engine_shape_key(lay, facs, 0)
    resilience.demote_engine(
        "xla", RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
        shape_key=shape_key)
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=True) == "xla_scan"


def test_autotune_off_ignores_plans():
    tt = _tensor()
    lay = build_layout(tt, 0, block=1024, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    _store_plan(tt, 0, RANK, jnp.float64, path="sorted_onehot",
                engine="xla", nnz_block=lay.block, scan_target=1 << 21)
    assert engine_plan(lay, facs, 0, "sorted_onehot", "xla",
                       autotune=False) == "xla_scan"


def test_autotune_env_kill_switch(monkeypatch):
    monkeypatch.setenv("SPLATT_AUTOTUNE", "0")
    assert tune.autotune_enabled(None) is False
    assert tune.autotune_enabled(True) is True  # explicit opt wins
    monkeypatch.setenv("SPLATT_AUTOTUNE", "1")
    assert tune.autotune_enabled(None) is True
    assert tune.autotune_enabled(False) is False


def test_compile_builds_layouts_at_tuned_blocks():
    """BlockedSparse.compile consults the plan cache so the layout is
    built ONCE at the winning block instead of rebuilt later."""
    tt = _tensor()
    # sorted_scatter is the heuristic path for these layouts on CPU, so
    # the stored plans stay applicable (and reportable) at dispatch
    for m in range(tt.nmodes):
        _store_plan(tt, m, RANK, jnp.float64, path="sorted_scatter",
                    engine="xla", nnz_block=512, scan_target=1 << 23)
    opts = _opts(block_alloc=BlockAlloc.ALLMODE)  # default block 4096
    bs = BlockedSparse.compile(tt, opts, rank=RANK)
    assert all(lay.block == 512 for lay in bs.layouts)
    # without a rank (or with autotune off) compile is plain from_coo
    bs_plain = BlockedSparse.compile(tt, opts)
    assert all(lay.block != 512 for lay in bs_plain.layouts)
    out = cpd_als(bs, RANK, opts=_opts(max_iterations=3))
    assert np.isfinite(float(out.fit))
    # cpd_als surfaced the consulted plan in the run report
    assert resilience.run_report().events("tuned_plan")


def test_tuned_cpd_matches_untuned_fit():
    """End to end: a tuned run computes the same factorization as the
    static-default run (the plan changes speed, never math)."""
    tt = _tensor()
    tune.tune(tt, RANK, opts=_opts(), reps=1)
    init = init_factors(tt.dims, RANK, 7, dtype=jnp.float64)
    a = cpd_als(BlockedSparse.from_coo(tt, _opts(autotune=False)), RANK,
                opts=_opts(autotune=False, max_iterations=5), init=init)
    b = cpd_als(BlockedSparse.compile(tt, _opts(autotune=True), rank=RANK),
                RANK, opts=_opts(autotune=True, max_iterations=5),
                init=init)
    assert float(a.fit) == pytest.approx(float(b.fit), abs=1e-9)


# -- donated sweeps ---------------------------------------------------------

def test_cpd_fit_identical_with_donation_on_and_off():
    """The donated whole-sweep fast path is a pure buffer-aliasing
    optimization: bit-identical results, donation on or off."""
    tt = _tensor()
    init = init_factors(tt.dims, 3, 11, dtype=jnp.float64)
    outs = {}
    for donate in (False, True):
        opts = _opts(max_iterations=6, nnz_block=256,
                     block_alloc=BlockAlloc.ALLMODE, donate_sweep=donate)
        outs[donate] = cpd_als(BlockedSparse.from_coo(tt, opts), 3,
                               opts=opts, init=init)
    assert float(outs[False].fit) == float(outs[True].fit)
    for ua, ub in zip(outs[False].factors, outs[True].factors):
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))
    # the caller's init arrays survive the donated run
    assert not any(u.is_deleted() for u in init)


def test_donated_sweep_consumes_inputs():
    """The donated fused sweep really donates: its inputs are consumed
    (so the no-copy aliasing is actually in effect, not silently off)."""
    from splatt_tpu.cpd import _make_sweep
    from splatt_tpu.ops.linalg import gram

    tt = _tensor()
    bs = BlockedSparse.from_coo(tt, _opts(nnz_block=256, autotune=False,
                                          block_alloc=BlockAlloc.ALLMODE))
    factors = init_factors(tt.dims, 3, 3, dtype=jnp.float64)
    grams = [gram(U) for U in factors]
    sweep = _make_sweep(bs, tt.nmodes, 0.0, donate=True)
    f2, g2, *_ = sweep(factors, grams, True)
    # mode 0's INPUT factor/gram are dead values in the sweep dataflow
    # (the update replaces them before any read), so jit prunes rather
    # than donates them; every live input is consumed
    assert all(u.is_deleted() for u in factors[1:])
    assert all(g.is_deleted() for g in grams[1:])
    assert all(not u.is_deleted() for u in f2)


def test_rescue_rematerializes_donated_state(monkeypatch):
    """An ASYNC engine failure surfacing after the sweep already
    consumed its donated inputs: the rescue re-materializes the
    pre-sweep state from the host snapshot and the run completes on
    the surviving engines (instead of dying on deleted buffers)."""
    import splatt_tpu.cpd as cpd_mod

    tt = _tensor()
    opts = _opts(max_iterations=4, nnz_block=256, donate_sweep=True,
                 block_alloc=BlockAlloc.ALLMODE, engine_fallback=True)
    bs = BlockedSparse.from_coo(tt, opts)
    real_make = cpd_mod._make_sweep
    state = {"fail": True}

    def patched(X, nmodes, reg, donate=False):
        real = real_make(X, nmodes, reg, donate=donate)

        def wrapper(factors, grams, first):
            out = real(factors, grams, first)  # consumes donated inputs
            if state["fail"]:
                state["fail"] = False
                resilience.note_engine_attempt("xla_scan", None)
                raise RuntimeError("INTERNAL: async runtime failure")
            return out

        return wrapper

    monkeypatch.setattr(cpd_mod, "_make_sweep", patched)
    out = cpd_mod.cpd_als(bs, 3, opts=opts)
    assert np.isfinite(float(out.fit))
    assert resilience.is_demoted("xla_scan")


# -- block-clamp observability (ISSUE 3 satellite) --------------------------

def test_block_clamp_is_reported(capsys):
    tt = _tensor()  # ~3k nnz: a 65536 block must clamp
    lay = build_layout(tt, 0, block=65536, val_dtype=np.float64,
                       verbose=True)
    assert lay.block < 65536
    assert "clamped" in capsys.readouterr().out
    events = resilience.run_report().events("block_clamp")
    assert events and events[-1]["requested"] == 65536
    assert events[-1]["effective"] == lay.block
    # the effective block is surfaced by the repr (not the dataclass
    # default dumping device arrays)
    assert f"block={lay.block}" in repr(lay)
    assert "inds" not in repr(lay)


def test_no_clamp_no_event():
    tt = _tensor()
    resilience.run_report().clear()
    build_layout(tt, 0, block=256, val_dtype=np.float64)
    assert not resilience.run_report().events("block_clamp")


# -- tuner measurement plumbing --------------------------------------------

def test_measure_candidate_times_forced_engine():
    """The real measurement body: times the forced engine and returns
    a positive median — and the faults hook is live in it."""
    tt = _tensor()
    lay = build_layout(tt, 0, block=512, val_dtype=np.float64)
    facs = init_factors(tt.dims, RANK, 0, dtype=jnp.float64)
    sec = tune._measure_candidate(lay, facs, 0, "sorted_onehot", "xla",
                                  "xla_scan", 1 << 21, warm=1, reps=2)
    assert sec > 0
    with faults.inject("tuner.measure", "runtime", times=1):
        with pytest.raises(RuntimeError):
            tune._measure_candidate(lay, facs, 0, "sorted_onehot", "xla",
                                    "xla_scan", 1 << 21)


def test_tuned_plan_never_slower_than_static_default():
    """The never-worse acceptance property, by construction: the static
    default configuration is itself a candidate, so the winner's
    measured time is <= the default's measured time."""
    tt = _tensor()
    recorded = {}

    real = tune._measure_candidate

    def recording(layout, factors, mode, path, impl, engine, st, **kw):
        sec = real(layout, factors, mode, path, impl, engine, st, **kw)
        recorded.setdefault(mode, {})[(engine, layout.block, st)] = sec
        return sec

    import splatt_tpu.tune as tmod
    orig = tmod._measure_candidate
    tmod._measure_candidate = recording
    try:
        res = tune.tune(tt, RANK, opts=_opts(), reps=1)
    finally:
        tmod._measure_candidate = orig
    for m, plan in res.plans.items():
        assert plan.sec <= min(recorded[m].values()) + 1e-12


# -- concurrent shared-cache access (docs/serve.md) --------------------------
#
# The serve daemon runs N tenants' jobs as threads in ONE process, all
# sharing the warm plan cache.  The locked protocol must hold under
# that contention: no torn JSON, no lost winners, and a broken cache
# degrades classified — never into a failed dispatch.

def test_concurrent_plan_stores_lose_no_winners():
    """N threads storing distinct winners simultaneously: the final
    cache file holds every one (the locked read-modify-write cannot
    drop a concurrent writer's entry) and parses as one JSON object."""
    import threading

    n = 16
    errs = []

    def store(i):
        try:
            tune._entry_store(f"conc:key{i}",
                              {"plan": dict(path="sorted_onehot",
                                            engine="xla", nnz_block=512,
                                            scan_target=1 << 21,
                                            sec=0.001 * (i + 1))})
        except Exception as e:  # pragma: no cover - the assert reports
            errs.append(e)

    threads = [threading.Thread(target=store, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    data = json.loads(_cache_file().read_text())  # not torn
    env = data["envs"][pk._cache_env_key()]
    assert {f"conc:key{i}" for i in range(n)} <= set(env)
    # and every winner is readable back through the memo-less path
    tune.reset_memo()
    for i in range(n):
        assert tune._entry_get(f"conc:key{i}")["plan"]["sec"] == \
            pytest.approx(0.001 * (i + 1))


def test_concurrent_loads_and_stores_interleaved():
    """Readers hammering the cache while writers mutate it: every read
    returns either None (not yet written) or a complete entry — never
    a torn/partial one — and no exception escapes."""
    import threading

    stop = threading.Event()
    errs = []

    def writer(i):
        try:
            for k in range(8):
                tune._entry_store(
                    f"mix:w{i}k{k}",
                    {"plan": dict(path="sorted_onehot", engine="xla",
                                  nnz_block=512, scan_target=1 << 21,
                                  sec=0.5)})
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                tune.reset_memo()  # force real file reads
                for i in range(4):
                    ent = tune._entry_get(f"mix:w{i}k0")
                    assert ent is None or ent["plan"]["sec"] == 0.5
        except Exception as e:  # pragma: no cover
            errs.append(e)

    writers = [threading.Thread(target=writer, args=(i,))
               for i in range(4)]
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errs
    tune.reset_memo()
    for i in range(4):
        for k in range(8):
            assert tune._entry_get(f"mix:w{i}k{k}") is not None


def test_concurrent_reads_of_corrupt_cache_degrade_classified():
    """A corrupt cache under concurrent readers: every read degrades
    to None (re-tune) and the failure is CLASSIFIED into the run
    report (tune_cache_io_error) — never an exception, never a torn
    verdict."""
    import threading

    _cache_file().write_text("{ definitely not json")
    errs = []

    def reader():
        try:
            tune.reset_memo()
            for _ in range(5):
                assert tune._load_file() is None
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=reader) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    evs = resilience.run_report().events("tune_cache_io_error")
    assert evs and all(e["failure_class"] == "unknown" for e in evs)


def test_entry_get_never_clobbers_concurrent_write_through(monkeypatch):
    """The memo's check-then-act window: a reader that missed the memo
    and read a stale (empty) cache file must ADOPT a write-through
    that landed mid-read, not negative-cache over it — otherwise a
    persisted plan reads as missing for the rest of the process."""
    key = "race:key"
    plan = {"plan": dict(path="sorted_onehot", engine="xla",
                         nnz_block=512, scan_target=1 << 21, sec=0.5)}
    real_load = tune._load_file

    def stale_read_with_concurrent_store():
        # a sibling job's store lands while this reader holds its
        # stale view of the file
        tune._entry_store(key, plan)
        return None  # the reader's read: nothing on disk

    monkeypatch.setattr(tune, "_load_file",
                        stale_read_with_concurrent_store)
    got = tune._entry_get(key)
    assert got is not None and got["plan"]["sec"] == 0.5
    # and the memo was not poisoned with a negative entry
    monkeypatch.setattr(tune, "_load_file", real_load)
    assert tune._entry_get(key) is not None
