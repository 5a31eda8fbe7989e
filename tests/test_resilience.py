"""Resilience layer: taxonomy, retries, fallback chain, checkpoints.

The contract under test (ISSUE 1 / docs/resilience.md): failures are
CLASSIFIED (deterministic / transient / resource / unknown) and each
class gets the right consequence — persist, retry-with-backoff, demote
per shape, or re-probe next process; a runtime engine failure degrades
the run to the next engine in the ordered chain instead of killing
cpd_als; corrupt checkpoints fall back a generation instead of crashing
the resume; and every branch is reachable on CPU through the fault
injection harness (splatt_tpu.utils.faults) — resilience code that only
runs when infrastructure misbehaves is dead code until it is testable.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import splatt_tpu.ops.pallas_kernels as pk
from splatt_tpu import resilience
from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import Options, Verbosity
from splatt_tpu.cpd import (CheckpointError, _save_checkpoint, cpd_als,
                            load_checkpoint, load_checkpoint_resilient)
from splatt_tpu.ops.mttkrp import engine_chain, engine_plan, mttkrp
from splatt_tpu.resilience import FailureClass, classify_failure
from splatt_tpu.utils import faults
from tests import gen


@pytest.fixture(autouse=True)
def _clean_resilience_state(monkeypatch):
    """Demotions, the run report, and armed faults are process-global;
    every test starts clean and leaves nothing armed.  Backoff sleeps
    are zeroed so retry tests don't slow the suite."""
    resilience.reset_demotions()
    resilience.run_report().clear()
    resilience.set_fallback(None)
    faults.reset()
    monkeypatch.setattr(resilience.time, "sleep", lambda s: None)
    yield
    resilience.reset_demotions()
    resilience.run_report().clear()
    resilience.set_fallback(None)
    faults.reset()


def _opts(**kw):
    kw.setdefault("random_seed", 31)
    kw.setdefault("verbosity", Verbosity.NONE)
    return Options(**kw)


# -- failure taxonomy -------------------------------------------------------

@pytest.mark.parametrize("msg,cls", [
    # deterministic: Mosaic/kernel-compiler rejection signatures
    ("Mosaic failed to compile the kernel", FailureClass.DETERMINISTIC),
    ("Internal TPU kernel compiler error", FailureClass.DETERMINISTIC),
    ("Invalid input layout for broadcast", FailureClass.DETERMINISTIC),
    ("Unsupported lowering of take_along_axis",
     FailureClass.DETERMINISTIC),
    ("NotImplementedError: dynamic gather", FailureClass.DETERMINISTIC),
    # transient: service failures, never persisted
    ("XLA compile: HTTP code 500 from service", FailureClass.TRANSIENT),
    ("HTTP code 503: service unavailable", FailureClass.TRANSIENT),
    ("INTERNAL: stream reset by the service", FailureClass.TRANSIENT),
    ("UNAVAILABLE: TPU backend setup error", FailureClass.TRANSIENT),
    ("DEADLINE_EXCEEDED: compile RPC", FailureClass.TRANSIENT),
    ("OSError: Connection reset by peer", FailureClass.TRANSIENT),
    ("socket.timeout: timed out", FailureClass.TRANSIENT),
    # resource: capacity, demote this shape only
    ("RESOURCE_EXHAUSTED: attempting to allocate 9G",
     FailureClass.RESOURCE),
    ("Out of memory allocating partials", FailureClass.RESOURCE),
    ("Mosaic: scoped vmem limit exceeded", FailureClass.RESOURCE),
    # unknown: unproven, re-probe next process
    ("ValueError: something else entirely", FailureClass.UNKNOWN),
])
def test_classify_failure_branches(msg, cls):
    assert classify_failure(msg) is cls


def test_classify_precedence():
    """'INTERNAL: Mosaic ...' carries a real compiler signature — the
    transient INTERNAL: prefix must not launder it into a retry; and a
    VMEM message trumping the Mosaic marker is capacity, not
    capability."""
    assert classify_failure(
        "INTERNAL: Mosaic failed to lower") is FailureClass.DETERMINISTIC
    assert classify_failure(
        "Mosaic: scoped vmem limit exceeded") is FailureClass.RESOURCE


def test_classify_accepts_exceptions():
    e = RuntimeError("UNAVAILABLE: service dropped")
    assert classify_failure(e) is FailureClass.TRANSIENT


# -- transient retry with capped backoff + jitter ---------------------------

def test_retry_transient_retries_then_succeeds():
    calls = []
    delays = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("HTTP code 500")
        return "proved"

    out = resilience.retry_transient(flaky, attempts=3,
                                     sleep=delays.append,
                                     rng=lambda: 1.0)
    assert out == "proved"
    assert len(calls) == 3
    # exponential, capped: base, 2*base (full jitter at rng()=1.0)
    assert delays == [resilience.BACKOFF_BASE_S,
                      2 * resilience.BACKOFF_BASE_S]
    assert len(resilience.run_report().events("transient_retry")) == 2


def test_retry_transient_cap_bounds_delay():
    calls = []
    delays = []

    def always_500():
        calls.append(1)
        raise RuntimeError("HTTP code 500")

    with pytest.raises(RuntimeError):
        resilience.retry_transient(always_500, attempts=8,
                                   sleep=delays.append, rng=lambda: 1.0)
    assert len(calls) == 8
    assert max(delays) == resilience.BACKOFF_CAP_S


def test_retry_transient_does_not_retry_other_classes():
    for msg in ("Mosaic rejection", "RESOURCE_EXHAUSTED: oom",
                "ValueError: bug"):
        calls = []

        def fail():
            calls.append(1)
            raise RuntimeError(msg)

        with pytest.raises(RuntimeError):
            resilience.retry_transient(fail, attempts=5,
                                       sleep=lambda s: None)
        assert len(calls) == 1, msg


# -- fault injection harness ------------------------------------------------

def test_faults_inject_and_countdown():
    with faults.inject("somewhere", "http500", times=2):
        with pytest.raises(RuntimeError, match="HTTP code 500"):
            faults.maybe_fail("somewhere")
        with pytest.raises(RuntimeError):
            faults.maybe_fail("somewhere")
        faults.maybe_fail("somewhere")  # exhausted: no-op
    faults.maybe_fail("somewhere")      # disarmed on exit


def test_faults_env_malformed_entries_ignored(monkeypatch, capsys):
    """A typo in SPLATT_FAULTS must warn-and-ignore, not kill the run
    at some random hook site."""
    monkeypatch.setenv("SPLATT_FAULTS",
                       "ck:runtime:two,probe:htp500:1,ok_site:mosaic:1")
    faults.reset()
    faults.maybe_fail("ck")      # malformed times: ignored
    faults.maybe_fail("probe")   # unknown kind: ignored
    with pytest.raises(RuntimeError, match="Mosaic"):
        faults.maybe_fail("ok_site")  # the valid entry still armed
    err = capsys.readouterr().err
    assert "ck:runtime:two" in err and "htp500" in err


def test_faults_env_var(monkeypatch):
    monkeypatch.setenv("SPLATT_FAULTS",
                       "site_a:internal:1, site_b:oom:*")
    faults.reset()
    with pytest.raises(RuntimeError, match="INTERNAL"):
        faults.maybe_fail("site_a")
    faults.maybe_fail("site_a")  # count 1 exhausted
    for _ in range(3):           # '*' never exhausts
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            faults.maybe_fail("site_b")
    faults.maybe_fail("unarmed_site")


@pytest.mark.parametrize("case", ["env_dir", "checkout_default",
                                  "virtual_cpu_devices"])
def test_apply_compile_cache(monkeypatch, case):
    """The persistent compile cache (utils/env.py): JAX_COMPILATION_CACHE_DIR
    when set, and then nothing is set in code; else the fixed in-checkout
    <repo>/.jax_cache; none under virtual CPU devices (deserialized
    sharded CPU executables corrupt the heap).  Config only —
    executing deserialized entries is the chaos soaks' job."""
    import pathlib

    import jax

    from splatt_tpu.utils.env import apply_compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    prior = jax.config.jax_compilation_cache_dir
    prior_t = jax.config.jax_persistent_cache_min_compile_time_secs
    prior_b = jax.config.jax_persistent_cache_min_entry_size_bytes
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        if case == "env_dir":
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/xc-test")
            assert apply_compile_cache() == "/tmp/xc-test"
            # JAX reads the variable itself: no directory set in code
            assert jax.config.jax_compilation_cache_dir == prior
        elif case == "checkout_default":
            want = str(repo / ".jax_cache")
            assert apply_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            monkeypatch.setenv(
                "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
            assert apply_compile_cache() is None
            assert jax.config.jax_compilation_cache_dir == prior
            return
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prior_t)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", prior_b)


def test_compile_cache_dir_is_gitignored():
    """The default cache path lives in the checkout and git ignores it."""
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    lines = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in lines and "chiprun_out/" in lines


def test_faults_kinds_map_to_taxonomy():
    for kind, cls in [("http500", FailureClass.TRANSIENT),
                      ("internal", FailureClass.TRANSIENT),
                      ("unavailable", FailureClass.TRANSIENT),
                      ("timeout", FailureClass.TRANSIENT),
                      ("oom", FailureClass.RESOURCE),
                      ("mosaic", FailureClass.DETERMINISTIC),
                      ("runtime", FailureClass.UNKNOWN)]:
        with faults.inject("k", kind):
            with pytest.raises(Exception) as ei:
                faults.maybe_fail("k")
        assert classify_failure(ei.value) is cls, kind


def test_faults_consume():
    assert faults.consume("torn") is False
    with faults.inject("torn", "runtime", times=1):
        assert faults.consume("torn") is True
        assert faults.consume("torn") is False


# -- demotion registry ------------------------------------------------------

def test_demotion_scopes():
    resilience.demote_engine("fused_t",
                             RuntimeError("injected runtime failure"))
    assert resilience.is_demoted("fused_t")
    assert resilience.is_demoted("fused_t", "ck1:b4096")  # any shape
    # RESOURCE failures demote per-shape only
    resilience.demote_engine("fused_tg",
                             RuntimeError("RESOURCE_EXHAUSTED: oom"),
                             shape_key="ck1:b4096")
    assert resilience.is_demoted("fused_tg", "ck1:b4096")
    assert not resilience.is_demoted("fused_tg", "ck1:b128")
    assert not resilience.is_demoted("fused_tg")
    evs = resilience.run_report().events("engine_demotion")
    assert {e["engine"] for e in evs} == {"fused_t", "fused_tg"}
    resilience.reset_demotions()
    assert not resilience.is_demoted("fused_t")


# -- engine chain / plan ----------------------------------------------------

def _blocked(name="med", **opt_kw):
    """ALLMODE BlockedSparse built without BlockedSparse.from_coo:
    from_coo reaches into splatt_tpu.parallel for the shared layout
    policy, and these tests must run even where the distributed stack's
    jax APIs are unavailable."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.config import resolve_dtype

    tt = gen.fixture_tensor(name)
    opt_kw.setdefault("use_pallas", True)  # pallas_interpret on CPU
    opt_kw.setdefault("nnz_block", 256)
    opts = _opts(**opt_kw).validate()
    layouts = [build_layout(tt, m, block=opts.nnz_block,
                            val_dtype=resolve_dtype(opts, tt.vals.dtype))
               for m in range(tt.nmodes)]
    bs = BlockedSparse(layouts=layouts,
                       mode_map={m: m for m in range(tt.nmodes)},
                       dims=tt.dims, nnz=tt.nnz, opts=opts)
    return tt, bs


def test_engine_chain_order_and_terminal():
    tt, bs = _blocked()
    lay = bs.layouts[0]
    facs = [jnp.zeros((d, 4), jnp.float32) for d in bs.dims]
    chain = engine_chain(lay, facs, lay.mode, "sorted_onehot",
                         "pallas_interpret")
    # best-first, xla_scan before the terminal stream/scatter engine
    assert chain[0].startswith("fused")
    assert chain[-2:] == ["xla_scan", "xla"]
    assert chain.index("xla_scan") > chain.index(chain[0])
    # the xla impl has no pallas candidates
    assert engine_chain(lay, facs, lay.mode, "sorted_onehot",
                        "xla") == ["xla_scan", "xla"]
    # scatter paths are single-engine
    assert engine_chain(lay, facs, lay.mode, "sorted_scatter",
                        "pallas_interpret") == ["xla"]


def test_engine_chain_skips_demoted():
    tt, bs = _blocked()
    lay = bs.layouts[0]
    facs = [jnp.zeros((d, 4), jnp.float32) for d in bs.dims]
    full = engine_chain(lay, facs, lay.mode, "sorted_onehot",
                        "pallas_interpret")
    head = full[0]
    resilience.demote_engine(head, RuntimeError("injected runtime"))
    pruned = engine_chain(lay, facs, lay.mode, "sorted_onehot",
                          "pallas_interpret")
    assert head not in pruned
    assert engine_plan(lay, facs, lay.mode, "sorted_onehot",
                       "pallas_interpret") == pruned[0]
    # the terminal engine can never be demoted out of the chain
    for e in list(full):
        resilience.demote_engine(e, RuntimeError("injected runtime"))
    assert engine_chain(lay, facs, lay.mode, "sorted_onehot",
                        "pallas_interpret")[-1] == "xla"


# -- runtime engine fallback ------------------------------------------------

def test_mttkrp_falls_back_on_engine_fault():
    tt, bs = _blocked()
    lay = bs.layouts[0]
    mode = lay.mode
    rank = 4
    rng = np.random.default_rng(0)
    facs = [jnp.asarray(rng.random((d, rank))) for d in bs.dims]
    want = mttkrp(bs, facs, mode)
    head = engine_plan(lay, facs, mode, "sorted_onehot",
                       "pallas_interpret")
    resilience.run_report().clear()
    with faults.inject(f"engine.{head}", "runtime", times=faults.ALWAYS):
        got = mttkrp(bs, facs, mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-8)
    evs = resilience.run_report().events("engine_demotion")
    assert [e["engine"] for e in evs] == [head]


def test_mttkrp_fallback_off_raises():
    tt, bs = _blocked(engine_fallback=False)
    lay = bs.layouts[0]
    facs = [jnp.asarray(np.random.default_rng(0).random((d, 4)))
            for d in bs.dims]
    head = engine_plan(lay, facs, lay.mode, "sorted_onehot",
                       "pallas_interpret")
    with faults.inject(f"engine.{head}", "runtime", times=faults.ALWAYS):
        with pytest.raises(RuntimeError, match="injected"):
            mttkrp(bs, facs, lay.mode)


def test_cpd_als_completes_through_engine_fault():
    """Acceptance: with fault injection forcing the lead Pallas engine
    to fail at runtime, cpd_als completes on the next engine in the
    chain, the fit matches the no-fault run to 1e-6, and the demotion
    appears in the run report."""
    tt, bs = _blocked()
    opts = _opts(max_iterations=6, use_pallas=True)
    base = cpd_als(bs, rank=3, opts=opts)

    resilience.reset_demotions()
    resilience.run_report().clear()
    lay = bs.layouts[0]
    facs = [jnp.zeros((d, 3), jnp.float32) for d in bs.dims]
    head = engine_plan(lay, facs, lay.mode, "sorted_onehot",
                       "pallas_interpret")
    with faults.inject(f"engine.{head}", "runtime", times=faults.ALWAYS):
        faulted = cpd_als(bs, rank=3, opts=_opts(max_iterations=6,
                                                 use_pallas=True))
    assert float(faulted.fit) == pytest.approx(float(base.fit), abs=1e-6)
    demoted = [e["engine"] for e in
               resilience.run_report().events("engine_demotion")]
    assert head in demoted


def test_sweep_level_rescue_decision():
    """_try_engine_rescue: demote-and-retry only when fallback is on,
    an engine was attempted, it is not terminal, it was not already
    demoted (livelock guard), and the error is engine-shaped."""
    from splatt_tpu.cpd import _try_engine_rescue

    tt, bs = _blocked()
    err = RuntimeError("INTERNAL: async runtime failure")
    # no attempt noted yet (the attempt note is scope state now)
    resilience._state().last_attempt = None
    assert _try_engine_rescue(bs, _opts(), err) is False
    resilience.note_engine_attempt("fused_t", "ck1:b256")
    assert _try_engine_rescue(bs, _opts(), err) is True
    assert resilience.is_demoted("fused_t")
    # same engine again: already demoted, nothing new was tried
    assert _try_engine_rescue(bs, _opts(), err) is False
    # terminal engine: nothing left to fall back to
    resilience.note_engine_attempt("xla", None)
    assert _try_engine_rescue(bs, _opts(), err) is False
    # fallback off
    resilience.note_engine_attempt("fused_tg", None)
    assert _try_engine_rescue(bs, _opts(engine_fallback=False),
                              err) is False
    # a non-engine-shaped error (UNKNOWN class, e.g. a LinAlgError from
    # the solve) must surface, not demote a healthy engine
    resilience.note_engine_attempt("fused_tg", None)
    assert _try_engine_rescue(
        bs, _opts(), RuntimeError("LinAlgError: singular matrix")) is False
    assert not resilience.is_demoted("fused_tg")
    # COO oracle input has no engine chain
    resilience.note_engine_attempt("fused_tg", None)
    assert _try_engine_rescue(tt, _opts(), err) is False


# -- probe-compile fault injection (acceptance criterion) -------------------

def test_injected_compile_500_leaves_no_persisted_rejection(tmp_path,
                                                            monkeypatch):
    """Acceptance: an injected compile-time HTTP 500 leaves no
    persisted 'compile_failed' entry in the on-disk probe cache."""
    import jax

    cache = tmp_path / "probe_cache.json"
    monkeypatch.setenv(pk._CACHE_ENV, str(cache))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pk.PROBE_STATES.clear()
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    with faults.inject("probe_compile", "http500", times=faults.ALWAYS):
        assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "infra"
    text = cache.read_text()
    assert "compile_failed" not in text
    assert json.loads(text)  # still valid JSON
    # the service recovers within the retry budget: proven in-process
    pk.PROBE_STATES.clear()
    with faults.inject("probe_compile", "http500", times=1):
        assert pk._probe_compiles(None, "testk2", "ck1", 4096) is True
    assert pk.probe_cache_load("testk2:ck1:b4096") == "ok"


# -- checkpoint integrity ---------------------------------------------------

def _mk_ckpt(path, seed=0, it=4, fit=0.5):
    rng = np.random.default_rng(seed)
    factors = [jnp.asarray(rng.random((d, 3))) for d in (6, 5, 4)]
    lam = jnp.asarray(rng.random(3))
    _save_checkpoint(str(path), factors, lam, it, fit)
    return factors, lam


def test_checkpoint_roundtrip_with_checksum(tmp_path):
    ck = tmp_path / "ck.npz"
    factors, lam = _mk_ckpt(ck)
    got_f, got_lam, it, fit = load_checkpoint(str(ck))
    assert it == 4 and fit == 0.5
    for a, b in zip(got_f, factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with np.load(str(ck)) as z:
        assert int(z["schema"]) == 2
        assert "checksum" in z.files


def test_checkpoint_truncation_detected(tmp_path):
    ck = tmp_path / "ck.npz"
    _mk_ckpt(ck)
    data = ck.read_bytes()
    ck.write_bytes(data[:len(data) // 2])
    with pytest.raises(CheckpointError):
        load_checkpoint(str(ck))


def test_checkpoint_checksum_catches_tampered_payload(tmp_path):
    """The content checksum catches corruption the zip container
    misses: a payload swapped under a stale checksum must not load."""
    ck = tmp_path / "ck.npz"
    _mk_ckpt(ck)
    with np.load(str(ck)) as z:
        data = {k: z[k] for k in z.files}
    data["factor0"] = data["factor0"] + 1.0
    np.savez(str(ck), **data)
    with pytest.raises(CheckpointError, match="checksum"):
        load_checkpoint(str(ck))
    # verify=False loads it anyway (forensics)
    factors, _, it, _ = load_checkpoint(str(ck), verify=False)
    assert it == 4


def test_legacy_v1_checkpoint_still_loads(tmp_path):
    ck = tmp_path / "ck.npz"
    rng = np.random.default_rng(0)
    factors = [rng.random((d, 2)) for d in (5, 4, 3)]
    np.savez(str(ck), nmodes=3, it=7, fit=0.25, lam=np.ones(2),
             dims=np.asarray([5, 4, 3]), rank=2,
             **{f"factor{m}": f for m, f in enumerate(factors)})
    got_f, lam, it, fit = load_checkpoint(str(ck))
    assert it == 7 and fit == 0.25 and len(got_f) == 3


def test_resilient_load_falls_back_to_bak(tmp_path):
    ck = tmp_path / "ck.npz"
    _mk_ckpt(ck, seed=1, it=2, fit=0.3)      # generation 1
    _mk_ckpt(ck, seed=2, it=4, fit=0.6)      # generation 2; gen1 -> .bak
    assert (tmp_path / "ck.npz.bak").exists()
    data = ck.read_bytes()
    ck.write_bytes(data[: len(data) // 3])   # corrupt the latest
    out = load_checkpoint_resilient(str(ck))
    assert out is not None
    _, _, it, fit = out
    assert (it, fit) == (2, 0.3)             # the previous generation
    ev = resilience.run_report().events("checkpoint_recovery")
    assert len(ev) == 1 and "previous generation" in ev[0]["action"]


def test_resilient_load_gives_up_gracefully(tmp_path):
    ck = tmp_path / "ck.npz"
    _mk_ckpt(ck, it=2)
    _mk_ckpt(ck, it=4)
    ck.write_bytes(b"garbage")
    (tmp_path / "ck.npz.bak").write_bytes(b"also garbage")
    assert load_checkpoint_resilient(str(ck)) is None
    ev = resilience.run_report().events("checkpoint_recovery")
    assert len(ev) == 1 and "starting fresh" in ev[0]["action"]


def test_torn_write_injection_and_resume(tmp_path):
    """Acceptance-adjacent: a torn checkpoint write (injected) corrupts
    the latest generation; the next resume degrades to .bak instead of
    crashing, and cpd_als completes."""
    tt = gen.fixture_tensor("med")
    ck = str(tmp_path / "ck.npz")
    opts = _opts(max_iterations=4)
    cpd_als(tt, rank=3, opts=opts, checkpoint_path=ck, checkpoint_every=2)
    # overwrite the latest generation with a TORN write
    with np.load(ck) as z:
        pass  # it is valid now
    factors, lam, it, fit = load_checkpoint(ck)
    with faults.inject("checkpoint_torn", "runtime", times=1):
        _save_checkpoint(ck, factors, lam, it, fit)
    with pytest.raises(CheckpointError):
        load_checkpoint(ck)
    # resume: falls back to the .bak generation, completes more sweeps
    out = cpd_als(tt, rank=3, opts=_opts(max_iterations=6),
                  checkpoint_path=ck, checkpoint_every=2)
    assert np.isfinite(float(out.fit))
    ev = resilience.run_report().events("checkpoint_recovery")
    assert len(ev) == 1


def test_resume_from_bak_when_primary_missing(tmp_path):
    """A crash between the writer's two renames can leave ONLY the
    .bak generation on disk; the resume must still find it instead of
    silently restarting from iteration 0."""
    import os

    tt = gen.fixture_tensor("med")
    ck = str(tmp_path / "ck.npz")
    a = cpd_als(tt, rank=3, opts=_opts(max_iterations=4),
                checkpoint_path=ck, checkpoint_every=2)
    # simulate the torn-rename crash: primary gone, .bak intact
    os.replace(ck, ck + ".bak")
    assert not os.path.exists(ck)
    b = cpd_als(tt, rank=3, opts=_opts(max_iterations=4),
                checkpoint_path=ck, checkpoint_every=2)
    # resumed at the checkpointed iteration -> same terminal model
    assert float(b.fit) == pytest.approx(float(a.fit), abs=1e-8)
    ev = resilience.run_report().events("checkpoint_recovery")
    assert len(ev) == 1 and "previous generation" in ev[0]["action"]


def test_checkpoint_write_fault_raises(tmp_path):
    ck = tmp_path / "ck.npz"
    with faults.inject("checkpoint_write", "runtime", times=1):
        with pytest.raises(RuntimeError, match="injected"):
            _mk_ckpt(ck)
    assert not ck.exists()


def test_distributed_resume_shares_hardened_path():
    """run_distributed_als resumes through load_checkpoint_resilient —
    the same corrupt-checkpoint degradation as the single-chip driver
    (source-level contract; the distributed sweep itself needs
    shard_map)."""
    import pathlib

    import splatt_tpu

    src = (pathlib.Path(splatt_tpu.__file__).parent / "parallel"
           / "common.py").read_text()
    assert "load_checkpoint_resilient" in src
