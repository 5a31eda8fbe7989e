"""Persistent capability-probe cache.

A probe's verdict depends only on (jax version, device kind, kernel,
regime, block), so it is cached on disk and reused by later processes —
a process spends its chip time measuring, not re-proving what an
earlier process already compiled.  The contract under
test (the probe-cache lifecycle of the resilience layer): proven
verdicts ("ok"/"compile_failed"/"resource") short-circuit the probe,
"timeout"/"infra" are recorded but always retried, transient failures
are retried in-place with backoff and NEVER persisted as a rejection,
entries expire after a TTL, and cache IO failures never break dispatch.
"""

import json
import time

import jax
import pytest

import splatt_tpu.ops.pallas_kernels as pk
from splatt_tpu import resilience


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    """Transient-retry backoff must not slow the suite down."""
    monkeypatch.setattr(resilience.time, "sleep", lambda s: None)


@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = tmp_path / "probe_cache.json"
    monkeypatch.setenv(pk._CACHE_ENV, str(path))
    return path


@pytest.fixture()
def fake_tpu(monkeypatch):
    """Pretend the backend is TPU so _probe_compiles reaches the cache
    and probe machinery; the probe body itself is substituted per-test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _states(snapshot):
    """Context to keep PROBE_STATES isolated per test."""
    pk.PROBE_STATES.clear()
    pk.PROBE_STATES.update(snapshot)


def test_store_load_roundtrip(cache_file):
    pk.probe_cache_store("fused_t:ck1:b4096", "ok")
    assert pk.probe_cache_load("fused_t:ck1:b4096") == "ok"
    assert pk.probe_cache_load("fused_t:ck1:b128") is None
    # the file is keyed by environment (jax version | device kind)
    data = json.loads(cache_file.read_text())
    (env_key,) = data.keys()
    assert jax.__version__ in env_key


def test_cache_hit_skips_probe(cache_file, fake_tpu, monkeypatch):
    _states({})
    pk.probe_cache_store("testk:ck1:b4096", "compile_failed")

    def boom(*a, **k):
        raise AssertionError("probe must not run on a cache hit")

    monkeypatch.setattr(pk, "_probe_case", boom)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "compile_failed"

    pk.probe_cache_store("testk2:ck1:b4096", "ok")
    assert pk._probe_compiles(None, "testk2", "ck1", 4096) is True
    assert pk.PROBE_STATES["testk2:ck1:b4096"] == "ok"


def test_cache_miss_runs_probe_and_stores(cache_file, fake_tpu, monkeypatch):
    _states({})
    calls = []
    monkeypatch.setattr(pk, "_probe_case",
                        lambda fn, regime, block: calls.append(1) or True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert calls == [1]
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"
    # a second PROCESS (simulated: fresh PROBE_STATES) hits the cache
    _states({})
    monkeypatch.setattr(pk, "_probe_case",
                        lambda fn, regime, block: calls.append(2) or True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert calls == [1], "second process must not re-probe"


def test_timeout_is_retried_not_inherited(cache_file, fake_tpu, monkeypatch):
    _states({})
    pk.probe_cache_store("testk:ck1:b4096", "timeout")
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    # an unproven verdict must NOT short-circuit: the probe runs and
    # upgrades the cached state to the proven one
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"


def test_infra_error_is_retried_not_inherited(cache_file, fake_tpu,
                                              monkeypatch):
    _states({})

    def flaky(fn, regime, block):
        raise RuntimeError("UNAVAILABLE: TPU backend setup error")

    monkeypatch.setattr(pk, "_probe_case", flaky)
    # a transient service failure is NOT a kernel rejection
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "infra"
    assert pk.probe_cache_load("testk:ck1:b4096") == "infra"
    # the next process re-probes and can prove the kernel fine
    _states({})
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"


def test_transient_500_retried_in_place_then_proven(cache_file, fake_tpu,
                                                    monkeypatch):
    """A transient HTTP 500 is retried with backoff INSIDE the probe:
    when the service recovers within the retry budget, the verdict is
    proven in this very process — no demotion at all."""
    _states({})
    calls = []

    def flaky_then_ok(fn, regime, block):
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("XLA compile: HTTP code 500 from the compile service")
        return True

    monkeypatch.setattr(pk, "_probe_case", flaky_then_ok)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert len(calls) == 3
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"


def test_transient_500_never_persisted_as_compile_failed(cache_file,
                                                         fake_tpu,
                                                         monkeypatch):
    """ADVICE.md medium: one wedged-service 500 must NOT demote the
    flagship engine for every future session.  Retries exhausted →
    'infra' (re-probed next process); the on-disk cache must contain
    no 'compile_failed' entry."""
    _states({})

    def always_500(fn, regime, block):
        raise RuntimeError("XLA compile: HTTP code 500 from the compile service")

    monkeypatch.setattr(pk, "_probe_case", always_500)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "infra"
    assert "compile_failed" not in cache_file.read_text()
    # bare INTERNAL: is transient too (no Mosaic co-marker)
    _states({})

    def always_internal(fn, regime, block):
        raise RuntimeError("INTERNAL: compile service stream reset")

    monkeypatch.setattr(pk, "_probe_case", always_internal)
    assert pk._probe_compiles(None, "testk2", "ck1", 4096) is False
    assert "compile_failed" not in cache_file.read_text()
    # the next process re-probes and can prove the kernels fine
    _states({})
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True


def test_internal_mosaic_co_marker_is_deterministic(cache_file, fake_tpu,
                                                    monkeypatch):
    """'INTERNAL: Mosaic failed ...' carries a real compiler signature:
    the transient INTERNAL: prefix must not launder it into a retry —
    it persists as a proven rejection."""
    _states({})

    def mosaic_internal(fn, regime, block):
        raise RuntimeError("INTERNAL: Mosaic failed to lower the kernel")

    monkeypatch.setattr(pk, "_probe_case", mosaic_internal)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.probe_cache_load("testk:ck1:b4096") == "compile_failed"


def test_resource_verdict_is_shape_scoped_and_persisted(cache_file,
                                                        fake_tpu,
                                                        monkeypatch):
    """An OOM is capacity, not capability: persisted as 'resource' for
    THIS (regime, block) shape only — other shapes keep probing."""
    _states({})

    def oom(fn, regime, block):
        raise RuntimeError("RESOURCE_EXHAUSTED: attempting to allocate 9G")

    monkeypatch.setattr(pk, "_probe_case", oom)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.probe_cache_load("testk:ck1:b4096") == "resource"
    # the verdict short-circuits the next process for the same shape
    _states({})

    def boom(fn, regime, block):
        raise AssertionError("probe must not run on a cached resource "
                             "verdict")

    monkeypatch.setattr(pk, "_probe_case", boom)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    # ... but a DIFFERENT shape still probes
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 128) is True


def test_ttl_expiry_reprobes(cache_file, fake_tpu, monkeypatch):
    """Even a proven verdict expires after the TTL: infrastructure
    drifts under a fixed env key, so stale rejections (and stale OKs)
    are re-earned instead of trusted forever."""
    _states({})
    pk.probe_cache_store("testk:ck1:b4096", "compile_failed")
    # age the entry past the TTL
    data = json.loads(cache_file.read_text())
    for env in data.values():
        env["testk:ck1:b4096"]["ts"] = (
            time.time() - pk.probe_cache_ttl() - 1)
    cache_file.write_text(json.dumps(data))
    assert pk.probe_cache_load("testk:ck1:b4096") is None
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"


def test_ttl_env_override(cache_file, monkeypatch):
    _states({})
    pk.probe_cache_store("testk:ck1:b4096", "ok")
    monkeypatch.setenv(pk._CACHE_TTL_ENV, "0.0")
    # TTL <= 0 disables expiry entirely
    assert pk.probe_cache_load("testk:ck1:b4096") == "ok"
    monkeypatch.setenv(pk._CACHE_TTL_ENV, "1e-9")
    assert pk.probe_cache_load("testk:ck1:b4096") is None


def test_kernel_edit_invalidates_cache(cache_file, fake_tpu, monkeypatch):
    _states({})
    pk.probe_cache_store("testk:ck1:b4096", "compile_failed")
    # simulate a kernel fix: the module source hash changes, so the old
    # environment's verdicts no longer apply and the probe re-runs
    monkeypatch.setattr(pk, "_kernel_src_hash", lambda: "newhash12345")
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True


def test_compile_failure_is_stored(cache_file, fake_tpu, monkeypatch):
    _states({})

    def fail(fn, regime, block):
        raise RuntimeError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(pk, "_probe_case", fail)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.probe_cache_load("testk:ck1:b4096") == "compile_failed"
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "compile_failed"


def test_unrecognized_error_is_not_persisted_as_rejection(cache_file,
                                                         fake_tpu,
                                                         monkeypatch):
    """Only whitelisted deterministic signatures may persist as
    compile_failed — the cache makes misclassification permanent, so an
    unknown exception is unproven and the next process re-probes."""
    _states({})

    def weird(fn, regime, block):
        raise OSError("Connection reset by peer")

    monkeypatch.setattr(pk, "_probe_case", weird)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.probe_cache_load("testk:ck1:b4096") == "infra"
    _states({})
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True


def test_not_tpu_short_circuits_without_cache(cache_file):
    _states({})
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is False
    assert pk.PROBE_STATES["testk:ck1:b4096"] == "not_tpu"
    assert not cache_file.exists()


def test_cache_io_failure_is_harmless(fake_tpu, monkeypatch, tmp_path):
    _states({})
    # a path whose parent is a regular file: mkdir/open both fail
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    monkeypatch.setenv(pk._CACHE_ENV, str(blocker / "sub" / "cache.json"))
    monkeypatch.setattr(pk, "_probe_case", lambda fn, regime, block: True)
    # store/load both raise internally; dispatch still gets its verdict
    assert pk._probe_compiles(None, "testk", "ck1", 4096) is True


def test_reads_route_through_shared_json_cache_load(cache_file,
                                                    monkeypatch):
    """Regression for the SPL011 (cache-lock discipline) fix: both the
    probe cache and the autotuner's plan cache read through the single
    `_json_cache_load` helper — the sanctioned chokepoint of the locked
    cache protocol — and a corrupt file degrades through it with a
    classified run-report event instead of an inline open()."""
    calls = []
    real = pk._json_cache_load

    def spy(path, on_error=None):
        calls.append(str(path))
        return real(path, on_error=on_error)

    monkeypatch.setattr(pk, "_json_cache_load", spy)
    cache_file.write_text("{ not json")
    resilience.run_report().clear()
    assert pk.probe_cache_load("anything") is None
    assert calls and calls[0] == str(cache_file)
    assert resilience.run_report().events("probe_cache_io_error")

    from splatt_tpu import tune

    tune.reset_memo()
    monkeypatch.setenv(tune._CACHE_ENV, str(cache_file))
    resilience.run_report().clear()
    assert tune._load_file() is None
    assert len(calls) >= 2 and calls[-1] == str(cache_file)
    assert resilience.run_report().events("tune_cache_io_error")


# -- concurrent shared-cache access (docs/serve.md) --------------------------

def test_concurrent_probe_stores_lose_no_verdicts(cache_file):
    """N threads persisting distinct probe verdicts simultaneously
    (concurrent serve jobs proving different kernels): the locked
    read-modify-write keeps every verdict — no lost updates, no torn
    JSON."""
    import threading

    n = 16
    errs = []

    def store(i):
        try:
            pk.probe_cache_store(f"conc_state{i}",
                                 "ok" if i % 2 == 0 else "compile_failed")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=store, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    data = json.loads(cache_file.read_text())  # parses: not torn
    env = data[pk._cache_env_key()]
    assert {f"conc_state{i}" for i in range(n)} <= set(env)
    for i in range(n):
        want = "ok" if i % 2 == 0 else "compile_failed"
        assert pk.probe_cache_load(f"conc_state{i}") == want


def test_concurrent_probe_and_tune_writers_share_one_protocol(cache_file,
                                                              monkeypatch):
    """Probe verdicts and tuner plans hammering their caches from
    interleaved threads (the serve steady state): both files end
    complete and parseable — the shared locked protocol serializes
    writers within the process as well as across processes."""
    import threading

    from splatt_tpu import tune

    monkeypatch.setenv(tune._CACHE_ENV,
                       str(cache_file.with_name("tc.json")))
    tune.reset_memo()
    errs = []

    def probe_writer(i):
        try:
            for k in range(4):
                pk.probe_cache_store(f"pt{i}k{k}", "ok")
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def tune_writer(i):
        try:
            for k in range(4):
                tune._entry_store(
                    f"tt{i}k{k}",
                    {"plan": dict(path="sorted_onehot", engine="xla",
                                  nnz_block=512, scan_target=1 << 21,
                                  sec=0.5)})
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = ([threading.Thread(target=probe_writer, args=(i,))
                for i in range(4)]
               + [threading.Thread(target=tune_writer, args=(i,))
                  for i in range(4)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    probe_env = json.loads(cache_file.read_text())[pk._cache_env_key()]
    assert {f"pt{i}k{k}" for i in range(4) for k in range(4)} \
        <= set(probe_env)
    tune.reset_memo()
    for i in range(4):
        for k in range(4):
            assert tune._entry_get(f"tt{i}k{k}") is not None
