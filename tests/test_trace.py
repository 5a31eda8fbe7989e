"""Structured span tracing + metrics registry (splatt_tpu/trace.py,
docs/observability.md).

Covers the ISSUE 10 acceptance surface: span nesting and attributes
round-trip through the Chrome trace-event exporter; disabled tracing is
a true no-op (the shared singleton, zero extra device syncs — spied);
the metrics registry emits parseable Prometheus text with per-job
isolation (one tenant's counters never leak into a neighbor's result);
the chaos smoke's ``--trace`` leg proves every fired fault leaves a
matching point event on the exported trace; and the ``splatt trace``
summarizer reconciles per-iteration spans with the driver's clock.
"""

import json
import re

import numpy as np
import pytest

from splatt_tpu import resilience, trace
from splatt_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts from a fresh recorder/registry and leaves no
    process-global enablement behind (trace state is process-wide by
    design — the drivers share one recorder)."""
    trace.set_enabled(None)
    trace.reset()
    trace.reset_metrics()
    resilience.run_report().clear()
    yield
    trace.set_enabled(None)
    trace.reset()
    trace.reset_metrics()
    resilience.run_report().clear()


def _small_tensor(seed=0):
    from splatt_tpu.chaos import synthetic_tensor

    return synthetic_tensor((14, 12, 10), 500, seed)


def _opts(**kw):
    from splatt_tpu.config import Options, Verbosity

    base = dict(random_seed=0, max_iterations=3, verbosity=Verbosity.NONE,
                use_pallas=False, autotune=False, fit_check_every=1)
    base.update(kw)
    return Options(**base)


# -- span recorder ----------------------------------------------------------

def test_disabled_span_is_the_shared_noop():
    assert not trace.enabled()
    h = trace.span("cpd.sweep", mode=0)
    assert h is trace.NOOP
    with h:
        pass
    assert trace.spans() == []
    # begin/end on the no-op is equally free
    trace.end(trace.begin("cpd.iter"))
    assert trace.spans() == []


def test_span_nesting_attributes_and_stack():
    trace.set_enabled(True)
    with trace.span("cpd.als", rank=4) as root:
        with trace.span("cpd.iter", it=1) as it:
            it.set(fit=0.5)
        with trace.span("cpd.iter", it=2):
            pass
    recs = trace.spans()
    assert [r["name"] for r in recs] == ["cpd.iter", "cpd.iter",
                                        "cpd.als"]
    iters = trace.spans("cpd.iter")
    assert all(r["parent"] == root.rec["sid"] for r in iters)
    assert iters[0]["args"] == {"it": 1, "fit": 0.5}
    assert all(r["dur"] >= 0 for r in recs)
    root_rec = trace.spans("cpd.als")[0]
    assert root_rec["parent"] is None
    assert root_rec["args"]["rank"] == 4


def test_enabling_scope_and_process_override():
    with trace.enabling(True):
        assert trace.enabled()
        with trace.span("cpd.sweep"):
            pass
    assert not trace.enabled()
    trace.set_enabled(True)
    assert trace.enabled()
    with trace.enabling(False):
        assert not trace.enabled()
        assert trace.span("cpd.sweep") is trace.NOOP
    trace.set_enabled(None)
    assert len(trace.spans("cpd.sweep")) == 1


def test_env_enablement(monkeypatch):
    """The env default is memoized (the disabled hot path is one
    boolean test); set_enabled(None) re-earns the verdict."""
    monkeypatch.setenv("SPLATT_TRACE", "on")
    trace.set_enabled(None)
    assert trace.enabled()
    monkeypatch.setenv("SPLATT_TRACE", "0")
    assert trace.enabled()  # memoized: the flip is invisible ...
    trace.set_enabled(None)
    assert not trace.enabled()  # ... until the verdict is cleared


def test_point_events_attach_to_enclosing_span():
    trace.set_enabled(True)
    with trace.span("cpd.als") as root:
        resilience.run_report().add("transient_retry", label="engine.xla",
                                    attempt=1)
    pts = trace.points("transient_retry")
    assert len(pts) == 1
    assert pts[0]["parent"] == root.rec["sid"]
    assert pts[0]["args"]["label"] == "engine.xla"


def test_mis_nested_legacy_brackets_are_tolerated():
    """start A, start B, stop A, stop B — the utils/timers interleave
    the span layer must absorb without corrupting the stack."""
    trace.set_enabled(True)
    a = trace.begin("timer.cpd")
    b = trace.begin("timer.mttkrp")
    trace.end(a)
    trace.end(b)
    with trace.span("cpd.sweep") as h:
        assert h.rec["parent"] is None  # stack fully unwound
    assert {r["name"] for r in trace.spans()} == {
        "timer.cpd", "timer.mttkrp", "cpd.sweep"}


# -- Chrome trace-event export ----------------------------------------------

def test_chrome_export_roundtrip(tmp_path):
    trace.set_enabled(True)
    with trace.span("cpd.als", rank=3):
        with trace.span("cpd.iter", it=1):
            resilience.run_report().add("block_clamp", mode=0,
                                        requested=64, clamped=32)
    out = tmp_path / "trace.json"
    ev = trace.write_chrome_trace(str(out))
    assert ev["ok"] and ev["spans"] == 2 and ev["events"] == 1
    data = json.loads(out.read_text())
    assert "traceEvents" in data
    evs = data["traceEvents"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"cpd.als", "cpd.iter"}
    # the tree is rebuildable from args.sid/parent, not timestamps
    assert (spans["cpd.iter"]["args"]["parent"]
            == spans["cpd.als"]["args"]["sid"])
    assert spans["cpd.als"]["args"]["rank"] == 3
    pts = [e for e in evs if e["ph"] == "i"]
    assert len(pts) == 1 and pts[0]["name"] == "block_clamp"
    # loader accepts both the object form and a bare array
    assert len(trace.load_trace(str(out))) == 3
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(evs))
    assert len(trace.load_trace(str(bare))) == 3


def test_open_span_rides_along_marked(tmp_path):
    trace.set_enabled(True)
    h = trace.begin("serve.job", job="j1")
    evs = trace.chrome_events()
    trace.end(h)
    open_evs = [e for e in evs if e["args"].get("open")]
    assert len(open_evs) == 1 and open_evs[0]["name"] == "serve.job"
    assert open_evs[0]["dur"] >= 1


def test_trace_export_fault_degrades_classified(tmp_path):
    """The trace.export chaos site: losing the trace must never lose
    the run — the export returns a classified trace_written ok=False
    event instead of raising."""
    trace.set_enabled(True)
    with trace.span("cpd.als"):
        pass
    out = tmp_path / "t.json"
    with faults.inject("trace.export", "runtime"):
        ev = trace.write_chrome_trace(str(out))
    assert ev["kind"] == "trace_written" and ev["ok"] is False
    assert ev["failure_class"]
    assert not out.exists()
    # and the very next export (fault disarmed) succeeds
    assert trace.write_chrome_trace(str(out))["ok"]


# -- overhead contract: no-op when disabled, no extra syncs -----------------

def test_traced_cpd_adds_zero_device_syncs(monkeypatch):
    """The SPL003 contract, spied at runtime: an identical cpd_als run
    with tracing enabled performs EXACTLY as many block_until_ready
    host syncs as with tracing disabled — spans never touch the
    device."""
    import jax

    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.cpd import cpd_als

    calls = {"n": 0}
    real = jax.block_until_ready

    def spy(x):
        calls["n"] += 1
        return real(x)

    tt = _small_tensor()
    counts = {}
    for enabled in (False, True):
        X = BlockedSparse.from_coo(tt, _opts())
        trace.reset()
        calls["n"] = 0
        monkeypatch.setattr(jax, "block_until_ready", spy)
        out = cpd_als(X, rank=3, opts=_opts(trace=enabled))
        monkeypatch.setattr(jax, "block_until_ready", real)
        counts[enabled] = calls["n"]
        assert np.isfinite(float(out.fit))
    assert counts[True] == counts[False]
    # and the enabled run actually recorded the driver's span tree
    names = {r["name"] for r in trace.spans()}
    assert {"cpd.als", "cpd.iter", "cpd.sweep",
            "cpd.fit_check"} <= names


def test_traced_cpd_iteration_spans_reconcile(tmp_path):
    """Acceptance shape: per-iteration spans nest under cpd.als, carry
    the fit at check iterations, sum to less than the root, and the
    summarizer reports them with guard spans separately attributed."""
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.cpd import cpd_als

    tt = _small_tensor()
    X = BlockedSparse.from_coo(tt, _opts())
    opts = _opts(trace=True, max_iterations=4, tolerance=0.0)
    cpd_als(X, rank=3, opts=opts)
    iters = trace.spans("cpd.iter")
    assert len(iters) == 4
    assert [r["args"]["it"] for r in iters] == [1, 2, 3, 4]
    assert all(isinstance(r["args"].get("fit"), float) for r in iters)
    root = trace.spans("cpd.als")[0]
    assert sum(r["dur"] for r in iters) <= root["dur"] * 1.001
    # guard spans exist and are attributed under the guard namespace
    assert trace.spans("cpd.guard.snapshot")
    assert trace.spans("cpd.guard.health_pack")
    out = tmp_path / "cpd.json"
    assert trace.write_chrome_trace(str(out))["ok"]
    s = trace.summarize_file(str(out))
    assert s["root_us"] >= root["dur"] * 1e6 * 0.99
    assert len(s["iters"]) == 4
    assert abs(s["iter_total_us"] / 1e6
               - sum(r["dur"] for r in iters)) < 0.05
    assert 0.0 <= s["guard_pct"] <= 100.0
    assert any(trace._is_guard(n) for n in s["names"])
    lines = trace.format_summary(s)
    text = "\n".join(lines)
    assert "guard overhead" in text and "iterations: 4 spans" in text


def test_health_pack_layout_carries_solve_reports():
    """The packed vector the fit check fetches: ``[fit, isfinite(U_0..
    U_{n-1}), isfinite(λ), isfinite(fit)]`` then each mode's solve
    report ``[pinv, cond, margin]`` in mode order; the verdict reads
    only the flags."""
    import jax.numpy as jnp

    from splatt_tpu.cpd import _health_pack, _health_verdict

    factors = [jnp.ones((4, 2)), jnp.ones((3, 2)), jnp.ones((5, 2))]
    solve = [jnp.asarray([0.0, 12.5, 4e3]), jnp.asarray([1.0, 3e5, 0.5]),
             jnp.asarray([0.0, 7.0, 9e4])]
    vec = np.asarray(_health_pack(factors, jnp.ones(2),
                                  jnp.asarray(0.25), solve))
    np.testing.assert_array_equal(
        vec, [0.25, 1, 1, 1, 1, 1, 0.0, 12.5, 4e3, 1.0, 3e5, 0.5,
              0.0, 7.0, 9e4])
    assert _health_verdict(vec, 3) == (0.25, [], True)
    # without reports (run_distributed_als) the tail is empty
    assert np.asarray(_health_pack(factors, jnp.ones(2),
                                   jnp.asarray(0.25))).size == 6


@pytest.mark.parametrize("path", ["cholesky", "pinv"])
def test_solve_counter_and_span_attributes(path):
    """Each fit check counts every mode's solve path into
    splatt_solve_total{path,mode}; the cpd.iter spans carry the sweep's
    largest condition estimate and smallest factorization margin, and
    the cpd.als root the call's counts, largest estimate and smallest
    margin.  An init whose first column is zero in every
    mode keeps that column zero, so every normal matrix has a zero row:
    singular, the pseudoinverse path."""
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.cpd import cpd_als

    tt = _small_tensor()
    X = BlockedSparse.from_coo(tt, _opts())
    rng = np.random.default_rng(4)
    init = [rng.random((d, 3)) for d in tt.dims]
    if path == "pinv":
        for U in init:
            U[:, 0] = 0.0
    cpd_als(X, rank=3, opts=_opts(trace=True, tolerance=0.0), init=init)
    snap = trace.metrics_snapshot()
    other = "pinv" if path == "cholesky" else "cholesky"
    for m in range(3):
        assert snap[f'splatt_solve_total{{mode="{m}",path="{path}"}}'] == 3
        assert f'splatt_solve_total{{mode="{m}",path="{other}"}}' not in snap
    root = trace.spans("cpd.als")[-1]["args"]
    assert root["solve"] == {path: [3, 3, 3], other: [0, 0, 0]}
    conds = [s["args"]["cond_max"] for s in trace.spans("cpd.iter")]
    assert len(conds) == 3 and all(c >= 1.0 for c in conds)
    assert root["cond_max"] == max(conds)
    margins = [s["args"]["margin_min"] for s in trace.spans("cpd.iter")]
    assert root["margin_min"] == min(margins)
    assert all((m > 1.0) == (path == "cholesky") for m in margins)
    _assert_prometheus_text(trace.metrics_text())


@pytest.mark.parametrize("guard", ["3", "0"])
def test_solve_reports_ride_the_fit_fetch(monkeypatch, guard):
    """The solve reports add no host sync.  With the sentinel on they
    ride its one packed fetch per check and count with tracing on or
    off; with it off, tracing on fetches them in the fit's one
    device_get, and tracing off leaves the fit fetch a scalar and
    counts nothing."""
    import jax

    import splatt_tpu.cpd as cpd_mod
    from splatt_tpu.blocked import BlockedSparse

    calls = {"pack": 0, "get": 0, "block": 0}
    real_pack, real_get = cpd_mod._health_pack, jax.device_get
    real_block = jax.block_until_ready

    def spy(key, real):
        def f(*a, **k):
            calls[key] += 1
            return real(*a, **k)
        return f

    counts, solves = {}, {}
    for enabled in (False, True):
        X = BlockedSparse.from_coo(_small_tensor(), _opts())
        trace.reset()
        trace.reset_metrics()
        for k in calls:
            calls[k] = 0
        monkeypatch.setenv("SPLATT_HEALTH_RETRIES", guard)
        monkeypatch.setattr(cpd_mod, "_health_pack", spy("pack", real_pack))
        monkeypatch.setattr(jax, "device_get", spy("get", real_get))
        monkeypatch.setattr(jax, "block_until_ready",
                            spy("block", real_block))
        cpd_mod.cpd_als(X, rank=3, opts=_opts(trace=enabled, tolerance=0.0))
        monkeypatch.undo()
        counts[enabled] = dict(calls)
        solves[enabled] = sum(v for k, v in trace.metrics_snapshot().items()
                              if k.startswith("splatt_solve_total"))
    assert counts[False]["block"] == counts[True]["block"]
    if guard != "0":
        assert counts[False] == counts[True]
        assert counts[False]["pack"] == 3
        assert solves == {False: 9, True: 9}
    else:
        assert counts[False]["pack"] == counts[True]["pack"] == 0
        assert (counts[False]["get"], counts[True]["get"]) == (0, 3)
        assert solves == {False: 0, True: 9}


def test_summarize_self_time_subtracts_children():
    evs = [
        {"name": "cpd.als", "ph": "X", "ts": 0, "dur": 1000,
         "args": {"sid": 1}},
        {"name": "cpd.iter", "ph": "X", "ts": 100, "dur": 600,
         "args": {"sid": 2, "parent": 1, "it": 1}},
        {"name": "cpd.guard.snapshot", "ph": "X", "ts": 150, "dur": 200,
         "args": {"sid": 3, "parent": 2}},
        {"name": "engine_demotion", "ph": "i", "ts": 300, "args": {}},
    ]
    s = trace.summarize(evs)
    assert s["names"]["cpd.als"]["self_us"] == 400
    assert s["names"]["cpd.iter"]["self_us"] == 400
    assert s["guard_self_us"] == 200
    assert s["root_us"] == 1000
    assert s["guard_pct"] == 20.0
    assert s["points"] == {"engine_demotion": 1}
    assert s["iters"] == [{"it": 1, "us": 600, "fit": None}]


# -- metrics registry -------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eE]+$")


def _assert_prometheus_text(text: str):
    for line in text.splitlines():
        if not line or line.startswith("# HELP ") \
                or line.startswith("# TYPE "):
            continue
        assert _PROM_LINE.match(line), f"bad Prometheus line: {line!r}"


def test_metrics_registry_discipline():
    with pytest.raises(KeyError):
        trace.metric_inc("splatt_not_a_metric")
    with pytest.raises(TypeError):
        trace.metric_set("splatt_events_total", 1.0)  # counter, not gauge
    with pytest.raises(TypeError):
        trace.metric_observe("splatt_serve_queue_depth", 1.0)


def test_metrics_text_parses_and_histograms_accumulate():
    trace.metric_inc("splatt_events_total", kind="engine_demotion")
    trace.metric_inc("splatt_events_total", kind="engine_demotion")
    trace.metric_set("splatt_serve_queue_depth", 3)
    for v in (0.05, 0.3, 7.0, 1e9):
        trace.metric_observe("splatt_job_seconds", v)
    text = trace.metrics_text()
    _assert_prometheus_text(text)
    assert 'splatt_events_total{kind="engine_demotion"} 2' in text
    assert "splatt_serve_queue_depth 3" in text
    assert 'splatt_job_seconds_bucket{le="+Inf"} 4' in text
    assert "splatt_job_seconds_count 4" in text
    # cumulative buckets are monotone
    cums = [int(m.group(1)) for m in re.finditer(
        r'splatt_job_seconds_bucket\{le="[^"]+"\} (\d+)', text)]
    assert cums == sorted(cums) and cums[-1] == 4


def test_event_metrics_are_always_on_spans_are_not():
    assert not trace.enabled()
    resilience.run_report().add("transient_retry", label="engine.xla",
                                attempt=1)
    resilience.run_report().add("health_rollback", iteration=2,
                                attempt=1)
    snap = trace.metrics_snapshot()
    assert snap['splatt_events_total{kind="transient_retry"}'] == 1.0
    assert snap["splatt_retries_total"] == 1.0
    assert snap["splatt_health_rollbacks_total"] == 1.0
    assert trace.points() == []  # points gated with the spans


def test_jit_counters_count_each_stage_once_per_compile():
    """A fresh jit counts one trace, one lower and one compile under its
    own program name (the jnp helpers it traces through count in it,
    not again); calling it again counts nothing."""
    import jax
    import jax.numpy as jnp

    trace.count_jit()
    x = jnp.ones(5)

    def _jit_probe(v):
        return v * 3.0 + 1.0

    f = jax.jit(_jit_probe)
    before = trace.metrics_snapshot()
    f(x)
    snap = trace.metrics_snapshot()
    new = {k: v for k, v in snap.items() if before.get(k) != v}
    for stage in ("trace", "lower", "compile"):
        key = f'{{program="_jit_probe",stage="{stage}"}}'
        assert new["splatt_jit_total" + key] == 1.0
        assert new["splatt_jit_seconds_total" + key] > 0.0
    assert sorted({k.split("{")[1] for k in new}) == [
        'program="_jit_probe",stage="compile"}',
        'program="_jit_probe",stage="lower"}',
        'program="_jit_probe",stage="trace"}']
    f(x)
    assert trace.metrics_snapshot() == snap
    assert trace.jit_totals()["_jit_probe"][0] == 3.0
    _assert_prometheus_text(trace.metrics_text())


def test_traced_cpd_records_its_compile_work():
    """The cpd.als root carries the compile work inside the run: jit_s
    and, per program, its stage runs and seconds."""
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.cpd import cpd_als

    X = BlockedSparse.from_coo(_small_tensor(), _opts())
    cpd_als(X, rank=3, opts=_opts(trace=True))
    root = trace.spans("cpd.als")[-1]["args"]
    assert root["jit_s"] > 0.0
    assert root["jit_s"] == pytest.approx(
        sum(s for _, s in root["jit"].values()))
    # the sweep is jit-wrapped afresh by each call: traced, lowered and
    # compiled inside it
    assert root["jit"]["sweep"][0] >= 3
    trace.reset()
    cpd_als(X, rank=3, opts=_opts(trace=False))
    assert trace.spans() == []


def test_metrics_per_job_isolation():
    with resilience.scope("tenant-a"):
        resilience.run_report().add("health_rollback", iteration=1,
                                    attempt=1)
    with resilience.scope("tenant-b"):
        resilience.run_report().add("engine_demotion", engine="unfused_pallas",
                                    failure_class="oom",
                                    shape_key="k", error="x")
    a_text = trace.metrics_text(job="tenant-a")
    _assert_prometheus_text(a_text)
    assert "tenant-b" not in a_text
    assert "splatt_health_rollbacks_total" in a_text
    assert "splatt_demotions_total" not in a_text
    a_snap = trace.metrics_snapshot(job="tenant-a")
    assert a_snap and all('job="tenant-a"' in k for k in a_snap)
    b_snap = trace.metrics_snapshot(job="tenant-b")
    assert b_snap and all('job="tenant-b"' in k for k in b_snap)
    assert not set(a_snap) & set(b_snap)


def test_write_metrics_atomic_and_classified(tmp_path):
    trace.metric_inc("splatt_events_total", kind="job_accepted")
    path = tmp_path / "metrics.prom"
    ev = trace.write_metrics(str(path))
    assert ev["kind"] == "metrics_snapshot" and ev["ok"]
    _assert_prometheus_text(path.read_text())
    assert not path.with_suffix(".prom.tmp").exists()
    # a write failure degrades classified, never raises
    bad = trace.write_metrics(str(tmp_path / "no" / "dir" / "m.prom"))
    assert bad["ok"] is False and bad["failure_class"]


# -- serve integration ------------------------------------------------------

def _serve_spec(jid, seed, **kw):
    spec = {"id": jid, "rank": 3, "iters": 3,
            "synthetic": {"dims": [14, 12, 10], "nnz": 500,
                          "seed": seed}}
    spec.update(kw)
    return spec


def test_serve_embeds_isolated_metrics_and_snapshots(tmp_path,
                                                     monkeypatch):
    """One NaN tenant + one clean neighbor through a real Server: each
    result embeds ONLY its own job's metric samples, and the daemon's
    Prometheus snapshot file parses and carries both."""
    from splatt_tpu import serve, tune

    monkeypatch.setenv("SPLATT_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    tune.set_cache_path(str(tmp_path / "tune_cache.json"))
    prom = tmp_path / "metrics.prom"
    monkeypatch.setenv("SPLATT_METRICS_PATH", str(prom))
    try:
        srv = serve.Server(str(tmp_path / "root"), workers=1)
        assert srv.metrics_path == str(prom)
        srv.submit(_serve_spec("nan-job", 0, health_retries=1,
                               faults="cpd.sweep:nan:iter=1"))
        srv.submit(_serve_spec("clean-job", 1))
        srv.run_once()
        srv.write_metrics_now()
    finally:
        tune.set_cache_path(None)
    nan_res = serve.read_result(str(tmp_path / "root"), "nan-job")
    clean_res = serve.read_result(str(tmp_path / "root"), "clean-job")
    assert nan_res is not None and clean_res is not None
    assert "metrics" in nan_res and "metrics" in clean_res
    assert all('job="nan-job"' in k for k in nan_res["metrics"])
    assert all('job="clean-job"' in k for k in clean_res["metrics"])
    # the NaN tenant's health evidence is in ITS cut only
    assert any("health" in k for k in nan_res["metrics"])
    assert not any("health" in k for k in clean_res["metrics"])
    assert any("splatt_serve_jobs_total" in k
               for k in nan_res["metrics"])
    # the daemon-level snapshot carries both tenants + the queue gauge
    text = prom.read_text()
    _assert_prometheus_text(text)
    assert 'job="nan-job"' in text and 'job="clean-job"' in text
    assert "splatt_serve_queue_depth" in text
    snaps = resilience.run_report().events("metrics_snapshot")
    assert snaps and snaps[-1]["ok"]


def test_serve_job_span_wraps_the_run(tmp_path, monkeypatch):
    from splatt_tpu import serve, tune

    monkeypatch.setenv("SPLATT_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    tune.set_cache_path(str(tmp_path / "tune_cache.json"))
    trace.set_enabled(True)
    try:
        srv = serve.Server(str(tmp_path / "root"), workers=1)
        srv.submit(_serve_spec("traced-job", 0))
        srv.run_once()
    finally:
        tune.set_cache_path(None)
    jobs = trace.spans("serve.job")
    assert len(jobs) == 1 and jobs[0]["job"] == "traced-job"
    # the tenant's cpd root nests under its serve.job span
    als = trace.spans("cpd.als")
    assert als and als[0]["parent"] == jobs[0]["sid"]
    assert als[0]["job"] == "traced-job"


# -- timers routed through the span layer -----------------------------------

def test_timer_brackets_become_spans():
    from splatt_tpu.utils.timers import TimerRegistry

    reg = TimerRegistry()
    trace.set_enabled(True)
    reg.start("cpd")
    reg.stop("cpd")
    with reg.time("mttkrp"):
        pass
    recs = trace.spans()
    assert {r["name"] for r in recs} == {"timer.cpd", "timer.mttkrp"}
    assert reg["cpd"] >= 0.0


def test_timer_report_folds_in_running_interval():
    """The double-report drift fix: a started-but-never-stopped timer
    reports its LIVE total, marked running — not the stale accumulated
    seconds of the last stop."""
    import time as _time

    from splatt_tpu.utils.timers import TimerRegistry

    reg = TimerRegistry()
    reg.start("cpd")
    _time.sleep(0.02)
    live = reg["cpd"]
    assert live >= 0.02  # the old .seconds read reported 0.0 here
    rep = reg.report(level=2)
    assert "cpd" in rep and "(running)" in rep
    reg.stop("cpd")
    assert reg["cpd"] >= live
    assert "(running)" not in reg.report(level=2)


# -- chaos --trace leg ------------------------------------------------------

@pytest.mark.parametrize("smoke", [True])
def test_chaos_smoke_trace_leg(tmp_path, smoke):
    """The tier-1 exporter soak (ISSUE 10 satellite): the chaos smoke
    under --trace passes its invariant INCLUDING the two trace legs —
    the export succeeded and every fired fault left matching point
    events on the trace — and the exported file summarizes."""
    from splatt_tpu import chaos

    out = tmp_path / "chaos_trace.json"
    res = chaos.run_chaos(smoke=smoke, trace_path=str(out))
    assert res.ok, res.violations
    assert res.fired and any(res.fired.values())
    assert out.exists()
    s = trace.summarize_file(str(out))
    assert s["spans"] > 0 and s["points"]
    # the point events on the trace include the faults' evidence kinds
    evidence = set()
    for kinds in chaos._EVIDENCE.values():
        evidence |= set(kinds)
    assert set(s["points"]) & evidence
    assert not trace.enabled()  # the soak disarmed on exit


def test_chaos_trace_leg_catches_a_dead_exporter(tmp_path, monkeypatch):
    """The leg is a real invariant: a failing export flips the chaos
    verdict to violated instead of passing silently."""
    from splatt_tpu import chaos

    out = tmp_path / "sub" / "never" / "chaos.json"  # unwritable path
    res = chaos.run_chaos(smoke=True, trace_path=str(out))
    assert not res.ok
    assert any("trace export" in v for v in res.violations)


# -- CLI: splatt trace verb -------------------------------------------------

def test_cli_trace_verb_summarizes(tmp_path, capsys):
    from splatt_tpu import cli

    trace.set_enabled(True)
    with trace.span("cpd.als", rank=2):
        with trace.span("cpd.iter", it=1):
            pass
    out = tmp_path / "t.json"
    trace.write_chrome_trace(str(out))
    trace.set_enabled(None)
    assert cli.main(["trace", str(out)]) == 0
    text = capsys.readouterr().out
    assert "top spans by self-time" in text
    assert "cpd.als" in text and "guard overhead" in text
    assert cli.main(["trace", str(out), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out.strip())
    assert payload["spans"] == 2
    # a missing file is a classified CLI error, not a traceback
    assert cli.main(["trace", str(tmp_path / "nope.json")]) == 1


def test_cli_cpd_trace_flag_exports(tmp_path, capsys):
    """`splatt cpd --trace out.json` end to end on a tiny tensor: the
    export lands, is perfetto-loadable, holds the driver's span tree,
    and `splatt trace` reads it back."""
    from splatt_tpu import cli
    from splatt_tpu.io import save

    tns = tmp_path / "tiny.tns"
    save(_small_tensor(), str(tns))
    out = tmp_path / "run_trace.json"
    rc = cli.main(["cpd", str(tns), "-r", "3", "-i", "3", "--nowrite",
                   "--trace", str(out)])
    assert rc == 0
    assert not trace.enabled()  # the CLI restored the default
    err = capsys.readouterr().err
    assert "trace written to" in err
    s = trace.summarize_file(str(out))
    assert {"cpd.als", "cpd.iter", "timer.total"} <= set(s["names"])
    assert len(s["iters"]) >= 1
    ev = resilience.run_report().events("trace_written")
    assert ev and ev[-1]["ok"]
