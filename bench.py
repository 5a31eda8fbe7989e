"""Benchmark driver: CPD-ALS sec/iteration (≙ BASELINE.json primary metric).

Runs rank-50 CPD-ALS on a NELL-2-shaped synthetic sparse tensor
(3-mode, power-law slice skew; NELL-2 itself — FROSTT, 77M nnz — is not
downloadable in this environment).  Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "sec/iter", "vs_baseline": N}

``vs_baseline`` is reference_sec_per_iter / ours (higher is better) when
a measured reference number exists in BASELINE_MEASURED.json; else 1.0.

Env knobs: SPLATT_BENCH_NNZ (default 20_000_000), SPLATT_BENCH_RANK (50),
SPLATT_BENCH_ITERS (3 timed iterations), SPLATT_BENCH_DTYPE
(float32 default; bfloat16 stores factors in bf16 with f32 accumulation),
SPLATT_BENCH_ENGINE (auto|pallas|xla — one-hot reduction engine; auto
lets dispatch probe Mosaic capability on TPU), SPLATT_BENCH_ALLOC
(allmode default — every mode gets its sorted layout; twomode/onemode
for the reference's memory-lean policies), SPLATT_BENCH_JIT
(auto|fused|phased — whole-sweep jit vs. per-phase jits; auto picks
phased on TPU, the cpd_als default there),
SPLATT_BENCH_SHAPE (nell2 default | enron4 — the 4-mode Enron-shaped
workload of BASELINE.md row 2), SPLATT_BENCH_SCENARIO (uniform default
| zipf:<a> | powerlaw | amazon-like — named nnz-distribution scenarios,
docs/layout-balance.md; non-uniform scenarios tag the metric string and
carry per-scenario imbalance stats; batched — the K-tenant fleet A/B,
docs/batched.md; predict — the prediction plane's hot-cache vs
direct-fenced-read request-latency A/B with p50/p99 + cache hit rate,
docs/predict.md, sized by SPLATT_BENCH_PREDICT_B entries/request and
SPLATT_BENCH_PREDICT_N requests/leg), SPLATT_BENCH_PATHS
("blocked,balanced,compact,tuned,stream" default — which
representations to measure; "balanced" is the load-balanced row:
nnz-packed fibers with long-fiber splitting (docs/layout-balance.md);
"compact" is the format-v2 row: local narrow indices +
segment encoding + bf16 storage (docs/format.md), timed with matching
bf16 factors; "tuned" runs the splatt-tune autotuner (warm plan cache
= zero measurement) and times the winning plan — now including format,
packing and reorder candidates — reported with the chosen
engine/nnz_block/scan_target/format under "tuned_plan"; "blocked"
alone skips the slow stream oracle on long-rank configs / scarce chip
time), SPLATT_BENCH_GUARD_AB (1 = time cpd_als with the health
sentinel on/off x donation on/off and record the legs under
"guard_ab" — ROADMAP open item 1's explicit guard-cost measurement),
SPLATT_BENCH_TRACE_AB (1 = time cpd_als with span recording
enabled-but-unexported vs off — plus a leg with the flight-recorder
ring armed — and record the legs under "trace_ab": the <2%
tracing-overhead budget of docs/observability.md, measured, for both
the tracing and the black-box steady states).

Bytes are reported per path from the ENCODED layouts
(bench_algs.mttkrp_bytes_encoded) PLUS each path's operand-prep decode
traffic (bench_algs.mttkrp_decode_bytes, per the engine its plan
names): ``model_gb_per_path`` carries the achieved bytes/iteration,
``decode_overhead`` the achieved/encoded ratio (~1.0 when the plan
consumes the compact streams natively — the per-chunk scan decode —
vs ~2x under operand-prep decode,
docs/format.md), ``format`` the achieved encoding summary, and the
regression gate compares the bytes too — a format OR engine change
that silently re-inflates traffic >10% fails ``--gate`` exactly like a
time regression.

Regression gate (ROADMAP open item 1): the fresh result is compared
against the newest prior ``BENCH_*.json`` (same metric only — unlike
workloads are never compared); any headline or per-path slowdown
beyond 10% is recorded as a ``bench_regression`` run-report event and
rides along in the JSON under ``"bench_regressions"``.  Run with
``--gate`` to turn regressions into a nonzero exit, so a perf PR ships
with a verdict, not just a number.  SPLATT_BENCH_PRIOR_DIR overrides
where priors are searched (tests).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from splatt_tpu.utils.env import apply_compile_cache

apply_compile_cache()


def synthetic_tensor(dims, nnz: int, seed: int = 0):
    """Power-law synthetic tensor: zipf-skewed indices per mode."""
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(seed)
    inds = np.empty((len(dims), nnz), dtype=np.int64)
    for m, d in enumerate(dims):
        # zipf-ish skew, cycled through the mode so every slice is nonempty
        raw = rng.zipf(1.3, size=nnz).astype(np.int64)
        inds[m] = (raw * 2654435761 + rng.integers(0, d, size=nnz)) % d
    vals = rng.random(nnz)
    return SparseTensor(inds, vals, dims)


# workload shapes: NELL-2-like 3-mode (flagship) and Enron-like 4-mode
# (exercises the n-mode generic paths, ≙ BASELINE.md rows 2-3)
SHAPES = {
    "nell2": (12092, 9184, 28818),
    "enron4": (6066, 5699, 244268, 1176),
}

# scenario shape presets (docs/layout-balance.md): power-law MODE SIZES
# (three orders of magnitude between dims) and an Amazon-reviews-like
# (user x item x word) shape at 1/100 scale
SCENARIO_SHAPES = {
    "powerlaw": (131072, 4096, 128),
    "amazon-like": (48212, 17742, 18051),
    # one near-dense mode (docs/dense.md): at the default densemode
    # nnz the mode-0 unfolding fills past the dense threshold while
    # the tensor stays sparse by COO standards — the workload class
    # the dense tile layout + MXU engine exist for
    "densemode": (24, 256, 512),
}

#: per-mode zipf exponents of the amazon-like scenario: reviews/user
#: and reviews/item are heavy power-laws, word frequency is zipfian
#: but flatter at this truncation
_AMAZON_EXPONENTS = (1.5, 1.5, 1.2)


def synthetic_nell2_like(nnz: int, seed: int = 0):
    """Power-law 3-mode tensor with NELL-2-ish dims (12k × 9k × 29k)."""
    return synthetic_tensor(SHAPES["nell2"], nnz, seed)


def synthetic_zipf(dims, nnz: int, a=1.5, seed: int = 0,
                   exponents=None):
    """GENUINELY zipf-skewed synthetic tensor: slice popularity per
    mode follows zipf(a) (the hottest slice holds a macroscopic share
    of all nonzeros), with hot slices scattered across the index space
    by a fixed permutation.  Unlike :func:`synthetic_tensor` — whose
    per-nnz uniform offset destroys the zipf head, leaving an
    effectively uniform tensor — this is the power-law input the
    balanced layouts exist for (docs/layout-balance.md)."""
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(seed)
    inds = np.empty((len(dims), nnz), dtype=np.int64)
    for m, d in enumerate(dims):
        am = float(exponents[m]) if exponents is not None else float(a)
        raw = (rng.zipf(am, size=nnz) - 1) % d
        inds[m] = rng.permutation(d)[raw]
    vals = rng.random(nnz)
    return SparseTensor(inds, vals, dims)


def scenario_tensor(scenario: str, shape: str, nnz: int, seed: int):
    """Build the bench tensor for a named scenario → (tt, desc, label).

    `desc` feeds the metric string; `label` is None for the default
    uniform scenario (metric string byte-identical to prior BENCH
    artifacts) and the scenario tag otherwise — the regression gate
    compares same-metric priors only, so scenarios never gate against
    unlike workloads."""
    names = {"nell2": "NELL-2-shaped", "enron4": "Enron-shaped"}
    if scenario in ("", "uniform", None):
        return (synthetic_tensor(SHAPES[shape], nnz, seed),
                names[shape], None)
    if scenario == "zipf" or scenario.startswith("zipf:"):
        # exact spellings only: a typo like "zipf1.8" must hit the
        # unknown-scenario error below, not silently bench exponent 1.5
        a = float(scenario.split(":", 1)[1]) if ":" in scenario else 1.5
        if not 1.0 < a <= 4.0:
            raise ValueError(f"zipf exponent must be in (1, 4], got {a}")
        label = f"zipf{a:g}"
        return (synthetic_zipf(SHAPES[shape], nnz, a=a, seed=seed),
                f"{names[shape]} {label}-skewed", label)
    if scenario == "powerlaw":
        return (synthetic_zipf(SCENARIO_SHAPES["powerlaw"], nnz, a=1.3,
                               seed=seed),
                "power-law-mode-size", "powerlaw")
    if scenario == "amazon-like":
        return (synthetic_zipf(SCENARIO_SHAPES["amazon-like"], nnz,
                               seed=seed, exponents=_AMAZON_EXPONENTS),
                "Amazon-like review-tensor", "amazon-like")
    if scenario == "densemode":
        return (synthetic_tensor(SCENARIO_SHAPES["densemode"], nnz, seed),
                "dense-mode", "densemode")
    raise ValueError(
        f"unknown SPLATT_BENCH_SCENARIO {scenario!r}; want uniform, "
        f"zipf:<a>, powerlaw, amazon-like, densemode, batched, "
        f"predict or ingest")


def _timing_cv(times) -> float:
    """Coefficient of variation of a timing sample (population stddev
    over mean; 0.0 on degenerate input) — the ONE dispersion
    definition every artifact path records and the CV-aware gate
    reads (ISSUE 14: four hand-rolled copies disagreeing someday is
    exactly how a noise rule rots)."""
    if not times:
        return 0.0
    mean = sum(times) / len(times)
    if mean <= 0:
        return 0.0
    var = sum((t - mean) ** 2 for t in times) / len(times)
    return (var ** 0.5) / mean


def _ref_sec_per_iter(measured: dict, shape: str, nnz: int, rank: int):
    """Reference sec/it for this exact workload from
    BASELINE_MEASURED.json, or None when it was never measured (then
    vs_baseline stays 1.0 rather than comparing unlike workloads)."""
    det = measured.get("details", {})
    if shape == "nell2":
        if rank == 200 and nnz == 20_000_000:
            return det.get("nell2_20m_rank200",
                           {}).get("reference_sec_per_iter")
        if rank == 50:
            return measured.get("cpd_sec_per_iter", {}).get(str(nnz))
    if shape == "enron4" and nnz == 5_000_000 and rank == 25:
        return det.get("enron4mode_5m_rank25",
                       {}).get("reference_sec_per_iter")
    return None


def _scaling_child(n: int) -> None:
    """One scaling-sweep measurement at `n` virtual CPU devices (the
    parent set XLA_FLAGS/JAX_PLATFORMS before this interpreter
    started).  Prints one ``SCALING {json}`` line.

    sec/iter is the median of the per-iteration wall clocks the
    distributed driver prints (each iteration is host-synced by the fit
    fetch at fit_check_every=1), skipping the first two iterations —
    they carry compile time.
    """
    import contextlib
    import io
    import re

    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.parallel.sharded import sharded_cpd_als

    nnz = int(os.environ.get("SPLATT_BENCH_NNZ", 2_000_000))
    rank = int(os.environ.get("SPLATT_BENCH_RANK", 16))
    iters = int(os.environ.get("SPLATT_BENCH_ITERS", 3))
    shape = os.environ.get("SPLATT_BENCH_SHAPE", "nell2")
    tt = synthetic_tensor(SHAPES.get(shape, SHAPES["nell2"]), nnz,
                          seed=1 if shape == "enron4" else 0)

    opts = Options(random_seed=7, verbosity=Verbosity.LOW,
                   val_dtype=np.float32, max_iterations=2 + iters,
                   tolerance=0.0, fit_check_every=1)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        sharded_cpd_als(tt, rank, opts=opts)
    times = [float(s) for s in
             re.findall(r"its =\s*\d+ \(([0-9.]+)s\)", buf.getvalue())]
    steady = sorted(times[2:]) or sorted(times)
    sec = steady[len(steady) // 2] if steady else None
    # dispersion rides every timing artifact (ISSUE 14 satellite): a
    # scaling point without its CV cannot be judged against the
    # 2x-CV noise rule later
    cv = round(_timing_cv(steady), 4) if steady else None
    print("SCALING " + json.dumps(
        dict(n_devices=n,
             sec_per_iter=round(sec, 5) if sec is not None else None,
             cv=cv, nnz=nnz, rank=rank)), flush=True)


def _run_scaling(devices) -> None:
    """Worker-count scaling sweep over virtual CPU devices (≙ the
    thread-scaling loop of the reference's bench verb,
    src/bench.c:84-117,95-101).  CPU only: one subprocess per device
    count (the virtual device count is fixed at interpreter start),
    each pinned to JAX_PLATFORMS=cpu, so no child ever needs the chip
    — this sweep measures the host, never a TPU.  Reports sec/iter and
    parallel efficiency vs the smallest count."""
    import subprocess

    results = {}
    for n in devices:
        env = dict(os.environ)
        env["SPLATT_SCALING_CHILD"] = str(n)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"])
        try:
            p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=1800)
            line = [l for l in p.stdout.splitlines()
                    if l.startswith("SCALING ")]
            results[n] = (json.loads(line[0][8:]) if line
                          else dict(error=p.stderr[-200:]))
        except subprocess.SubprocessError as e:
            results[n] = dict(error=str(e)[:200])
        print(f"bench: scaling n={n}: {results[n]}", file=sys.stderr,
              flush=True)
    n0 = devices[0]
    base = results.get(n0, {}).get("sec_per_iter")
    rows = []
    for n in devices:
        sec = results.get(n, {}).get("sec_per_iter")
        # a 0.0 measurement (iteration under the print resolution) is a
        # valid result, just unusable as a ratio denominator
        eff = (round(base * n0 / (n * sec), 3)
               if base and sec else (1.0 if n == n0 and base is not None
                                     else None))
        rows.append(dict(n_devices=n, sec_per_iter=sec, efficiency=eff))
    ok = [r for r in rows if r["sec_per_iter"] is not None]
    best = min(ok, key=lambda r: r["sec_per_iter"]) if ok else {}
    rec = dict(
        metric=f"CPD-ALS device-scaling sweep (fine decomposition, "
               f"virtual CPU devices {list(devices)})",
        value=best.get("sec_per_iter", 0.0),
        unit="sec/iter",
        vs_baseline=1.0,
        scaling=rows)
    if not ok:
        # a 0.0 "measurement" must not masquerade as a fast run
        rec["error"] = "all device counts failed; see stderr"
    print(json.dumps(rec, allow_nan=False), flush=True)
    if not ok:
        raise SystemExit(1)


def _guard_ab_legs(tt, rank: int, iters: int, bench_dtype, use_pallas,
                   alloc) -> dict:
    """Guard-cost A/B (ROADMAP open item 1): time the full cpd_als
    driver — the layer the guards actually live in; the raw-sweep
    timings above never execute them — with the health sentinel
    on/off x donation on/off, over the same blocked layouts.
    sec/iter per leg is the median of the per-iteration wall clocks
    cpd_als prints (first two skipped: compile), recorded under
    ``guard_ab`` in the bench JSON so the gate — and ROADMAP's r05
    investigation — can see guard cost explicitly instead of inferring
    it from cross-PR noise."""
    import contextlib
    import io
    import re

    from splatt_tpu import resilience
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.cpd import cpd_als

    X = BlockedSparse.from_coo(
        tt, Options(random_seed=7, verbosity=Verbosity.NONE,
                    val_dtype=bench_dtype, use_pallas=use_pallas,
                    block_alloc=alloc, autotune=False))
    legs = {}
    for retries in (3, 0):
        for donate in (True, False):
            label = (f"guard_{'on' if retries else 'off'}:"
                     f"donate_{'on' if donate else 'off'}")
            opts = Options(random_seed=7, verbosity=Verbosity.LOW,
                           val_dtype=bench_dtype, use_pallas=use_pallas,
                           block_alloc=alloc, autotune=False,
                           donate_sweep=donate,
                           max_iterations=iters + 2, tolerance=0.0,
                           fit_check_every=1)
            buf = io.StringIO()
            # a scope per leg: the health budget override rides the
            # scope (serve's mechanism), and leg demotions/events stay
            # isolated from the main bench run
            with resilience.scope(f"bench-{label}",
                                  health_retries=retries):
                with contextlib.redirect_stdout(buf):
                    cpd_als(X, rank, opts=opts)
            times = sorted(float(s) for s in re.findall(
                r"its =\s*\d+ \(([0-9.]+)s\)", buf.getvalue())[2:])
            legs[label] = (round(times[len(times) // 2], 4)
                           if times else None)
            if times:
                # dispersion rides every timing artifact (ISSUE 14
                # satellite): guard legs were the one path still
                # publishing bare medians
                legs[f"{label}_cv"] = round(_timing_cv(times), 4)
    on = legs.get("guard_on:donate_on")
    off = legs.get("guard_off:donate_on")
    # `on` may legitimately round to 0.0 at smoke scale — only a missing
    # leg (None) or a zero denominator drops the headline ratio
    if on is not None and off:
        legs["guard_overhead_pct"] = round((on / off - 1.0) * 100, 1)
    return legs


#: overhead budget of enabled-but-unexported tracing on the blocked
#: path (docs/observability.md): the trace A/B leg records the measured
#: percentage; beyond this the observability layer is taxing the hot
#: loop it exists to observe
TRACE_OVERHEAD_BUDGET_PCT = 2.0


def _trace_ab_legs(tt, rank: int, iters: int, bench_dtype, use_pallas,
                   alloc) -> dict:
    """Trace-overhead A/B (docs/observability.md): time the full
    cpd_als driver over the same blocked layouts with span recording
    ON (enabled but never exported — the steady-state cost of leaving
    SPLATT_TRACE=1 on in production), ON + the flight-recorder ring
    armed (the fleet-replica steady state: every finished span/point
    appended to the bounded black box), and OFF.  sec/iter per leg is
    the median of the per-iteration wall clocks cpd_als prints (first
    two skipped: compile); ``trace_overhead_pct`` /
    ``flight_overhead_pct`` are the headlines the <2%% budget is
    judged against."""
    import contextlib
    import io
    import re
    import tempfile

    from splatt_tpu import trace
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.cpd import cpd_als

    X = BlockedSparse.from_coo(
        tt, Options(random_seed=7, verbosity=Verbosity.NONE,
                    val_dtype=bench_dtype, use_pallas=use_pallas,
                    block_alloc=alloc, autotune=False))
    legs = {}
    # ALTERNATE the legs over two rounds and pool each label's
    # per-iteration samples: the effect under test (a few µs of span
    # bookkeeping per iteration) is far below this host's run-to-run
    # drift, and interleaving cancels slow drift that a
    # one-leg-then-the-other order would book entirely to one side
    samples = {"trace_off": [], "trace_on": [], "trace_flight": []}
    with tempfile.TemporaryDirectory(prefix="splatt-flight-ab-") as td:
        for _ in range(2):
            for label, tr in (("trace_off", False), ("trace_on", True),
                              ("trace_flight", True)):
                opts = Options(random_seed=7, verbosity=Verbosity.LOW,
                               val_dtype=bench_dtype,
                               use_pallas=use_pallas,
                               block_alloc=alloc, autotune=False,
                               trace=tr, max_iterations=iters + 2,
                               tolerance=0.0, fit_check_every=1)
                if label == "trace_flight":
                    trace.set_flight(f"{td}/flight.jsonl")
                before = len(trace.spans())
                buf = io.StringIO()
                try:
                    with contextlib.redirect_stdout(buf):
                        cpd_als(X, rank, opts=opts)
                finally:
                    if label == "trace_flight":
                        trace.set_flight(None)
                if label == "trace_on":
                    # enabled-but-unexported: report the leg's span
                    # count as a delta, and LEAVE the recorder alone —
                    # a caller exporting the whole process's trace
                    # (SPLATT_TRACE=1) keeps its earlier spans; ~100
                    # extra records are noise
                    legs["trace_spans"] = len(trace.spans()) - before
                samples[label] += [float(s) for s in re.findall(
                    r"its =\s*\d+ \(([0-9.]+)s\)", buf.getvalue())[2:]]
    for label, ts in samples.items():
        ts.sort()
        legs[label] = (round(ts[len(ts) // 2], 4) if ts else None)
        if ts:
            legs[f"{label}_cv"] = round(_timing_cv(ts), 4)
    on, off = legs.get("trace_on"), legs.get("trace_off")
    if on is not None and off:
        legs["trace_overhead_pct"] = round((on / off - 1.0) * 100, 1)
        legs["budget_pct"] = TRACE_OVERHEAD_BUDGET_PCT
    flight = legs.get("trace_flight")
    if flight is not None and off:
        legs["flight_overhead_pct"] = round((flight / off - 1.0) * 100,
                                            1)
    return legs


#: slowdown threshold of the regression gate: >10% beyond the newest
#: prior on the same metric flags a bench_regression
REGRESSION_THRESHOLD = 0.10

#: coefficient-of-variation ceiling for a trustworthy timing
#: comparison: a would-be regression whose CV (either side) exceeds
#: this is recorded as a ``bench_noisy`` warning instead of failing
#: the --gate (measured run-to-run spread on the shared CPU host is
#: ±7%; 0.15 leaves headroom without swallowing real 10% slips)
NOISE_CV = 0.15

#: the ROADMAP variance note, made the gate's default: a single-run
#: timing delta smaller than this multiple of the measured CV (either
#: side) is noise, whatever the absolute CV — r07/r08 CVs ran
#: 0.10-0.55 on this shared host, where a "12% regression" against a
#: 10%-CV distribution is one draw, not a verdict
CV_NOISE_MULT = 2.0


def _prior_bench_record(search_dir: str, metric: str = None):
    """(filename, parsed-record) of the newest prior ``BENCH_*.json``
    holding a usable bench record — the newest SAME-METRIC one when
    `metric` is given, so a different workload benched in between
    cannot silently disable the gate against an older comparable
    prior.  Newest = highest name in sort order (the drivers write
    BENCH_r01, BENCH_r02, ...); files without a usable record (or CPU
    side-artifacts without "parsed") are skipped rather than trusted."""
    import glob

    candidates = sorted(glob.glob(os.path.join(search_dir,
                                               "BENCH_*.json")),
                        reverse=True)
    for path in candidates:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        rec = data.get("parsed") if isinstance(data, dict) else None
        if rec is None and isinstance(data, dict) and "value" in data:
            rec = data  # a bare bench record is also a valid prior
        if not (isinstance(rec, dict) and rec.get("value")
                and rec.get("unit") == "sec/iter"):
            continue
        if metric is not None and rec.get("metric") != metric:
            continue  # unlike workload: keep searching older priors
        return os.path.basename(path), rec
    return None


def _bench_regressions(rec: dict, prior: dict,
                       threshold: float = REGRESSION_THRESHOLD,
                       noise_cv: float = None) -> list:
    """Slowdowns beyond `threshold` between a fresh bench record and a
    prior one ON THE SAME METRIC: the headline value, plus every path
    both runs timed (per-path medians localize a regression to the
    representation that slipped, even when a different path holds the
    headline).  Pure function — the gate's unit under test.

    Variance hygiene (ISSUE 8 satellite, made CV-aware by default in
    ISSUE 14): a TIMING slowdown is marked ``noisy=True`` — the gate
    turns it into a loud ``bench_noisy`` warning instead of a hard
    failure — when either side's recorded coefficient of variation
    exceeds `noise_cv`, OR when the delta itself is smaller than
    ``CV_NOISE_MULT`` × that CV (the ROADMAP note: single-run deltas
    under ~2× the CV are one draw from the timing distribution, not a
    verdict).  Bytes/balance legs are deterministic and never noisy;
    priors without a recorded cv gate normally (noise cannot be
    claimed, only measured).
    """
    if noise_cv is None:
        noise_cv = NOISE_CV
    out = []
    if rec.get("metric") != prior.get("metric"):
        return out  # unlike workloads: no comparison, no verdict
    mine = rec.get("timing_stats") or {}
    theirs = prior.get("timing_stats") or {}

    def cv_of(stats: dict, path: str):
        try:
            v = (stats.get(path) or {}).get("cv")
            return float(v) if v is not None else None
        except (TypeError, ValueError):
            return None

    best = rec.get("best_path")
    pairs = [("headline", rec.get("value"), prior.get("value"),
              cv_of(mine, best) if best else None,
              cv_of(theirs, prior.get("best_path"))
              if prior.get("best_path") else None)]
    for path in sorted(set(mine) & set(theirs)):
        pairs.append((path, (mine[path] or {}).get("median"),
                      (theirs[path] or {}).get("median"),
                      cv_of(mine, path), cv_of(theirs, path)))
    # achieved bytes/iteration per path (the encoded-format model,
    # docs/format.md): a format that silently re-inflates traffic is a
    # regression even when the clock has not caught it yet
    mine_gb = rec.get("model_gb_per_path") or {}
    theirs_gb = prior.get("model_gb_per_path") or {}
    for path in sorted(set(mine_gb) & set(theirs_gb)):
        pairs.append((f"bytes:{path}", mine_gb[path], theirs_gb[path],
                      None, None))
    # modeled flops per path (docs/dense.md): work amplification the
    # bytes model cannot see — a dispatch change that silently
    # re-inflates padded MACs is a regression; deterministic, never
    # noisy (the bytes-legs contract)
    mine_f = rec.get("model_gflops_per_path") or {}
    theirs_f = prior.get("model_gflops_per_path") or {}
    for path in sorted(set(mine_f) & set(theirs_f)):
        pairs.append((f"flops:{path}", mine_f[path], theirs_f[path],
                      None, None))
    # achieved balance per path (docs/layout-balance.md): the one-hot
    # work amplification of the built layouts — a packing/reorder
    # change that silently re-inflates padded work is a regression
    # like a bytes inflation, deterministic and never noisy
    mine_b = (rec.get("imbalance") or {}).get("per_path") or {}
    theirs_b = (prior.get("imbalance") or {}).get("per_path") or {}
    for path in sorted(set(mine_b) & set(theirs_b)):
        pairs.append((f"balance:{path}",
                      (mine_b[path] or {}).get("work_amp"),
                      (theirs_b[path] or {}).get("work_amp"),
                      None, None))
    for path, sec, prior_sec, cv_a, cv_b in pairs:
        if not sec or not prior_sec:
            continue
        if sec > prior_sec * (1.0 + threshold):
            entry = dict(path=path, sec=round(float(sec), 4),
                         prior_sec=round(float(prior_sec), 4),
                         pct=round((sec / prior_sec - 1.0) * 100, 1))
            cv = max((c for c in (cv_a, cv_b) if c is not None),
                     default=None)
            if cv is not None and (cv > noise_cv
                                   or (sec / prior_sec - 1.0)
                                   < CV_NOISE_MULT * cv):
                entry["noisy"] = True
                entry["cv"] = round(cv, 4)
            out.append(entry)
    return out


def _apply_regression_gate(rec: dict) -> list:
    """Compare `rec` against the newest prior and record every
    regression (run-report event + stderr line + the record itself
    under ``bench_regressions``).  Returns the regression list."""
    from splatt_tpu import resilience

    search_dir = (os.environ.get("SPLATT_BENCH_PRIOR_DIR")
                  or os.path.dirname(os.path.abspath(__file__)))
    prior = _prior_bench_record(search_dir, metric=rec.get("metric"))
    if prior is None:
        print("bench: no prior BENCH_*.json with this metric found; "
              "regression gate has no baseline", file=sys.stderr,
              flush=True)
        return []
    fname, prec = prior
    found = _bench_regressions(rec, prec)
    regs = [r for r in found if not r.get("noisy")]
    noisy = [r for r in found if r.get("noisy")]
    for r in regs:
        resilience.record_bench_regression(prior_file=fname, **r)
        print(f"bench: REGRESSION on {r['path']}: {r['sec']}s vs "
              f"{r['prior_sec']}s in {fname} (+{r['pct']}%)",
              file=sys.stderr, flush=True)
    for r in noisy:
        # a slowdown measured through a noisy distribution is a
        # WARNING, not a verdict (bench_noisy event; the gate ignores
        # it) — ROADMAP open item 1's "regressions are verdicts".
        # Name the ACTUAL suppression rule: the absolute CV ceiling,
        # or the under-2x-CV delta rule (whichever fired)
        if r["cv"] > NOISE_CV:
            threshold, why = NOISE_CV, f"CV {r['cv']} > {NOISE_CV}"
        else:
            threshold = round(CV_NOISE_MULT * r["cv"], 4)
            why = (f"delta {r['pct']}% < {CV_NOISE_MULT:g}x CV "
                   f"{r['cv']} (= {threshold * 100:g}%)")
        resilience.record_bench_noisy(
            path=r["path"], cv=r["cv"], threshold=threshold,
            sec=r["sec"], prior_sec=r["prior_sec"], prior_file=fname)
        print(f"bench: NOISY comparison on {r['path']}: {r['sec']}s vs "
              f"{r['prior_sec']}s in {fname} (+{r['pct']}%) but {why} "
              f"— warning, not gated",
              file=sys.stderr, flush=True)
    if regs or noisy:
        rec["bench_prior"] = fname
    if regs:
        rec["bench_regressions"] = regs
    if noisy:
        rec["bench_noisy"] = noisy
    if not regs:
        print(f"bench: no gated >{int(REGRESSION_THRESHOLD * 100)}% "
              f"regression vs {fname}", file=sys.stderr, flush=True)
    return regs


def _run_batched_bench(gate: bool) -> None:
    """SPLATT_BENCH_SCENARIO=batched (docs/batched.md): the fleet
    shape — K small SAME-REGIME tensors (dims/nnz varied within one
    bucket, the realistic many-tenant mix) decomposed by (a) a
    sequential cpd_als loop, one dispatch + compile per tensor, and
    (b) ONE vmapped cpd_als_batched.  Reports amortized per-tensor
    s/iter for both arms (median over reps, with CVs), compile-count
    evidence, and a CV-aware in-run verdict: under --gate a batched
    arm SLOWER than sequential beyond 2x the worse CV fails the run;
    a delta inside the noise floor is a bench_noisy warning, never a
    verdict (the r07/r08 lesson)."""
    import jax
    import jax.numpy as jnp

    from splatt_tpu import resilience
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.cpd import cpd_als, cpd_als_batched

    K = int(os.environ.get("SPLATT_BENCH_BATCH_K") or 32)
    nnz = int(os.environ.get("SPLATT_BENCH_NNZ") or 4000)
    rank = int(os.environ.get("SPLATT_BENCH_RANK") or 8)
    iters = int(os.environ.get("SPLATT_BENCH_ITERS") or 6)
    reps = 3
    rng = np.random.default_rng(7)
    base_dims = (48, 40, 36)
    tensors = []
    def in_bucket(v: int, frac: int) -> int:
        # jitter downward but stay inside v's power-of-two regime
        # bucket (bit_length must not drop)
        lo = max(v - v // frac, (1 << (int(v).bit_length() - 1)) + 1)
        return int(rng.integers(lo, v + 1))

    for i in range(K):
        # varied within the regime bucket: same per-mode bit_length,
        # same nnz bucket — what real tenant mixes look like, and what
        # makes the sequential loop pay K compiles where the batch
        # pays one (each distinct shape is its own XLA program)
        dims = tuple(in_bucket(d, 5) for d in base_dims)
        tensors.append(synthetic_tensor(dims, in_bucket(nnz, 4), seed=i))
    seeds = list(range(100, 100 + K))
    opts = lambda seed: Options(  # noqa: E731 - tiny per-slot factory
        random_seed=seed, max_iterations=iters, tolerance=0.0,
        verbosity=Verbosity.NONE, autotune=False)

    def seq_leg():
        t0 = time.perf_counter()
        fits = []
        for i, tt in enumerate(tensors):
            bs = BlockedSparse.compile(tt, opts(seeds[i]), rank=rank)
            out = cpd_als(bs, rank=rank, opts=opts(seeds[i]))
            fits.append(float(out.fit))
        return time.perf_counter() - t0, fits

    compiles = []

    def batched_leg():
        t0 = time.perf_counter()
        res = cpd_als_batched(tensors, rank=rank, opts=opts(seeds[0]),
                              seeds=seeds)
        compiles.append(res.compiles)
        return time.perf_counter() - t0, res.fits

    # one discarded warmup pass: first-touch library/tracing overhead
    # (imports, layout machinery) lands outside the timed reps.  The
    # per-run compile costs the A/B is ABOUT still recur inside every
    # timed rep — each cpd_als call rebuilds its jitted sweep (K
    # programs sequentially, one vmapped program batched).
    print("bench: batched warmup pass", file=sys.stderr, flush=True)
    seq_leg()
    batched_leg()
    compiles.clear()
    # alternating legs so drift on a shared host hits both arms alike
    seq_times, bat_times = [], []
    fits_seq = fits_bat = None
    for r in range(reps):
        s, fits_seq = seq_leg()
        b, fits_bat = batched_leg()
        seq_times.append(s)
        bat_times.append(b)
        print(f"bench: batched rep {r + 1}/{reps}: sequential "
              f"{s:.2f}s, batched {b:.2f}s", file=sys.stderr,
              flush=True)
    denom = K * iters
    seq_amort = float(np.median(seq_times)) / denom
    bat_amort = float(np.median(bat_times)) / denom
    cv_seq = _timing_cv(seq_times)
    cv_bat = _timing_cv(bat_times)
    max_fit_dev = float(max(abs(a - b)
                            for a, b in zip(fits_seq, fits_bat)))
    platform = jax.devices()[0].platform
    rec = {
        "metric": f"batched fleet CPD amortized sec/tensor-iter, "
                  f"k={K} same-regime synthetic ({nnz} nnz bucket, "
                  f"rank {rank}, f32) on {platform}; baseline: "
                  f"sequential cpd_als loop, same tensors",
        "value": round(bat_amort, 5),
        "unit": "sec/tensor-iter",
        "batched": {
            "k": K, "iters": iters, "reps": reps,
            "seq_s_per_tensor_iter": round(seq_amort, 5),
            "batched_s_per_tensor_iter": round(bat_amort, 5),
            "speedup": round(seq_amort / max(bat_amort, 1e-12), 2),
            "cv_seq": round(cv_seq, 4), "cv_batched": round(cv_bat, 4),
            "batched_compiles_per_run": max(compiles),
            "seq_sweep_builds_per_run": K,
            "max_fit_dev": round(max_fit_dev, 6),
        },
    }
    # CV-aware in-run verdict (the same noise rule the prior-artifact
    # gate applies): a delta smaller than 2x the worse CV is noise
    noise = 2.0 * max(cv_seq, cv_bat)
    delta = (bat_amort - seq_amort) / max(seq_amort, 1e-12)
    if delta > 0 and delta <= noise:
        resilience.record_bench_noisy(
            "batched", cv=max(cv_seq, cv_bat), threshold=noise,
            sec=bat_amort, prior_sec=seq_amort,
            prior_file="(in-run sequential baseline)")
        rec["batched"]["verdict"] = "noisy"
    elif delta > 0:
        resilience.record_bench_regression(
            "batched", sec=bat_amort, prior_sec=seq_amort,
            pct=100 * delta, prior_file="(in-run sequential baseline)")
        rec["batched"]["verdict"] = "fail"
    else:
        rec["batched"]["verdict"] = ("pass" if -delta > noise
                                     else "pass-within-noise")
    regressions = []
    try:
        regressions = _apply_regression_gate(rec)
    except Exception as e:
        print(f"bench: regression gate skipped "
              f"({resilience.classify_failure(e).value}: {e})",
              file=sys.stderr, flush=True)
    print(json.dumps(rec))
    if gate and (rec["batched"]["verdict"] == "fail" or regressions):
        raise SystemExit(1)


def _run_predict_bench(gate: bool) -> None:
    """SPLATT_BENCH_SCENARIO=predict (docs/predict.md): the prediction
    plane's request latency — N requests, each a B-entry batched
    reconstruct plus one top-k slice scan against a committed model
    generation, served (a) through the hot-factor cache (the steady
    state) and (b) through the direct fenced read EVERY request (the
    cache-miss/degrade arm: stamp read + checkpoint load + sha verify
    per request).  Reports p50/p99 per stage and per arm, the achieved
    cache hit rate, and a CV-aware in-run verdict: under --gate a hot
    arm slower than the direct read beyond 2x the worse CV fails the
    run — a cache that does not beat re-reading the store from disk is
    pure overhead."""
    import tempfile

    from splatt_tpu import predict, resilience
    from splatt_tpu.cpd import _save_checkpoint

    rank = int(os.environ.get("SPLATT_BENCH_RANK") or 16)
    B = int(os.environ.get("SPLATT_BENCH_PREDICT_B") or 256)
    N = int(os.environ.get("SPLATT_BENCH_PREDICT_N") or 120)
    topk = 10
    reps = 3
    dims = (2048, 1024, 512)
    rng = np.random.default_rng(11)
    factors = [np.asarray(rng.standard_normal((d, rank)),
                          dtype=np.float32) for d in dims]
    lam = np.asarray(rng.uniform(0.5, 2.0, rank), dtype=np.float32)
    root = tempfile.mkdtemp(prefix="splatt-bench-predict-")
    ckdir = os.path.join(root, "ckpt")
    os.makedirs(ckdir, exist_ok=True)
    _save_checkpoint(os.path.join(ckdir, "m.npz"), factors, lam,
                     0, 0.9)
    gen = predict.advance_generation(ckdir, "m", factors, lam)
    coords = np.stack([rng.integers(0, d, size=N * B) for d in dims],
                      axis=1)

    cache = predict.HotFactorCache(8)
    hit_miss = [0, 0]

    def hot_entry():
        entry = cache.get("m", gen)
        if entry is None:
            hit_miss[1] += 1
            entry = predict.load_model_generation(ckdir, "m")
            cache.put("m", gen, entry)
        else:
            hit_miss[0] += 1
        return entry

    def leg(lookup):
        # per-request stage latencies: (lookup, reconstruct, top-k)
        lat = {"lookup": [], "reconstruct": [], "topk": [],
               "request": []}
        for i in range(N):
            req = coords[i * B:(i + 1) * B]
            t0 = time.perf_counter()
            entry = lookup()
            t1 = time.perf_counter()
            predict.reconstruct_entries(entry["factors"],
                                        entry["lam"], req)
            t2 = time.perf_counter()
            predict.top_k_slice(entry["factors"], entry["lam"],
                                {1: int(req[0][1]), 2: int(req[0][2])},
                                0, topk)
            t3 = time.perf_counter()
            lat["lookup"].append(t1 - t0)
            lat["reconstruct"].append(t2 - t1)
            lat["topk"].append(t3 - t2)
            lat["request"].append(t3 - t0)
        return lat

    def direct_entry():
        return predict.load_model_generation(ckdir, "m")

    print("bench: predict warmup pass", file=sys.stderr, flush=True)
    leg(hot_entry)
    leg(direct_entry)
    hit_miss[0] = hit_miss[1] = 0
    # alternating legs so drift on a shared host hits both arms alike
    hot_legs, direct_legs = [], []
    for r in range(reps):
        hot_legs.append(leg(hot_entry))
        direct_legs.append(leg(direct_entry))
        print(f"bench: predict rep {r + 1}/{reps}: hot p99 "
              f"{1e3 * np.percentile(hot_legs[-1]['request'], 99):.3f}"
              f"ms, direct p99 "
              f"{1e3 * np.percentile(direct_legs[-1]['request'], 99):.3f}"
              f"ms", file=sys.stderr, flush=True)

    def pcts(legs, key):
        allv = np.concatenate([lg[key] for lg in legs])
        return (round(float(np.percentile(allv, 50)) * 1e3, 4),
                round(float(np.percentile(allv, 99)) * 1e3, 4))

    hot_p50, hot_p99 = pcts(hot_legs, "request")
    dir_p50, dir_p99 = pcts(direct_legs, "request")
    rec_p50, rec_p99 = pcts(hot_legs, "reconstruct")
    top_p50, top_p99 = pcts(hot_legs, "topk")
    # the CV legs for the noise rule: per-rep median request latency
    cv_hot = _timing_cv([float(np.median(lg["request"]))
                         for lg in hot_legs])
    cv_dir = _timing_cv([float(np.median(lg["request"]))
                         for lg in direct_legs])
    hit_rate = hit_miss[0] / max(hit_miss[0] + hit_miss[1], 1)
    rec = {
        "metric": f"predict request p99 latency (hot-cache arm), "
                  f"B={B} entries/request + top-{topk}, rank {rank} "
                  f"model dims {dims}, f32, host-side numpy",
        "value": hot_p99,
        "unit": "ms/request p99",
        "predict": {
            "requests_per_leg": N, "entries_per_request": B,
            "reps": reps, "cache_hit_rate": round(hit_rate, 4),
            "hot_p50_ms": hot_p50, "hot_p99_ms": hot_p99,
            "direct_p50_ms": dir_p50, "direct_p99_ms": dir_p99,
            "reconstruct_p50_ms": rec_p50,
            "reconstruct_p99_ms": rec_p99,
            "topk_p50_ms": top_p50, "topk_p99_ms": top_p99,
            "cv_hot": round(cv_hot, 4), "cv_direct": round(cv_dir, 4),
        },
    }
    # CV-aware in-run verdict (the same noise rule as the prior gate):
    # the hot arm must not lose to re-reading the store per request
    hot_med = float(np.median([np.median(lg["request"])
                               for lg in hot_legs]))
    dir_med = float(np.median([np.median(lg["request"])
                               for lg in direct_legs]))
    noise = 2.0 * max(cv_hot, cv_dir)
    delta = (hot_med - dir_med) / max(dir_med, 1e-12)
    if delta > 0 and delta <= noise:
        resilience.record_bench_noisy(
            "predict", cv=max(cv_hot, cv_dir), threshold=noise,
            sec=hot_med, prior_sec=dir_med,
            prior_file="(in-run direct-read baseline)")
        rec["predict"]["verdict"] = "noisy"
    elif delta > 0:
        resilience.record_bench_regression(
            "predict", sec=hot_med, prior_sec=dir_med,
            pct=100 * delta, prior_file="(in-run direct-read baseline)")
        rec["predict"]["verdict"] = "fail"
    else:
        rec["predict"]["verdict"] = ("pass" if -delta > noise
                                     else "pass-within-noise")
    regressions = []
    try:
        regressions = _apply_regression_gate(rec)
    except Exception as e:
        print(f"bench: regression gate skipped "
              f"({resilience.classify_failure(e).value}: {e})",
              file=sys.stderr, flush=True)
    print(json.dumps(rec))
    if gate and (rec["predict"]["verdict"] == "fail" or regressions):
        raise SystemExit(1)


def _run_ingest_bench(gate: bool) -> None:
    """SPLATT_BENCH_SCENARIO=ingest (docs/ingest.md): the streaming
    ingest plane end-to-end.  (a) Throughput: a synthetic mixed feed
    (vocab-keyed mode 0, ~1% malformed rows so the quarantine path's
    cost is in the number) ingested fresh per rep through the full
    exactly-once pipeline — parse + vocab delta + segment publish +
    fsync'd journal append per chunk — reported as records/sec with
    the headline as wall ms per 1k records (lower-better, so the
    regression gate's slowdown rule reads it directly).  (b)
    Freshness: a serve ``ingest`` job chaining ``update`` jobs off a
    committed base model; the commit->update-observe lag
    (splatt_ingest_update_lag_seconds) p95 is the live-feed freshness
    number.  The rep CV rides ``timing_stats`` so the 2x-CV noise
    rule applies to the throughput comparison."""
    import shutil
    import tempfile

    from splatt_tpu import ingest, resilience, serve

    records = int(os.environ.get("SPLATT_BENCH_INGEST_RECORDS")
                  or 60_000)
    chunk = int(os.environ.get("SPLATT_BENCH_INGEST_CHUNK") or 5_000)
    reps = 3
    root = tempfile.mkdtemp(prefix="splatt-bench-ingest-")
    src = os.path.join(root, "stream.tns")
    rng = np.random.default_rng(5)
    us = rng.integers(0, 4096, size=records)
    ii = rng.integers(0, 512, size=records)
    kk = rng.integers(0, 64, size=records)
    vv = rng.random(records) + 0.1
    bad = 0
    with open(src, "w") as f:
        for n in range(records):
            if n % 101 == 13:
                f.write("malformed row\n")
                bad += 1
            else:
                f.write(f"u{us[n]} {ii[n]} {kk[n]} {vv[n]:.6f}\n")
    print(f"bench: ingest stream {records} records ({bad} malformed), "
          f"chunk {chunk}", file=sys.stderr, flush=True)

    def leg(tag):
        dest = os.path.join(root, f"dest-{tag}")
        t0 = time.perf_counter()
        summary = ingest.ingest_stream(src, dest, fmt="tns",
                                       chunk_records=chunk)
        sec = time.perf_counter() - t0
        assert summary["status"] == "converged", summary
        assert summary["quarantined"] == bad, summary
        shutil.rmtree(dest, ignore_errors=True)
        return sec

    print("bench: ingest warmup pass", file=sys.stderr, flush=True)
    leg("warmup")
    secs = []
    for r in range(reps):
        secs.append(leg(f"r{r}"))
        print(f"bench: ingest rep {r + 1}/{reps}: "
              f"{records / secs[-1]:,.0f} records/s",
              file=sys.stderr, flush=True)
    med = float(np.median(secs))
    cv = _timing_cv(secs)
    rps = records / med
    ms_per_krec = 1e3 * med / (records / 1000.0)

    # freshness leg: serve ingest job chaining updates off a base
    # model — each update result carries the commit->observe lag the
    # splatt_ingest_update_lag_seconds histogram records
    srv = serve.Server(os.path.join(root, "serve"), workers=1)
    dims = [48, 32, 16]
    base = {"id": "base", "rank": 4, "iters": 6, "seed": 7,
            "checkpoint_every": 2,
            "synthetic": {"dims": dims, "nnz": 2000, "seed": 3}}
    lags = []
    if srv.submit(base)["state"] == serve.ACCEPTED:
        srv.run_once()
        usrc = os.path.join(root, "updates.tns")
        un = 4000
        with open(usrc, "w") as f:
            for n in range(un):
                f.write(f"{rng.integers(0, dims[0])} "
                        f"{rng.integers(0, dims[1])} "
                        f"{rng.integers(0, dims[2])} "
                        f"{rng.random() + 0.1:.5f}\n")
        spec = {"id": "ing", "kind": "ingest", "source": usrc,
                "base": "base", "dims": dims,
                "chunk_records": un // 8, "update_every": 2}
        if srv.submit(spec)["state"] == serve.ACCEPTED:
            srv.run_once()
            res = serve.read_result(srv.root, "ing") or {}
            for uid in res.get("updates", []):
                ur = serve.read_result(srv.root, uid) or {}
                lag = (ur.get("update") or {}).get("ingest_lag_s")
                if ur.get("status") == "converged" and lag is not None:
                    lags.append(float(lag))
    lag_p95 = (round(float(np.percentile(lags, 95)), 4)
               if lags else None)
    print(f"bench: ingest {rps:,.0f} records/s (cv {cv:.4f}); "
          f"update lag p95 "
          f"{'n/a' if lag_p95 is None else f'{lag_p95}s'} over "
          f"{len(lags)} update(s)", file=sys.stderr, flush=True)

    rec = {
        "metric": f"streaming ingest wall ms per 1k records, mixed "
                  f"vocab+numeric 4-col feed with ~1% quarantined, "
                  f"{records} records chunk {chunk}, host-side numpy "
                  f"+ fsync'd exactly-once commits",
        "value": round(ms_per_krec, 4),
        "unit": "ms/krec",
        "timing_stats": {"ingest_stream": {"median": round(med, 4),
                                           "cv": round(cv, 4)}},
        "ingest": {
            "records": records, "malformed": bad,
            "chunk_records": chunk, "reps": reps,
            "records_per_sec": round(rps, 1),
            "sec_per_rep": [round(s, 4) for s in secs],
            "cv": round(cv, 4),
            "update_lag_p95_s": lag_p95,
            "updates_chained": len(lags),
        },
    }
    regressions = []
    try:
        regressions = _apply_regression_gate(rec)
    except Exception as e:
        print(f"bench: regression gate skipped "
              f"({resilience.classify_failure(e).value}: {e})",
              file=sys.stderr, flush=True)
    print(json.dumps(rec))
    if gate and regressions:
        raise SystemExit(1)


def _require_device() -> None:
    """The device paths run on the TPU JAX finds.  A CPU run is only
    ever an explicit request (JAX_PLATFORMS=cpu, as the tests make it);
    a run that asked for nothing and found no TPU fails instead of
    measuring the CPU under the chip's name."""
    import jax

    dev = jax.devices()[0]
    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if dev.platform != "tpu" and not explicit_cpu:
        print(f"bench: no TPU found (JAX's first device is "
              f"{dev.platform}); set JAX_PLATFORMS=cpu to measure the "
              f"CPU on purpose", file=sys.stderr, flush=True)
        raise SystemExit(1)
    print(f"bench: device {dev.platform} {dev.device_kind!r} x"
          f"{len(jax.devices())}", file=sys.stderr, flush=True)


def main(gate: bool = False) -> None:
    child = os.environ.get("SPLATT_SCALING_CHILD")
    if child:
        _scaling_child(int(child))
        return
    devices = os.environ.get("SPLATT_BENCH_DEVICES")
    if devices:
        try:
            devs = [int(x) for x in devices.split(",") if x.strip()]
            assert devs and all(d >= 1 for d in devs)
        except (ValueError, AssertionError):
            print(f"bench: bad SPLATT_BENCH_DEVICES {devices!r}; "
                  f"expected e.g. 1,2,4,8", file=sys.stderr, flush=True)
            raise SystemExit(2)
        _run_scaling(devs)
        return
    if os.environ.get("SPLATT_BENCH_SCENARIO", "").strip() == "batched":
        # the batched fleet scenario is its own A/B harness (K small
        # tensors, in-run sequential baseline) — not a path sweep over
        # one big tensor
        _require_device()
        _run_batched_bench(gate)
        return
    if os.environ.get("SPLATT_BENCH_SCENARIO", "").strip() == "predict":
        # the prediction plane's request-latency A/B is host-side
        # numpy over a committed model store — no device needed
        _run_predict_bench(gate)
        return
    if os.environ.get("SPLATT_BENCH_SCENARIO", "").strip() == "ingest":
        # the streaming-ingest plane is host-side numpy + fsync'd
        # commits — no device needed
        _run_ingest_bench(gate)
        return
    _require_device()
    import jax
    import jax.numpy as jnp

    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import BlockAlloc, Options, Verbosity
    from splatt_tpu.cpd import _make_phased_sweep, _make_sweep, init_factors
    from splatt_tpu.ops.linalg import gram

    nnz = int(os.environ.get("SPLATT_BENCH_NNZ", 20_000_000))
    rank = int(os.environ.get("SPLATT_BENCH_RANK", 50))
    iters = int(os.environ.get("SPLATT_BENCH_ITERS", 3))
    try:
        bench_dtype = jnp.dtype(os.environ.get("SPLATT_BENCH_DTYPE",
                                               "float32"))
        if not jnp.issubdtype(bench_dtype, jnp.floating):
            raise TypeError(f"non-floating dtype {bench_dtype}")
    except TypeError as e:
        print(f"bench: bad SPLATT_BENCH_DTYPE ({e}); using float32",
              file=sys.stderr, flush=True)
        bench_dtype = jnp.dtype("float32")

    shape = os.environ.get("SPLATT_BENCH_SHAPE", "nell2")
    if shape not in SHAPES:
        print(f"bench: bad SPLATT_BENCH_SHAPE {shape!r}; using nell2",
              file=sys.stderr, flush=True)
        shape = "nell2"
    scenario = os.environ.get("SPLATT_BENCH_SCENARIO", "uniform")
    _T0 = time.perf_counter()
    # seeds match the tensors the reference was measured on
    # (BASELINE_MEASURED.json description: nell2 seed 0, enron4 seed 1)
    try:
        tt, scen_desc, scen_label = scenario_tensor(
            scenario, shape, nnz, seed=1 if shape == "enron4" else 0)
    except ValueError as e:
        print(f"bench: {e}; using the uniform scenario",
              file=sys.stderr, flush=True)
        tt, scen_desc, scen_label = scenario_tensor(
            "uniform", shape, nnz, seed=1 if shape == "enron4" else 0)

    factors = init_factors(tt.dims, rank, 7, dtype=bench_dtype)

    def sync(f2):
        # The timed sweeps chain (each consumes the previous factors),
        # so fencing the last one fences them all.
        from splatt_tpu.utils.env import host_fence

        host_fence(f2)

    def note(msg):
        print(f"bench: {msg} [t+{time.perf_counter() - _T0:.0f}s]",
              file=sys.stderr, flush=True)

    jit_mode = os.environ.get("SPLATT_BENCH_JIT", "auto").lower()
    if jit_mode not in ("auto", "fused", "phased"):
        print(f"bench: bad SPLATT_BENCH_JIT {jit_mode!r}; using auto",
              file=sys.stderr, flush=True)
        jit_mode = "auto"

    def run(X):
        # auto: phased per-phase jits on TPU (the cpd_als default) and
        # whenever the native host MTTKRP engine runs (host calls can't
        # live inside a whole-sweep trace); the fully fused sweep
        # elsewhere.
        from splatt_tpu.ops.mttkrp import choose_impl, describe_plan

        native = (isinstance(X, BlockedSparse)
                  and choose_impl(X.opts) == "native")
        phased = (jit_mode == "phased"
                  or (jit_mode == "auto"
                      and (jax.default_backend() == "tpu" or native)))
        if isinstance(X, BlockedSparse):
            # name the dispatch plan in the log: the TPU number is only
            # interpretable knowing which engine (unfused_pallas/
            # xla_scan/native) actually ran.  Inside a FUSED whole-sweep
            # trace the host-only native engine cannot run (tracer
            # inputs) — say so rather than mislabel the measurement.
            plan = describe_plan(X, factors)
            if not phased and "native" in plan:
                plan += " [fused whole-sweep jit: native falls back to xla]"
            note(plan)
        sweep = (_make_phased_sweep if phased
                 else _make_sweep)(X, tt.nmodes, 0.0, donate=True)
        # donated sweeps consume their inputs: give each path a private
        # copy so the shared factor set survives for the next path —
        # cast to the layout's STORAGE dtype (the compact path stores
        # bf16 and runs bf16 factors with f32 accumulation, exactly as
        # a cpd_als over that BlockedSparse would; docs/format.md)
        dt = (X.layouts[0].vals.dtype if isinstance(X, BlockedSparse)
              else bench_dtype)
        f2 = [jnp.array(u, dtype=dt) for u in factors]
        g2 = [gram(u) for u in f2]
        # warmup / compile
        note("compiling + first sweep")
        f2, g2, *_ = sweep(f2, g2, True)
        sync(f2)
        note("warm sweep")
        f2, g2, *_ = sweep(f2, g2, False)
        sync(f2)
        note(f"timing {iters} sweeps")
        # per-sweep timing, MEDIAN headline: robust to OS noise spikes
        # on a shared host (measured ±7% run-to-run on identical code);
        # the per-sweep sync is one host fence (~ms) against
        # 0.5-6 s/sweep.  ≙ the reference printing each iteration's
        # time (src/cpd.c:357-367).  mean/min/max ride along in the
        # JSON: BASELINE reference rows are per-iteration MEANS over
        # 2-iteration runs, and under a skewed timing distribution the
        # median sits below the mean — emitting both keeps the
        # mean-vs-mean comparison reconstructable from the artifact.
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            f2, g2, *_ = sweep(f2, g2, False)
            sync(f2)
            times.append(time.perf_counter() - t0)
        times.sort()
        # coefficient of variation rides along (ISSUE 8 satellite;
        # _timing_cv is the single dispersion definition): the --gate
        # comparison downgrades a >10% "regression" to a bench_noisy
        # WARNING when either side's CV exceeds NOISE_CV or the delta
        # sits under CV_NOISE_MULT x CV — a regression verdict must be
        # a verdict, not OS noise
        return {"median": times[len(times) // 2],
                "mean": sum(times) / len(times),
                "min": times[0], "max": times[-1],
                "cv": _timing_cv(times)}

    # Measure both tensor representations and report the best: the
    # blocked/one-hot layout (Pallas on TPU, XLA engine elsewhere) and
    # the stream formulation. Degrade gracefully if one fails to
    # compile (e.g. a Mosaic lowering issue on new hardware).
    def release():
        # free the previous path's device buffers and jit executables so
        # measurements don't pollute each other (the sweeps close over
        # multi-GB layout arrays)
        import gc

        gc.collect()
        jax.clear_caches()

    results = {}
    default_paths = "blocked,balanced,compact,tuned,stream"
    if scen_label == "densemode":
        # the densemode scenario exists to A/B the hybrid dense-tile
        # dispatch against the sparse rows (docs/dense.md)
        default_paths = "blocked,compact,dense,tuned,stream"
    raw_paths = [p.strip() for p in
                 os.environ.get("SPLATT_BENCH_PATHS",
                                default_paths).split(",") if p.strip()]
    paths = [p for p in raw_paths
             if p in ("blocked", "balanced", "compact", "stream",
                      "tuned", "dense")]
    if paths != raw_paths:
        # keep the valid subset rather than silently re-enabling the
        # slow paths the caller asked to skip — inside a hard-timeout
        # chip window that would kill the run before any JSON prints
        print(f"bench: ignoring unknown SPLATT_BENCH_PATHS entries in "
              f"{raw_paths!r}; running "
              f"{paths or default_paths.split(',')}",
              file=sys.stderr, flush=True)
    if not paths:
        paths = default_paths.split(",")
    engine = os.environ.get("SPLATT_BENCH_ENGINE", "auto").lower()
    if engine not in ("auto", "pallas", "xla"):
        print(f"bench: bad SPLATT_BENCH_ENGINE {engine!r}; using auto",
              file=sys.stderr, flush=True)
        engine = "auto"
    use_pallas = {"auto": None, "pallas": True, "xla": False}[engine]
    try:
        alloc = BlockAlloc(os.environ.get("SPLATT_BENCH_ALLOC", "allmode"))
    except ValueError:
        print("bench: bad SPLATT_BENCH_ALLOC; using allmode",
              file=sys.stderr, flush=True)
        alloc = BlockAlloc.ALLMODE
    # the "blocked" row is the STATIC-default reference the tuned row
    # is judged against, so it must not consult the plan cache
    opts = Options(random_seed=7, verbosity=Verbosity.NONE,
                   val_dtype=bench_dtype, use_pallas=use_pallas,
                   block_alloc=alloc, autotune=False)
    # a path that fails mid-run is CLASSIFIED and recorded (the
    # bench_path_error run-report event + the path_errors JSON field)
    # and the remaining paths continue — one path's Mosaic rejection or
    # OOM must not cost the whole benchmark's chip window
    path_errors = {}
    # per-path ACHIEVED bytes/iteration + format summary, from the
    # encoded layouts (docs/format.md) — the fixed i32/f32 model would
    # claim the compact format moves bytes it no longer does.  The
    # achieved bytes INCLUDE each path's operand-prep decode traffic
    # (bench_algs.mttkrp_decode_bytes, per the engine the path's plan
    # names), so the bytes:<path> gate legs cover an engine change
    # that silently reintroduces prep decode; decode_overhead is the
    # achieved/encoded ratio — ~1.0 when the plan consumes the streams
    # natively (xla_scan/xla), ~2x under operand-prep decode
    path_gb = {}
    path_decode = {}
    path_fmt = {}
    # per-path modeled FLOPs (bench_algs.mttkrp_flops): the compute
    # half of the roofline — beside the bytes-only model, it is what
    # separates the dense MXU path (high intensity) from the
    # bandwidth-bound sparse rows (docs/dense.md).  flops:<path> gate
    # legs, like bytes:<path>.
    path_flops = {}
    # per-path achieved balance (docs/layout-balance.md): max/mean nnz
    # and row span per block (worst layout) + the summed one-hot work
    # amplification — the quantities the balanced packing improves,
    # and a deterministic --gate leg (balance:<path>) like bytes
    path_imb = {}
    pallas_ran = (use_pallas is True
                  or (use_pallas is None
                      and jax.default_backend() == "tpu"))

    def note_format(label, X, pallas=None):
        from splatt_tpu.bench_algs import (mttkrp_bytes_encoded,
                                           mttkrp_decode_bytes,
                                           mttkrp_flops)
        from splatt_tpu.ops.mttkrp import plan_mttkrp

        # `pallas` overrides the run-wide engine family for paths that
        # force their own (the blocked_xla fallback): the traffic model
        # must match what the path's engines actually stream
        if pallas is None:
            pallas = pallas_ran
        alg = "blocked_pallas" if pallas else "blocked"
        itemsize = jnp.dtype(X.layouts[0].vals.dtype).itemsize
        enc_gb = sum(mttkrp_bytes_encoded(alg, X, rank, m, itemsize)
                     for m in range(X.nmodes)) / 1e9
        # decode traffic follows the engine each mode's plan will run
        # (docs/format.md): plan probe factors are shape-only
        plan_facs = [jnp.zeros((d, rank), X.layouts[0].vals.dtype)
                     for d in X.dims]
        dec_gb = sum(mttkrp_decode_bytes(
                         X, rank, m, plan_mttkrp(X, plan_facs, m).engine)
                     for m in range(X.nmodes)) / 1e9
        gb = enc_gb + dec_gb
        # 4 decimals (0.1 MB): the gate COMPARES these values, and a
        # 2-decimal round would blind the >10% bytes leg at smoke scale
        path_gb[label] = round(gb, 4)
        path_decode[label] = (round(gb / enc_gb, 3) if enc_gb > 0
                              else 1.0)
        path_fmt[label] = X.format_summary()
        path_flops[label] = round(
            sum(mttkrp_flops(alg, X, rank, m)
                for m in range(X.nmodes)) / 1e9, 4)
        # dense tile layouts have no nnz stream to balance — a
        # fully-dense hybrid has no imbalance row (and no balance leg)
        per_mode = X.imbalance()
        if per_mode:
            path_imb[label] = dict(
                block_nnz_max_mean=max(d["block_nnz_max_mean"]
                                       for d in per_mode.values()),
                span_max_mean=max(d["span_max_mean"]
                                  for d in per_mode.values()),
                work_amp=round(sum(d["work_amp"]
                                   for d in per_mode.values()), 2),
                packing=sorted({d["packing"] for d in per_mode.values()}))
        bal = (f"; balance: block nnz max/mean "
               f"{path_imb[label]['block_nnz_max_mean']}, one-hot work "
               f"x{path_imb[label]['work_amp']}/nnz"
               if label in path_imb else "")
        note(f"format[{label}]: {path_fmt[label]} -> "
             f"{path_gb[label]} GB/iter (achieved bytes; decode "
             f"overhead x{path_decode[label]}), "
             f"{path_flops[label]} GFLOP/iter{bal}")

    def record_failure(label, e):
        from splatt_tpu import resilience

        ev = resilience.record_path_error(label, e)
        path_errors[label] = {"error": f"{ev['failure_class']}: "
                                       f"{ev['error']}"}
        print(f"bench: {label} path failed ({ev['failure_class']}: "
              f"{type(e).__name__}: {e}); continuing with the "
              f"remaining paths", file=sys.stderr, flush=True)

    blocked_failed = False
    if "blocked" in paths:
        try:
            note("building blocked layouts")
            X = BlockedSparse.from_coo(tt, opts)
            note_format("blocked", X)
            results["blocked"] = run(X)
        except Exception as e:
            record_failure("blocked", e)
            blocked_failed = True
        release()  # outside any handler: no traceback pinning buffers
    if blocked_failed:
        try:
            note("retrying blocked with the XLA engine")
            opts_x = Options(random_seed=7, verbosity=Verbosity.NONE,
                             val_dtype=bench_dtype, use_pallas=False,
                             block_alloc=alloc)
            X = BlockedSparse.from_coo(tt, opts_x)
            note_format("blocked_xla", X, pallas=False)
            results["blocked_xla"] = run(X)
        except Exception as e2:
            record_failure("blocked_xla", e2)
        release()
    if "balanced" in paths:
        # the load-balanced row (docs/layout-balance.md): same sweep,
        # layouts cut by nnz-balanced fiber packing with long-fiber
        # splitting — on skewed scenarios the bounded per-block row
        # span shrinks seg_width (and with it the one-hot work) that
        # the fixed slicing lets one straggler block inflate
        try:
            note("building balanced (nnz-packed fibers) layouts")
            opts_b = Options(random_seed=7, verbosity=Verbosity.NONE,
                             val_dtype=bench_dtype, use_pallas=use_pallas,
                             block_alloc=alloc, autotune=False,
                             fiber_packing="balanced")
            X = BlockedSparse.from_coo(tt, opts_b)
            note_format("balanced", X)
            results["balanced"] = run(X)
        except Exception as e:
            record_failure("balanced", e)
        release()
    if "compact" in paths:
        # the format-v2 row (docs/format.md): same sweep, layouts
        # encoded with local narrow indices + segment ids + bf16 value
        # storage — the bytes/iteration halving the roofline analysis
        # says the bandwidth-bound kernel converts into speed
        try:
            note("building compact (v2 idx + bf16 storage) layouts")
            opts_c = Options(random_seed=7, verbosity=Verbosity.NONE,
                             val_dtype=bench_dtype, use_pallas=use_pallas,
                             block_alloc=alloc, autotune=False,
                             idx_width="auto", val_storage="bf16")
            X = BlockedSparse.from_coo(tt, opts_c)
            note_format("compact", X)
            results["compact"] = run(X)
        except Exception as e:
            record_failure("compact", e)
        release()
    if "dense" in paths:
        # the hybrid dense-mode row (docs/dense.md): same sweep, modes
        # whose padded density crosses the threshold ride the dense
        # tile layout + MXU matmul engines, the rest keep the sparse
        # blocked path — zero index bytes on the dense modes is the
        # whole bet, and the bytes:dense gate leg holds it
        try:
            note("building hybrid dense-mode layouts")
            opts_d = Options(random_seed=7, verbosity=Verbosity.NONE,
                             val_dtype=bench_dtype, use_pallas=use_pallas,
                             block_alloc=alloc, autotune=False,
                             dense="auto")
            X = BlockedSparse.from_coo(tt, opts_d)
            note_format("dense", X)
            results["dense"] = run(X)
        except Exception as e:
            record_failure("dense", e)
        release()
    tuned_plan_info = None
    if "tuned" in paths:
        # the autotuned row: measure candidate plans (or hit the warm
        # plan cache), build the layouts at the tuned blocks, and time
        # the same sweep — so the BENCH trajectory can attribute wins
        # to tuning rather than to unrelated code movement
        try:
            import dataclasses as _dc

            from splatt_tpu import tune as _tune

            # on the densemode scenario the dense tile candidates join
            # the tuner's matrix (docs/dense.md) — the hybrid verdict
            # is measured, not assumed
            topts = Options(random_seed=7, verbosity=Verbosity.NONE,
                            val_dtype=bench_dtype, use_pallas=use_pallas,
                            block_alloc=alloc, autotune=True,
                            dense=("auto" if scen_label == "densemode"
                                   else None))
            note(f"autotuning (plan cache: {_tune.cache_path()})")
            tres = _tune.tune(tt, rank=rank, opts=topts)
            if tres.measured == 0 and tres.plans:
                note("tune: warm plan cache hit for every mode — "
                     "skipped all measurement")
            else:
                note(f"tune: {tres.measured} candidate measurements, "
                     f"{tres.cache_hits} cache hits")
            tuned_plan_info = {str(m): _dc.asdict(p)
                               for m, p in sorted(tres.plans.items())}
            note(f"tuned plans: {tuned_plan_info}")
            note("building tuned blocked layouts")
            X = BlockedSparse.compile(tt, topts, rank=rank)
            note_format("tuned", X)
            results["tuned"] = run(X)
        except Exception as e:
            record_failure("tuned", e)
        release()
    if "stream" in paths:
        try:
            note("stream path")
            results["stream"] = run(tt)
        except Exception as e:
            record_failure("stream", e)
    if not results:
        raise RuntimeError(
            f"all benchmark paths failed: {path_errors}")
    best = min(results, key=lambda k: results[k]["median"])
    sec_per_iter = results[best]["median"]
    timings = {k: round(v["median"], 4) for k, v in results.items()}
    print(f"bench: paths {timings} -> best {best}", file=sys.stderr,
          flush=True)

    vs = 1.0
    try:
        with open(os.path.join(os.path.dirname(__file__),
                               "BASELINE_MEASURED.json")) as f:
            measured = json.load(f)
        ref = _ref_sec_per_iter(measured, shape, nnz, rank)
        if ref:
            vs = ref / sec_per_iter
    except (OSError, json.JSONDecodeError):
        pass

    platform = jax.devices()[0].platform
    rec = {
        "metric": f"CPD-ALS sec/iteration, synthetic {scen_desc} "
                  f"({tt.nmodes}-mode, {nnz} nnz, rank {rank}, "
                  f"{jnp.dtype(factors[0].dtype).name}) on {platform}; "
                  f"baseline: reference 1-thread CPU same tensor",
        "value": round(sec_per_iter, 4),
        "unit": "sec/iter",
        "vs_baseline": round(vs, 3),
        # per-path spread: the headline `value` is the best path's
        # median; mean/min/max keep mean-vs-mean BASELINE comparisons
        # reconstructable from this artifact alone
        "best_path": best,
        "timing_stats": {k: {s: round(v[s], 4)
                             for s in ("median", "mean", "min", "max",
                                       "cv") if s in v}
                         for k, v in results.items()},
    }
    if scen_label is not None:
        rec["scenario"] = scen_label
    # per-scenario imbalance stats (docs/layout-balance.md): slice skew
    # of the input, nnz per equal row fence at 8 shards (what a
    # distributed run would see), and each path's achieved block
    # balance — deterministic numbers the --gate compares via the
    # balance:<path> legs.  per_path is recorded OUTSIDE the try: it
    # arms the balance gate legs, and an unrelated skew-stat failure
    # must not silently disarm a regression gate (the bytes-legs
    # precedent)
    rec["imbalance"] = {"per_path": dict(path_imb)} if path_imb else {}
    try:
        from splatt_tpu.stats import skew_stats
        from splatt_tpu.utils.env import max_mean_ratio

        st = skew_stats(tt)
        shard8 = {}
        for m in range(tt.nmodes):
            hist = tt.mode_histogram(m)
            cap = -(-tt.dims[m] // 8)
            fences = np.add.reduceat(
                np.concatenate([hist, np.zeros(cap * 8 - tt.dims[m],
                                               dtype=hist.dtype)]),
                np.arange(0, cap * 8, cap))
            shard8[str(m)] = max_mean_ratio(fences)
        rec["imbalance"].update(
            slices={m: d["max_mean"] for m, d in st["modes"].items()},
            slice_p99_median={m: d["p99_median"]
                              for m, d in st["modes"].items()},
            shard8_max_mean=shard8)
    except Exception as e:
        print(f"bench: imbalance stats skipped ({type(e).__name__}: {e})",
              file=sys.stderr, flush=True)
    if not rec["imbalance"]:
        del rec["imbalance"]
    if path_errors:
        # failed paths ride along classified: `{"error": <class>: msg}`
        # per path, so the artifact records WHY a row is missing
        # instead of silently narrowing the comparison
        rec["path_errors"] = path_errors
    if tuned_plan_info is not None:
        # the tuner's chosen plan rides along with the "tuned" timing so
        # the BENCH trajectory can attribute wins to tuning
        rec["tuned_plan"] = tuned_plan_info
    if os.environ.get("SPLATT_BENCH_GUARD_AB", "").strip() == "1":
        # guard-cost A/B legs (ROADMAP open item 1; docs/guarded-als.md)
        try:
            note("guard A/B: timing cpd_als with health sentinel "
                 "on/off x donation on/off")
            rec["guard_ab"] = _guard_ab_legs(tt, rank, iters, bench_dtype,
                                             use_pallas, alloc)
            note(f"guard A/B: {rec['guard_ab']}")
        except Exception as e:
            print(f"bench: guard A/B skipped ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
        release()
    if os.environ.get("SPLATT_BENCH_TRACE_AB", "").strip() == "1":
        # trace-overhead A/B legs (docs/observability.md): the <2%
        # enabled-but-unexported budget, measured, in the artifact
        try:
            note("trace A/B: timing cpd_als with span recording "
                 "on (unexported) vs off")
            rec["trace_ab"] = _trace_ab_legs(tt, rank, iters, bench_dtype,
                                             use_pallas, alloc)
            note(f"trace A/B: {rec['trace_ab']}")
        except Exception as e:
            print(f"bench: trace A/B skipped ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
        release()
    try:
        # first-order roofline: one iteration = nmodes MTTKRPs' HBM
        # traffic against the measured sec/iter — shows headroom next
        # to the seconds.  Blocked paths report ACHIEVED bytes from
        # their encoded layouts (computed per path above); the stream
        # path keeps the logical COO model.
        from splatt_tpu.bench_algs import hbm_peak_gbs, mttkrp_bytes

        if best in path_gb:
            gb = float(path_gb[best])
        else:
            itemsize = jnp.dtype(bench_dtype).itemsize
            gb = sum(mttkrp_bytes("stream", tt, rank, m, itemsize)
                     for m in range(tt.nmodes)) / 1e9
        rec["model_gb_per_iter"] = round(gb, 2)
        rec["eff_gbs"] = round(gb / sec_per_iter, 1)
        if path_gb:
            # per-path achieved bytes + eff_gbs + format summary: what
            # the --gate comparison and the BENCH trajectory read.
            # decode_overhead is achieved/encoded bytes per path — the
            # in-kernel-decode contract (achieved ≈ encoded, ≤ ~1.15x)
            # made a recorded number (docs/format.md)
            rec["model_gb_per_path"] = dict(path_gb)
            rec["decode_overhead"] = dict(path_decode)
            rec["eff_gbs_per_path"] = {
                k: round(path_gb[k] / results[k]["median"], 1)
                for k in path_gb if k in results}
            rec["format"] = dict(path_fmt)
        if path_flops:
            # the compute half of the roofline (docs/dense.md): modeled
            # GFLOP/iteration per path and the intensity-vs-ridge
            # verdict — the flops:<path> gate legs read the former, a
            # reader takes the bound classification from the latter
            from splatt_tpu.bench_algs import roofline_verdict

            rec["model_gflops_per_path"] = dict(path_flops)
            rec["roofline_verdict"] = {
                k: roofline_verdict(path_gb[k] * 1e9,
                                    path_flops[k] * 1e9)
                for k in path_flops if k in path_gb}
        peak = hbm_peak_gbs()
        if peak:
            rec["hbm_peak_pct"] = round(100 * gb / sec_per_iter / peak, 1)
    except Exception as e:  # the headline number must never be lost
        print(f"bench: roofline model skipped ({type(e).__name__}: {e})",
              file=sys.stderr, flush=True)
    # regression gate (ROADMAP open item 1): compare against the newest
    # prior BENCH_*.json on the same metric; >10% slowdowns are
    # recorded (bench_regression event + the JSON artifact) and, under
    # --gate, fail the run AFTER the headline JSON prints — the number
    # is never lost to the verdict
    regressions = []
    try:
        regressions = _apply_regression_gate(rec)
    except Exception as e:
        from splatt_tpu import resilience

        print(f"bench: regression gate skipped "
              f"({resilience.classify_failure(e).value}: {e})",
              file=sys.stderr, flush=True)
    print(json.dumps(rec))
    if path_errors:
        # a requested path that failed fails the run — after the record
        # (which names each failure) is out
        raise SystemExit(1)
    if gate and regressions:
        raise SystemExit(1)


if __name__ == "__main__":
    _unknown = [a for a in sys.argv[1:] if a != "--gate"]
    if _unknown:
        print(f"bench: unknown arguments {_unknown}; only --gate is "
              f"accepted (knobs are SPLATT_BENCH_* env vars)",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    main(gate="--gate" in sys.argv[1:])
