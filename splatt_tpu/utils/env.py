"""Environment/platform helpers shared by entry points.

This module is additionally the single place process environment is
read from (`splint` rule SPL001 enforces it): every environment
variable the project consumes is declared once in :data:`ENV_VARS`
(name → default → doc) and read through :func:`read_env` /
:func:`read_env_int` / :func:`read_env_float`.  Centralizing the reads
matters beyond tidiness — this file feeds the probe cache's
`_kernel_src_hash`, so an env-plumbing change invalidates cached
capability verdicts instead of silently desynchronizing from them, and
the registry is what keeps the docs (docs/resilience.md, DESIGN.md)
and the SPL007 documentation check from drifting against the code.
"""

from __future__ import annotations

import os
import sys
from typing import NamedTuple, Optional


class EnvVar(NamedTuple):
    """One declared environment variable: its default (None = unset)
    and a one-line doc string (the authoritative documentation — docs
    reference this registry instead of hand-listing variables)."""

    default: Optional[object]
    doc: str


#: Every environment variable the project reads, name → (default, doc).
#: `splint` rule SPL007 statically checks each SPLATT_* reference in
#: the code against this table; `python -m tools.splint --env-docs`
#: renders it for the docs.
ENV_VARS = {
    "JAX_PLATFORMS": EnvVar(None, "standard JAX platform selection "
                            "(the tests pin cpu; the chip path needs "
                            "the TPU JAX picks by default)"),
    "JAX_COMPILATION_CACHE_DIR": EnvVar(None, "standard JAX persistent "
                                        "compile cache directory; when "
                                        "set, JAX applies it and "
                                        "splatt-tpu sets none in code, "
                                        "else apply_compile_cache() "
                                        "uses <checkout>/.jax_cache"),
    "SPLATT_ENGINE_FALLBACK": EnvVar("1", "runtime MTTKRP engine "
                                     "fallback (docs/resilience.md); "
                                     "0/off/false/no = fail loudly"),
    "SPLATT_SCAN_TARGET_ELEMS": EnvVar(1 << 23, "one-hot elements "
                                       "materialized per scan step of "
                                       "the xla_scan MTTKRP engine; "
                                       "elements of each gathered row "
                                       "array per unfused_pallas scan "
                                       "step (its padded row arrays "
                                       "also stay within 5/4 of the "
                                       "device's VMEM)"),
    "SPLATT_FAULTS": EnvVar("", "comma-separated fault-arming specs "
                            "site[:kind][:modifier]... for the fault-"
                            "injection harness, including seeded chaos "
                            "schedules iter=k / p=x:seed=N / after=t "
                            "(utils/faults.py, docs/guarded-als.md)"),
    "SPLATT_HEALTH_RETRIES": EnvVar(3, "numerical-health sentinel "
                                    "rollback budget: how many times a "
                                    "run may restore the last-good "
                                    "snapshot (bumping regularization "
                                    "/ re-randomizing the offending "
                                    "factor) before degrading to "
                                    "checkpoint-and-abort; 0 disables "
                                    "the sentinel "
                                    "(docs/guarded-als.md)"),
    "SPLATT_DEADLINE_S": EnvVar(0.0, "deadline watchdog budget in "
                                "seconds for host-side compile/"
                                "measure/probe calls (probe compiles, "
                                "tuner measurements, engine dispatch); "
                                "a blown deadline classifies TIMEOUT "
                                "and demotes per-shape like OOM; <= 0 "
                                "disables (docs/guarded-als.md)"),
    "SPLATT_CHAOS_SCHEDULE": EnvVar("", "default fault schedule for "
                                    "the `splatt chaos` soak verb when "
                                    "no --schedule flag is given; same "
                                    "grammar as SPLATT_FAULTS "
                                    "(docs/guarded-als.md)"),
    "SPLATT_COMM": EnvVar(None, "default row-exchange strategy for "
                          "FINE-decomposition distributed runs "
                          "(docs/ring.md): all2all (collectives), "
                          "point2point (ppermute ring), async_ring "
                          "(Pallas remote-copy ring with "
                          "comm/compute overlap; degrades classified "
                          "point2point -> all2all on failure); an "
                          "explicit Options.comm_pattern / --comm "
                          "wins"),
    "SPLATT_PROBE_CACHE": EnvVar(None, "path override for the "
                                 "persistent capability-probe cache "
                                 "(default: tools/probe_cache.json in "
                                 "a repo checkout)"),
    "SPLATT_PROBE_CACHE_TTL_S": EnvVar(14 * 24 * 3600.0, "seconds a "
                                       "cached probe verdict stays "
                                       "fresh; <= 0 disables expiry "
                                       "(also the autotuner plan-cache "
                                       "TTL, docs/autotune.md)"),
    "SPLATT_IDX_WIDTH": EnvVar("i32", "blocked-layout index-width "
                               "policy (docs/format.md): i32 = v1 "
                               "global int32 indices; auto = compact "
                               "v2 encoding (per-block local indices, "
                               "uint16 where each mode's block extent "
                               "fits, int32 otherwise, plus int32 "
                               "per-block bases); u16 = v2 requiring "
                               "uint16 everywhere; u8 = v2 with the "
                               "sorted mode's segment-id stream at "
                               "uint8 (legal when every block's span "
                               "fits 255) and the other modes at the "
                               "auto widths; delta = v2 with the "
                               "gather modes' local streams stored as "
                               "within-block differences at the "
                               "narrowest signed width (i8 on smooth "
                               "runs; decode = one exact per-block "
                               "cumsum); rle = v2 with the sorted "
                               "mode's segment stream replaced by "
                               "per-block run-length counts (seg_width "
                               "entries instead of block entries — the "
                               "dense-ish-block hybrid).  All encode "
                               "failures degrade classified to v1"),
    "SPLATT_DECODE": EnvVar("kernel", "decode placement for compact "
                            "layouts (docs/format.md): kernel = "
                            "dispatch consumes the encoded streams "
                            "natively (the xla_scan engine "
                            "decodes per chunk inside the scan) so achieved HBM bytes track the "
                            "encoded bytes; prep = force operand-"
                            "prep decode (the pre-v2 dataflow: "
                            "global i32 materialized before the "
                            "kernel) — the A/B lever behind bench's "
                            "decode_overhead model"),
    "SPLATT_VAL_STORAGE": EnvVar("auto", "blocked-layout value-storage "
                                 "dtype (docs/format.md): auto = the "
                                 "resolved compute dtype; f32/bf16 pin "
                                 "it — bf16 stores nonzero values (and "
                                 "the factors derived from them) in "
                                 "bfloat16 with f32 accumulation"),
    "SPLATT_FIBER_PACKING": EnvVar("fixed", "blocked-layout fiber-"
                                   "packing policy (docs/layout-"
                                   "balance.md): fixed = slice the "
                                   "sorted stream every nnz_block "
                                   "nonzeros (the original policy); "
                                   "balanced = nnz-weighted fiber bin "
                                   "packing with long-fiber splitting, "
                                   "bounding each block's output-row "
                                   "span so skewed tensors stop "
                                   "inflating seg_width (a failed pack "
                                   "degrades classified to fixed).  An "
                                   "explicit Options.fiber_packing "
                                   "wins; unset, both are autotuner "
                                   "candidates"),
    "SPLATT_DENSE": EnvVar("off", "dense-mode tile layout policy "
                           "(docs/dense.md): off = every mode keeps "
                           "the sparse blocked encoding; auto = a "
                           "mode whose padded fiber density crosses "
                           "SPLATT_DENSE_THRESHOLD (and whose dense "
                           "cells stay within the blowup cap) gets a "
                           "dense tile layout and the MXU matmul "
                           "engines; on = force the dense tiling for "
                           "every geometrically feasible mode.  A "
                           "tuned dense plan wins over this policy; "
                           "any dense build failure degrades "
                           "classified to the sparse encoding "
                           "(format_fallback, site=dense)"),
    "SPLATT_DENSE_THRESHOLD": EnvVar("0.05", "padded per-mode density "
                                     "(nnz / dense tile cells) at or "
                                     "above which SPLATT_DENSE=auto "
                                     "elects the dense tile layout "
                                     "(docs/dense.md)"),
    "SPLATT_REORDER": EnvVar(None, "index-relabeling reorder applied "
                             "before blocked layouts are built (docs/"
                             "layout-balance.md): identity | random | "
                             "graph | hgraph | fibsched.  One whole-"
                             "tensor permutation relabels every mode; "
                             "factors are restored to original row "
                             "order on output (Permutation.undo).  An "
                             "explicit Options.reorder wins; unset, "
                             "the recipes are autotuner candidates and "
                             "compile applies a unanimous verdict.  "
                             "Any reorder failure degrades classified "
                             "to identity (reorder_fallback)"),
    "SPLATT_AUTOTUNE": EnvVar("1", "MTTKRP dispatch consults the "
                              "autotuner's persisted plan cache "
                              "(docs/autotune.md) before the heuristic "
                              "engine chain; 0/off/false/no = static "
                              "heuristics only"),
    "SPLATT_TUNE_CACHE": EnvVar(None, "path override for the "
                                "autotuner's persistent plan cache "
                                "(default: tune_cache.json next to the "
                                "probe cache)"),
    # structured tracing + metrics (splatt_tpu/trace.py,
    # docs/observability.md)
    "SPLATT_TRACE": EnvVar(None, "1/on/true/yes enables structured "
                           "span recording (docs/observability.md): "
                           "host-side spans (cpd -> sweep -> guard, "
                           "dispatch, comm) exportable as Chrome "
                           "trace-event JSON via --trace <path>.  Off "
                           "by default: disabled spans are no-ops "
                           "(one boolean check); an explicit "
                           "Options.trace / CLI --trace wins.  "
                           "Event-derived metrics are always on "
                           "regardless"),
    "SPLATT_METRICS_PATH": EnvVar(None, "serve: when set, the metrics "
                                  "registry (trace.METRICS) is "
                                  "snapshotted to this file in "
                                  "Prometheus text exposition format "
                                  "on a cadence "
                                  "(SPLATT_METRICS_INTERVAL_S) and at "
                                  "daemon exit — atomic replace, so a "
                                  "scraper never reads a torn file "
                                  "(docs/observability.md)"),
    "SPLATT_METRICS_INTERVAL_S": EnvVar(30.0, "serve: seconds between "
                                        "metrics snapshots to "
                                        "SPLATT_METRICS_PATH; <= 0 "
                                        "snapshots only at daemon "
                                        "exit"),
    "SPLATT_TRACE_MAX_RECORDS": EnvVar(100000, "in-memory span/point "
                                       "recorder bound: past this "
                                       "many finished records the "
                                       "OLDEST are dropped (counted, "
                                       "surfaced on trace_written) — "
                                       "what lets a fleet daemon run "
                                       "with recording + the flight "
                                       "ring armed for its whole "
                                       "life without unbounded RSS"),
    # flight recorder (splatt_tpu/trace.py, docs/observability.md)
    "SPLATT_FLIGHT": EnvVar("auto", "flight recorder — the bounded, "
                            "incrementally-appended ring of recent "
                            "spans/point events that survives a "
                            "SIGKILL (docs/observability.md): auto = "
                            "armed by fleet-mode `splatt serve` at "
                            "<root>/fleet/flight/<replica>.jsonl, off "
                            "elsewhere; 0/off disables even in fleet "
                            "mode; 1/on keeps the fleet default "
                            "explicit"),
    "SPLATT_FLIGHT_BYTES": EnvVar(1 << 20, "flight recorder: rotate "
                                  "the ring file atomically to "
                                  "<path>.1 once it outgrows this "
                                  "many bytes (one previous "
                                  "generation kept — the bound on "
                                  "the black box)"),
    "SPLATT_FLIGHT_FLUSH": EnvVar(32, "flight recorder: buffered "
                                  "records per ring-file flush; a "
                                  "SIGKILL loses at most this many "
                                  "trailing records (smaller = "
                                  "fresher black box, more write "
                                  "calls on the span path)"),
    # SLO layer (splatt_tpu/fleetobs.py, docs/observability.md)
    "SPLATT_SLO_QUEUE_WAIT_P95_S": EnvVar(30.0, "SLO objective: 95% "
                                          "of jobs start within this "
                                          "many seconds of acceptance "
                                          "(the splatt_serve_queue_"
                                          "wait_seconds histogram; "
                                          "threshold rounds up to a "
                                          "histogram bucket bound)"),
    "SPLATT_SLO_JOB_WALL_P95_S": EnvVar(600.0, "SLO objective: 95% of "
                                        "terminal jobs finish within "
                                        "this many wall seconds (the "
                                        "splatt_job_seconds "
                                        "histogram)"),
    "SPLATT_SLO_AVAILABILITY": EnvVar(0.99, "SLO objective: the "
                                     "accepted fraction of "
                                     "submissions — availability = "
                                     "1 - (queue_full + "
                                     "quota_rejected) / offered"),
    "SPLATT_SLO_WINDOW_S": EnvVar(300.0, "SLO burn-rate short window "
                                  "in seconds; the long window is "
                                  "SPLATT_SLO_LONG_WINDOWS times "
                                  "this (docs/observability.md)"),
    "SPLATT_SLO_LONG_WINDOWS": EnvVar(12, "SLO burn-rate long window, "
                                     "as a multiple of "
                                     "SPLATT_SLO_WINDOW_S (default "
                                     "12: a 5-minute short window "
                                     "pairs with a 1-hour long one)"),
    "SPLATT_SLO_BURN": EnvVar(2.0, "SLO alert threshold: emit "
                              "slo_burn when the error-budget burn "
                              "rate meets/exceeds this multiple on "
                              "BOTH windows (multi-window gating "
                              "suppresses blips and stale burns "
                              "alike)"),
    "SPLATT_SLO_PREDICT_P99_S": EnvVar(0.25, "SLO objective: 99% of "
                                       "served predicts complete "
                                       "within this many wall seconds "
                                       "accepted-to-served (the "
                                       "splatt_predict_latency_seconds "
                                       "histogram; threshold rounds "
                                       "up to a histogram bucket "
                                       "bound; docs/predict.md)"),
    # predict lane (splatt_tpu/predict.py + serve.py, docs/predict.md)
    "SPLATT_PREDICT_QUEUE_MAX": EnvVar(64, "serve predict lane: "
                                       "bounded pending-predict "
                                       "depth, separate from the "
                                       "fit/update queue; a predict "
                                       "past it is load-shed with an "
                                       "explicit queue_full rejection "
                                       "(<= 0 disables the bound)"),
    "SPLATT_PREDICT_CACHE_MAX": EnvVar(8, "predict hot-factor cache: "
                                      "(model, generation) entries "
                                      "kept per replica, LRU-evicted "
                                      "past the bound — an update "
                                      "commit invalidates by "
                                      "generation advance, never "
                                      "deletion, so a pinned "
                                      "in-flight predict still "
                                      "finishes on its generation; "
                                      "<= 0 disables the cache"),
    # fleet status / top (splatt_tpu/fleetobs.py, docs/fleet.md)
    "SPLATT_STATUS_JOBS": EnvVar(8, "splatt status/top: how many "
                                 "recent terminal jobs the dashboard "
                                 "lists"),
    "SPLATT_STATUS_WATCH_S": EnvVar(2.0, "splatt top / status --watch: "
                                    "seconds between dashboard "
                                    "refreshes"),
    "SPLATT_BENCH_TRACE_AB": EnvVar(None, "bench.py: 1 = time cpd_als "
                                    "with span recording enabled-but-"
                                    "unexported vs off — plus a third "
                                    "leg with the flight-recorder "
                                    "ring armed — over the same "
                                    "blocked layouts and record the "
                                    "legs under 'trace_ab' "
                                    "(trace_overhead_pct / "
                                    "flight_overhead_pct vs the <2% "
                                    "budget of docs/observability.md)"),
    # serve daemon knobs (splatt_tpu/serve.py, docs/serve.md)
    "SPLATT_SERVE_WORKERS": EnvVar(1, "serve: concurrent job-supervisor "
                                   "threads; each job runs under its "
                                   "own resilience scope, sharing the "
                                   "warm probe/tune/compile caches"),
    "SPLATT_SERVE_QUEUE_MAX": EnvVar(16, "serve: bounded pending-queue "
                                     "depth; a submission past it is "
                                     "load-shed with an explicit "
                                     "queue_full rejection instead of "
                                     "queueing unboundedly; <= 0 "
                                     "disables the bound"),
    "SPLATT_SERVE_POLL_S": EnvVar(0.5, "serve: seconds between "
                                  "filed-request spool scans in the "
                                  "daemon loop"),
    "SPLATT_SERVE_JOB_DEADLINE_S": EnvVar(0.0, "serve: default per-job "
                                          "deadline in seconds (a job "
                                          "spec's deadline_s "
                                          "overrides, 0 = explicit "
                                          "opt-out); a blown job "
                                          "deadline classifies "
                                          "TIMEOUT and the job is "
                                          "marked failed, releasing "
                                          "its worker; <= 0 disables"),
    "SPLATT_SERVE_BATCH_MIN": EnvVar(0, "serve auto-coalescing "
                                     "(docs/batched.md): when a "
                                     "replica's queue holds >= this "
                                     "many batchable jobs sharing one "
                                     "regime key, a worker dispatches "
                                     "them as ONE vmapped batched CPD "
                                     "(per-job journal lineage, "
                                     "results, deadlines and quotas "
                                     "preserved; failure degrades "
                                     "classified to per-tensor "
                                     "dispatch); <= 0 disables"),
    "SPLATT_UPDATE_SWEEPS": EnvVar(5, "update jobs (docs/batched.md): "
                                   "warm-started ALS sweeps an "
                                   "incremental model update runs "
                                   "when its spec gives no iters — "
                                   "the point of warm-starting is "
                                   "that a few sweeps suffice where "
                                   "a refit needs dozens"),
    "SPLATT_UPDATE_REFIT_EVERY": EnvVar(0, "update jobs "
                                        "(docs/batched.md): every Nth "
                                        "update of one base model "
                                        "runs a from-scratch refit of "
                                        "the merged tensor instead of "
                                        "the warm path (drift "
                                        "repair; refit_scheduled "
                                        "event); <= 0 disables the "
                                        "periodic cadence (the "
                                        "health/failure repair paths "
                                        "stay active)"),
    # fleet-mode serve knobs (splatt_tpu/fleet.py, docs/fleet.md)
    "SPLATT_FLEET_REPLICA": EnvVar(None, "fleet: this replica's "
                                   "stable id (file-name-safe); "
                                   "default is a fresh pid+random id "
                                   "per process — set it explicitly "
                                   "when a restarted replica should "
                                   "keep its identity"),
    "SPLATT_FLEET_LEASE_S": EnvVar(10.0, "fleet: job/membership lease "
                                   "duration in seconds — the "
                                   "failure-detection horizon: a "
                                   "replica silent this long is dead "
                                   "and its non-terminal jobs are "
                                   "adopted by live peers"),
    "SPLATT_FLEET_HEARTBEAT_S": EnvVar(0.0, "fleet: seconds between "
                                       "heartbeat/lease-renewal "
                                       "sweeps; <= 0 derives "
                                       "lease_s / 3"),
    "SPLATT_FLEET_TENANT_QUOTA": EnvVar(0, "serve admission control: "
                                        "max non-terminal jobs per "
                                        "tenant; past it submissions "
                                        "are shed with a "
                                        "quota_rejected event; <= 0 "
                                        "disables (docs/fleet.md)"),
    "SPLATT_FLEET_AFFINITY": EnvVar("1", "fleet: cache-affinity "
                                    "routing — jobs prefer the "
                                    "replica whose probe/tune/compile "
                                    "caches are warm for their shape "
                                    "regime, load as the tiebreaker; "
                                    "0/off/false/no = pure "
                                    "priority/FIFO dispatch"),
    "SPLATT_LOCKCHECK": EnvVar("0", "runtime lock-ownership sanitizer "
                               "(utils/lockcheck.py): the structures "
                               "declared in [tool.splint] "
                               "shared-state are wrapped in proxies "
                               "asserting their owning lock is held "
                               "by the mutating thread — the dynamic "
                               "cross-check of splint rule SPL014; "
                               "off by default (zero wrappers)"),
    # repo-root bench.py driver knobs (documented here; bench.py is a
    # standalone script outside the package's SPL001 scope)
    "SPLATT_BENCH_PRIOR_DIR": EnvVar(None, "bench.py: directory "
                                     "searched for the newest prior "
                                     "BENCH_*.json the regression "
                                     "gate compares against (default: "
                                     "the repo root)"),
    "SPLATT_BENCH_NNZ": EnvVar(None, "bench.py: synthetic tensor "
                               "nonzero count (per-driver default)"),
    "SPLATT_BENCH_RANK": EnvVar(None, "bench.py: CPD rank "
                                "(per-driver default)"),
    "SPLATT_BENCH_ITERS": EnvVar(3, "bench.py: timed iterations"),
    "SPLATT_BENCH_DTYPE": EnvVar("float32", "bench.py: compute dtype"),
    "SPLATT_BENCH_SHAPE": EnvVar("nell2", "bench.py: named tensor "
                                 "shape or IxJxK"),
    "SPLATT_BENCH_PATHS": EnvVar(None, "bench.py: comma-separated "
                                 "MTTKRP paths to time"),
    "SPLATT_BENCH_ENGINE": EnvVar("auto", "bench.py: force one "
                                  "reduction engine"),
    "SPLATT_BENCH_ALLOC": EnvVar("allmode", "bench.py: BlockAlloc "
                                 "layout policy"),
    "SPLATT_BENCH_JIT": EnvVar("auto", "bench.py: sweep jit mode"),
    "SPLATT_BENCH_SCENARIO": EnvVar("uniform", "bench.py: named nnz-"
                                    "distribution scenario (docs/"
                                    "layout-balance.md): uniform "
                                    "(default — hash-scattered, "
                                    "metric string unchanged), "
                                    "zipf:<a> (zipf-skewed slice "
                                    "popularity at exponent a, e.g. "
                                    "zipf:1.5), powerlaw (power-law "
                                    "mode sizes), amazon-like (scaled "
                                    "review-tensor shape preset), "
                                    "densemode (one near-dense mode, "
                                    "docs/dense.md — adds the hybrid "
                                    "dense-tile path row and the "
                                    "flops/roofline-verdict fields), "
                                    "batched (docs/batched.md), "
                                    "predict (docs/predict.md), or "
                                    "ingest (docs/ingest.md: "
                                    "streaming-ingest records/sec + "
                                    "update-lag p95).  "
                                    "Non-uniform scenarios tag the "
                                    "metric string so the regression "
                                    "gate only compares like "
                                    "workloads, and the JSON carries "
                                    "per-scenario imbalance stats"),
    "SPLATT_BENCH_BATCH_K": EnvVar(32, "bench.py batched scenario "
                                   "(SPLATT_BENCH_SCENARIO=batched, "
                                   "docs/batched.md): how many small "
                                   "same-regime tensors the "
                                   "batched-vs-sequential A/B stacks"),
    "SPLATT_BENCH_GUARD_AB": EnvVar(None, "bench.py: 1 = run the "
                                    "guard-cost A/B legs (ROADMAP "
                                    "open item 1): cpd_als timed with "
                                    "SPLATT_HEALTH_RETRIES on/off x "
                                    "donation on/off, recorded under "
                                    "guard_ab in the bench JSON so "
                                    "the gate can see guard overhead "
                                    "explicitly"),
    "SPLATT_BENCH_DEVICES": EnvVar(None, "bench.py: comma-separated "
                                   "device counts for the scaling "
                                   "sweep"),
    "SPLATT_SCALING_CHILD": EnvVar(None, "bench.py internal: marks a "
                                   "scaling-sweep child process"),
    # -- streaming ingest (splatt_tpu/ingest.py, docs/ingest.md) --
    "SPLATT_INGEST_CHUNK": EnvVar(5000, "ingest.py: records per "
                                  "chunk commit — the exactly-once "
                                  "watermark grain (docs/ingest.md); "
                                  "a resume must reuse the journal's "
                                  "value or ingest refuses"),
    "SPLATT_INGEST_INFLIGHT": EnvVar(4, "ingest.py: bounded reader-"
                                     "to-committer queue depth — the "
                                     "backpressure knob; the reader "
                                     "blocks rather than buffering "
                                     "the stream unboundedly"),
    "SPLATT_INGEST_QUARANTINE_MAX": EnvVar(1000, "ingest.py: absolute "
                                           "quarantined-record budget "
                                           "per run; past it the run "
                                           "DEGRADES classified "
                                           "(ingest_degraded) instead "
                                           "of shipping a corrupt "
                                           "tensor; 0 disables the "
                                           "count half of the budget"),
    "SPLATT_INGEST_QUARANTINE_RATE": EnvVar(0.5, "ingest.py: max "
                                            "quarantined/parsed ratio "
                                            "(evaluated once >= 200 "
                                            "records seen) before the "
                                            "run degrades classified; "
                                            "0 disables the rate half "
                                            "of the budget"),
    "SPLATT_INGEST_UPDATE_EVERY": EnvVar(1, "serve.py ingest job "
                                         "kind: emit one update job "
                                         "per this many committed "
                                         "chunks (the watermark "
                                         "interval of the live-feed "
                                         "lane, docs/ingest.md)"),
}


def read_env(name: str) -> Optional[object]:
    """Read a declared environment variable: the process value when
    set, the registered default otherwise.  Unregistered names raise —
    an undeclared variable is exactly the drift SPL007 exists to stop,
    so the runtime accessor enforces the same contract loudly."""
    spec = ENV_VARS.get(name)
    if spec is None:
        raise KeyError(
            f"environment variable {name!r} is not declared in "
            f"splatt_tpu.utils.env.ENV_VARS; register it (with a doc "
            f"string) before reading it")
    raw = os.environ.get(name)
    return spec.default if raw is None else raw


def _read_env_parsed(name: str, parse, kind: str):
    """Shared warn-and-default parse: a malformed value degrades to
    the registered default with one stderr line instead of killing the
    process at some random read site."""
    val = read_env(name)
    if isinstance(val, str):
        try:
            return parse(val)
        except (TypeError, ValueError):
            print(f"splatt-tpu: bad {name}={val!r} (want {kind}); "
                  f"using the default", file=sys.stderr)
            return ENV_VARS[name].default
    return val


def env_is_set(name: str) -> bool:
    """Whether the PROCESS environment explicitly sets a declared
    variable (as opposed to the registered default applying).  The
    autotuner uses this to tell a pinned format knob (measure only
    that) from an untouched default (measure the candidate matrix)."""
    if name not in ENV_VARS:
        raise KeyError(
            f"environment variable {name!r} is not declared in "
            f"splatt_tpu.utils.env.ENV_VARS")
    return name in os.environ


def read_env_int(name: str) -> Optional[int]:
    """:func:`read_env` + int parse (warn-and-default on bad values)."""
    return _read_env_parsed(name, int, "an int")


def read_env_float(name: str) -> Optional[float]:
    """:func:`read_env` + float parse (warn-and-default on bad values)."""
    return _read_env_parsed(name, float, "a float")


def ceil_to(x: int, mult: int) -> int:
    """Round x up to a multiple of mult."""
    return ((x + mult - 1) // mult) * mult


def max_mean_ratio(a) -> float:
    """round(max/mean, 3) of a nonnegative weight array — THE imbalance
    convention every layout/shard balance stat reports
    (docs/layout-balance.md); 1.0 means perfectly balanced (or empty).
    One definition so the slice/block/span/shard numbers in the run
    report, ``splatt cpd --json``, bench and MULTICHIP never drift."""
    import numpy as np

    a = np.asarray(a)
    mean = float(a.mean()) if a.size else 0.0
    return round(float(a.max()) / mean, 3) if mean > 0 else 1.0


def check_int32_dims(dims) -> None:
    """Device indices are int32 (≙ the reference's compile-time
    splatt_idx_t choice, include/splatt/types_config.h:38-43), and the
    blocked layouts use `dim` itself as the padding sentinel — so every
    dim must fit strictly below INT32_MAX.  Called by each path that
    casts host int64 coordinates down (layout build, nnz sharding,
    bucket scatter) so overflow fails loudly instead of wrapping.
    """
    limit = 2**31 - 1
    if max(dims, default=0) >= limit:
        raise ValueError(
            f"dims {tuple(dims)} exceed the int32 device index width "
            f"(max dim must be < {limit}); relabel/split the mode first")


def host_fence(x):
    """Wait until `x` and everything it depends on has executed on the
    device (``jax.block_until_ready``).  Returns `x` for chaining."""
    import jax

    return jax.block_until_ready(x)


def _checkout_root():
    """The splatt-tpu checkout this package runs from, or None when it
    is installed elsewhere (no pyproject.toml beside the package)."""
    from pathlib import Path

    root = Path(__file__).resolve().parents[2]
    return root if (root / "pyproject.toml").exists() else None


def compile_cache_dir() -> Optional[str]:
    """Where JAX's persistent compile cache lives for this process:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (a fixed, git-ignored path — the path is part of the cache key, so
    it must not move between runs).  None: no cache (an installed
    package with no checkout, or a CPU process with virtual devices,
    see :func:`apply_compile_cache`)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if ("xla_force_host_platform_device_count"
            in os.environ.get("XLA_FLAGS", "")):
        return None
    root = _checkout_root()
    return str(root / ".jax_cache") if root is not None else None


def apply_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compile cache for this process and
    return its directory (None when off).  Entry points (the CLI,
    bench.py, chip_smoke.py) call this before their first compile.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Either way the caching floors are
    zeroed: a serve fleet's steady state is many small same-regime
    compiles, exactly what the default min-compile-time floor would
    refuse to persist.

    No default cache under virtual CPU devices (XLA_FLAGS
    ``--xla_force_host_platform_device_count``): on this jaxlib,
    executing a DESERIALIZED multi-device CPU executable corrupts the
    process heap (malloc abort inside pxla).  Set
    ``JAX_COMPILATION_CACHE_DIR`` explicitly only for processes that
    run single-device programs there (the fleet soak's replicas).
    """
    path = compile_cache_dir()
    if path is None:
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
