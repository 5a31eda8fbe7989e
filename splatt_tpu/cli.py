"""Command-line interface (≙ src/cmds/: the `splatt` binary).

Verbs mirror splatt_cmds.h:77-92: cpd, bench, check, convert, reorder,
stats.  Invoke as ``python -m splatt_tpu.cli <verb> ...`` or via the
``splatt-tpu`` console entry.

Example (≙ `splatt cpd mytensor.tns -r 16 -v`):

    python -m splatt_tpu.cli cpd mytensor.tns -r 16 -v
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

import numpy as np

from splatt_tpu.reorder import PERM_TYPES
from splatt_tpu.utils.env import apply_compile_cache


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def _common_opts(p: argparse.ArgumentParser) -> None:
    p.add_argument("tensor", help="coordinate tensor file (.tns/.bin)")
    p.add_argument("-v", "--verbose", action="count", default=0,
                   help="increase verbosity (repeatable)")


def _trace_opt(p: argparse.ArgumentParser) -> None:
    p.add_argument("--trace", metavar="OUT_JSON",
                   help="record structured spans for this run and "
                        "export a perfetto-loadable Chrome trace-event "
                        "JSON file on exit (docs/observability.md); "
                        "summarize it with `splatt trace OUT_JSON`")


def _build_opts(args) -> "Options":
    from splatt_tpu.config import BlockAlloc, Options, Verbosity

    opts = Options()
    opts.verbosity = Verbosity(min(1 + getattr(args, "verbose", 0), 3))
    if getattr(args, "tol", None) is not None:
        opts.tolerance = args.tol
    if getattr(args, "iters", None) is not None:
        opts.max_iterations = args.iters
    if getattr(args, "reg", None) is not None:
        opts.regularization = args.reg
    if getattr(args, "seed", None) is not None:
        opts.random_seed = args.seed
    if getattr(args, "alloc", None):
        opts.block_alloc = BlockAlloc(args.alloc)
    if getattr(args, "block", None):
        opts.nnz_block = args.block
    if getattr(args, "f64", False):
        opts.val_dtype = np.dtype(np.float64)  # splint: ignore[SPL005] the --f64 flag IS the user-facing dtype contract
    if getattr(args, "mode_order", None):
        from splatt_tpu.config import ModeOrder
        opts.mode_order = ModeOrder(args.mode_order)
    if getattr(args, "engine_fallback", None):
        opts.engine_fallback = args.engine_fallback == "on"
    if getattr(args, "autotune", None):
        opts.autotune = args.autotune == "on"
    return opts


def _resilience_record(report, **extra) -> dict:
    """The machine-readable run summary for --json verbs: final fit,
    every run-report event (health rollbacks included) and every
    engine demotion — the same facts the human summary prints."""
    from splatt_tpu import resilience

    return dict(
        extra,
        degraded=bool(report.events("health_degraded")),
        events=[{k: v for k, v in e.items() if k != "ts"}
                for e in report.events()],
        demotions=[dict(engine=d.engine,
                        failure_class=d.failure_class.value,
                        shape_key=d.shape_key, error=d.error[:120])
                   for d in resilience.demotions()])


def cmd_cpd(args) -> int:
    """≙ splatt_cpd_cmd (src/cmds/cmd_cpd.c:159-243; distributed flags ≙
    the mpirun variant's -d, src/cmds/mpi_cmd_cpd.c:175-338)."""
    import jax

    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import CommPattern, Decomposition, Verbosity
    from splatt_tpu.cpd import cpd_als
    from splatt_tpu.io import load, read_permutation
    from splatt_tpu.stats import cpd_stats_text, tensor_stats
    from splatt_tpu.utils.timers import timers

    opts = _build_opts(args)
    if getattr(args, "comm", None):
        opts.comm_pattern = CommPattern(args.comm)
    timers.start("total")
    with timers.time("io"):
        if args.mmap:
            from splatt_tpu.io import load_memmap

            tt = load_memmap(args.tensor)
        else:
            tt = load(args.tensor)
    print(tensor_stats(tt, args.tensor))

    distributed = (args.decomp is not None or args.grid is not None
                   or args.partition is not None or args.comm is not None
                   or args.rowdist is not None)
    if distributed:
        from splatt_tpu.parallel import distributed_cpd_als

        if args.decomp:
            opts.decomposition = Decomposition(args.decomp)
        elif args.grid:
            opts.decomposition = Decomposition.MEDIUM
        elif args.comm or args.partition or args.rowdist:
            # comm patterns, partitions and row distribution are
            # fine-decomposition concepts
            opts.decomposition = Decomposition.FINE
        if args.partition and opts.decomposition is not Decomposition.FINE:
            raise ValueError(
                "-p/--partition is a FINE-decomposition input; combine it "
                f"with --decomp fine, not {opts.decomposition.value}")
        if (args.comm in ("point2point", "async_ring")
                and opts.decomposition is not Decomposition.FINE):
            raise ValueError(
                f"--comm {args.comm} (ring) applies to the fine "
                f"decomposition only")
        if args.grid and opts.decomposition is not Decomposition.MEDIUM:
            raise ValueError(
                "--grid applies to the medium decomposition only")
        grid = None
        if args.grid:
            grid = tuple(int(g) for g in args.grid.split("x"))
            if len(grid) != tt.nmodes or any(g < 1 for g in grid):
                raise ValueError(
                    f"--grid must give one positive factor per mode "
                    f"({tt.nmodes} modes), got {args.grid!r}")
        partition = (read_permutation(args.partition)
                     if args.partition else None)
        print(f"DISTRIBUTED decomp={opts.decomposition.value} "
              f"devices={len(jax.devices())}"
              + (f" grid={args.grid}" if args.grid else ""))
        # --json ring runs always carry the achieved-overlap metric
        # (docs/ring.md); otherwise the driver's HIGH-verbosity auto
        # gating applies (the measurement costs extra compiles)
        out = distributed_cpd_als(tt, rank=args.rank, opts=opts, grid=grid,
                                  partition=partition,
                                  row_distribute=args.rowdist,
                                  checkpoint_path=args.checkpoint,
                                  checkpoint_every=args.checkpoint_every,
                                  local_engine=args.local_engine,
                                  out_dir=args.scratch_dir,
                                  measure_overlap=(True if args.json
                                                   else None))
        bs = None
    else:
        if args.scratch_dir:
            # never silently ignore an explicit out-of-core request
            raise ValueError(
                "--scratch-dir applies to distributed runs (--decomp/"
                "--grid/...); the single-chip blocked build "
                "materializes its layouts in RAM")
        with timers.time("blocked_build"):
            # compile (not from_coo): with autotune on, the layouts are
            # built directly at the plan cache's tuned nnz_block
            bs = BlockedSparse.compile(tt, opts, rank=args.rank)
        print(cpd_stats_text(bs, args.rank, opts))
        out = cpd_als(bs, rank=args.rank, opts=opts,
                      checkpoint_path=args.checkpoint,
                      checkpoint_every=args.checkpoint_every)
    print(f"Final fit: {float(out.fit):0.5f}")
    # resilience report: silent degradation (engine demotions,
    # transient retries, health rollbacks, checkpoint recoveries) must
    # be observable in the run log, not only in exit codes — on the
    # single-device AND distributed paths alike
    from splatt_tpu import resilience

    report = resilience.run_report()
    if opts.verbosity >= Verbosity.LOW:
        lines = report.summary()
        if lines:
            print("Resilience events:")
            for line in lines:
                print(line)
    if getattr(args, "json", False):
        import json as _json

        print(_json.dumps(_resilience_record(report, fit=float(out.fit))))
    if bs is not None and opts.verbosity >= Verbosity.HIGH:
        # per-mode MTTKRP profile (≙ the per-mode times of `cpd -v -v`,
        # src/cpd.c:361-366) — at HIGH verbosity cpd_als runs the
        # split-jit profiled sweep, so these are true in-loop totals
        print("Per-mode MTTKRP time (in-loop totals):")
        for m in range(bs.nmodes):
            print(f"  mode {m}: {timers[f'mttkrp_mode{m}']:0.3f}s")
    if not args.nowrite:
        # ≙ the reference's -s file-stem semantics (cmd_cpd.c:209-230):
        # a bare stem writes <stem>.mode<N>.mat / <stem>.lambda.mat (the
        # reference's asprintf inserts the '.'); a directory-like stem
        # writes plain mode<N>.mat inside that directory.
        import os as _os

        stem_arg = args.stem
        if (stem_arg.endswith(_os.sep) or stem_arg in (".", "./")
                or _os.path.isdir(stem_arg)):
            out.save(stem_arg.rstrip(_os.sep) or ".", stem="")
        else:
            d, base = _os.path.split(stem_arg)
            out.save(d or ".", stem=base + ".")
    timers.stop("total")
    if opts.verbosity >= Verbosity.LOW:
        print(timers.report(level=2 if opts.verbosity >= Verbosity.HIGH
                            else 1))
    return 0


def cmd_tune(args) -> int:
    """Pre-tune a tensor offline (docs/autotune.md): measure the
    candidate MTTKRP plans — engine x nnz_block x scan_target — per
    mode and persist the winners in the plan cache, so later `cpd`
    runs (and other tensors in the same shape regime) dispatch straight
    to the measured-fastest configuration with zero measurement cost."""
    from splatt_tpu import tune
    from splatt_tpu.io import load
    from splatt_tpu.stats import tensor_stats

    opts = _build_opts(args)
    tt = load(args.tensor)
    print(tensor_stats(tt, args.tensor))
    res = tune.tune(tt, rank=args.rank, opts=opts, reps=args.reps,
                    force=args.force)
    for m in sorted(res.plans):
        p = res.plans[m]
        print(f"  mode {m}: path={p.path} engine={p.engine} "
              f"nnz_block={p.nnz_block} scan_target={p.scan_target} "
              f"({p.sec:.4f}s/call)")
    print(f"tuned {len(res.plans)}/{tt.nmodes} modes "
          f"({res.measured} measurements, {res.cache_hits} cache hits, "
          f"{res.skipped} skipped) -> {tune.cache_path()}")
    from splatt_tpu import resilience

    lines = resilience.run_report().summary()
    if lines:
        print("Resilience events:")
        for line in lines:
            print(line)
    return 0 if res.plans else 1


def cmd_chaos(args) -> int:
    """Chaos-schedule soak (docs/guarded-als.md): run a small seeded
    CPD under injected NaNs / blown deadlines / transient failures and
    assert the guarded-execution invariant — converged or gracefully
    degraded, zero unhandled exceptions, complete run report.  Exit 0
    iff the invariant held."""
    from splatt_tpu import chaos

    if args.fleet:
        # fleet soak: SIGKILL-and-restart across N replica daemons
        # over one spool under multi-tenant load (docs/fleet.md)
        res = chaos.run_fleet_chaos(seed=args.seed, smoke=args.smoke,
                                    replicas=args.replicas,
                                    verbose=args.verbose > 0)
        for line in chaos.format_fleet_report(res):
            print(line)
        if args.json:
            import json as _json

            print(_json.dumps(res.to_json()))
        return 0 if res.ok else 1
    if args.serve:
        # serve-daemon soak: SIGKILL a real daemon mid-queue, restart,
        # assert no accepted job is lost and one tenant's NaN never
        # demotes a neighbor's engines (docs/serve.md)
        res = chaos.run_serve_chaos(seed=args.seed, smoke=args.smoke,
                                    verbose=args.verbose > 0)
        for line in chaos.format_serve_report(res):
            print(line)
        if args.json:
            import json as _json

            print(_json.dumps(res.to_json()))
        return 0 if res.ok else 1
    if args.ingest:
        # ingest soak: SIGKILL a real `splatt ingest` subprocess
        # mid-stream, restart it, and audit the chunk journal ALONE
        # for the exactly-once invariant (docs/ingest.md)
        res = chaos.run_ingest_chaos(seed=args.seed, smoke=args.smoke,
                                     verbose=args.verbose > 0)
        for line in chaos.format_ingest_report(res):
            print(line)
        if args.json:
            import json as _json

            print(_json.dumps(res.to_json()))
        return 0 if res.ok else 1
    # schedule resolution (--schedule, else $SPLATT_CHAOS_SCHEDULE,
    # else the default recipe) lives in run_chaos — the single owner;
    # the resolved string comes back on the result for reporting
    res = chaos.run_chaos(schedule=args.schedule, seed=args.seed,
                          rank=args.rank, iters=args.iters,
                          deadline_s=args.deadline,
                          smoke=args.smoke,
                          verbose=args.verbose > 0,
                          trace_path=args.trace)
    for line in chaos.format_report(res):
        print(line)
    gate_ok = True
    if args.bench_gate:
        # the PR 6 bench regression gate rides the chaos smoke tier
        # (docs/format.md): a >10% time OR encoded-bytes regression
        # against the newest same-metric prior fails the run loudly
        gate = chaos.run_bench_gate(smoke=args.smoke)
        gate_ok = gate["ok"]
        verdict = "passed" if gate_ok else "FAILED"
        print(f"bench gate: {verdict} (exit {gate['returncode']})")
        if not gate_ok and gate.get("stderr_tail"):
            print(gate["stderr_tail"])
        if gate.get("record"):
            rec = gate["record"]
            print(f"bench gate: value={rec.get('value')} "
                  f"{rec.get('unit')} "
                  f"gb_per_path={rec.get('model_gb_per_path')} "
                  f"format={rec.get('format')}")
    if args.json:
        import json as _json

        print(_json.dumps(res.to_json()))
    return 0 if (res.ok and gate_ok) else 1


def cmd_ingest(args) -> int:
    """`splatt ingest` — stream a raw record file (.tns / CSV /
    JSONL) into a COO tensor under the exactly-once chunk journal
    (docs/ingest.md).  Re-running the same SOURCE into the same DEST
    resumes from the journal watermark: zero lost, zero duplicated
    records.  Exit 0 on a converged (finalized) run, 1 when the
    quarantine budget degraded it or nothing could be committed."""
    import json as _json

    from splatt_tpu import ingest, resilience

    dims = None
    if args.dims:
        try:
            dims = tuple(int(d) for d in args.dims.lower().split("x"))
        except ValueError:
            print(f"splatt ingest: bad --dims {args.dims!r} "
                  f"(want IxJxK)", flush=True)
            return 2
    try:
        summary = ingest.ingest_stream(
            args.source, args.dest, fmt=args.format,
            chunk_records=args.chunk, dims=dims,
            quarantine_max=args.quarantine_max,
            quarantine_rate=args.quarantine_rate)
    except (OSError, ValueError) as e:
        cls = resilience.classify_failure(e)
        print(f"splatt ingest: FAILED ({cls.value}): "
              f"{resilience.failure_message(e)[:200]}", flush=True)
        if args.json:
            print(_json.dumps({"status": "failed",
                               "failure_class": cls.value,
                               "error": str(e)[:200]}))
        return 1
    verb = "resumed and " if summary["resumed"] else ""
    print(f"splatt ingest: {verb}{summary['status']} — "
          f"{summary['chunks']} chunk(s), {summary['nnz']} nnz from "
          f"{summary['records']} record(s) "
          f"({summary['quarantined']} quarantined) at "
          f"{summary['records_per_sec']} rec/s")
    if summary.get("tensor"):
        print(f"splatt ingest: tensor at {summary['tensor']} "
              f"(dims {'x'.join(str(d) for d in summary['dims'])})")
    lines = resilience.run_report().summary()
    if lines:
        print("Resilience events:")
        for line in lines:
            print(line)
    if args.json:
        print(_json.dumps(summary))
    return 0 if summary["status"] == "converged" else 1


def cmd_serve(args) -> int:
    """`splatt serve` — the isolated, crash-resumable multi-tenant
    decomposition daemon (docs/serve.md).  Daemon mode runs the
    journal-backed queue over DIR; --submit/--status are the
    client-side filed-request API."""
    import json as _json

    from splatt_tpu import serve

    if args.submit:
        with open(args.submit) as f:
            spec = _json.load(f)
        jid = serve.file_request(args.dir, spec)
        print(_json.dumps({"job": jid, "filed": True}))
        return 0
    if args.status:
        print(_json.dumps(serve.read_status(args.dir, args.status)))
        return 0
    srv = serve.Server(args.dir, workers=args.workers,
                       queue_max=args.queue_max, poll_s=args.poll,
                       job_deadline_s=args.job_deadline,
                       verbose=args.verbose > 0,
                       fleet=args.fleet, replica=args.replica,
                       lease_s=args.lease, heartbeat_s=args.heartbeat,
                       tenant_quota=args.tenant_quota,
                       batch_min=args.batch_min)
    if args.fleet:
        # fleet observability wiring (docs/observability.md): stamp
        # every span/point with this replica's id (what merged traces
        # key on) and arm the flight recorder — span recording on + a
        # bounded per-replica ring in the spool, so a SIGKILLed
        # replica leaves a readable black box.  SPLATT_FLIGHT=0/off
        # opts out of the ring; done here (the daemon entry) rather
        # than in Server so library/test constructions never flip
        # process-wide tracing state behind the caller's back.
        import os as _os

        from splatt_tpu import trace
        from splatt_tpu.utils.env import read_env

        trace.set_replica(srv.fleet.replica)
        flight = str(read_env("SPLATT_FLIGHT") or "auto").lower()
        trace_off = str(read_env("SPLATT_TRACE") or "").lower() in (
            "0", "off", "false", "no")
        if flight not in ("0", "off", "false", "no") and trace_off:
            # an EXPLICIT SPLATT_TRACE=0 wins over the flight
            # recorder's auto-arm: the documented recording switch
            # must not be silently overridden — say so instead
            print("splatt-serve: flight recorder off — SPLATT_TRACE "
                  "is explicitly disabled (set SPLATT_FLIGHT=0 to "
                  "silence this, or drop SPLATT_TRACE=0 to arm the "
                  "black box)", file=sys.stderr)
        elif flight not in ("0", "off", "false", "no"):
            fdir = _os.path.join(args.dir, "fleet", "flight")
            _os.makedirs(fdir, exist_ok=True)
            trace.set_enabled(True)
            trace.set_flight(_os.path.join(
                fdir, f"{srv.fleet.replica}.jsonl"))
    srv.install_signal_handlers()
    try:
        summary = srv.run_once() if args.once else srv.serve_forever()
        if args.once:
            # batch mode exits without the daemon loop's exit
            # snapshot: force one here — BEFORE the fleet retirement
            # below, so the exit aggregation still sees this replica's
            # heartbeat (docs/observability.md)
            srv.write_metrics_now()
    finally:
        if args.fleet:
            # retire the membership lease on the way out: peers route
            # around this replica immediately (docs/fleet.md), and the
            # black box keeps everything recorded up to this exit
            srv.shutdown()
            from splatt_tpu import trace

            trace.flight_flush()
    from splatt_tpu import resilience

    lines = resilience.run_report().summary()
    if lines and args.verbose > 0:
        print("Resilience events:")
        for line in lines:
            print(line)
    print(_json.dumps(summary if args.json
                      else {"jobs": summary["counts"],
                            "pending": summary["pending"]}))
    # --once is the batch/CI entry: nonzero when any accepted job
    # failed outright (degraded-but-terminal is a success of the
    # guarded contract; interrupted jobs resume next start)
    if args.once and summary["counts"].get(serve.FAILED):
        return 1
    return 0


def cmd_bench(args) -> int:
    """≙ splatt_bench_cmd (src/cmds/cmd_bench.c:198-286)."""
    from splatt_tpu.bench_algs import ALGS, bench_mttkrp, format_bench
    from splatt_tpu.io import load
    from splatt_tpu.reorder import reorder
    from splatt_tpu.stats import tensor_stats

    opts = _build_opts(args)
    tt = load(args.tensor)
    print(tensor_stats(tt, args.tensor))
    if args.permute:
        perm = reorder(tt, args.permute, seed=opts.seed())
        tt = perm.apply(tt)
        print(f"  (reordered: {args.permute})")
    algs = args.alg or list(ALGS)
    results, layouts = bench_mttkrp(tt, rank=args.rank, algs=algs,
                                    opts=opts, reps=args.reps,
                                    return_layouts=True)
    print(f"Benchmarking MTTKRP, rank {args.rank}, {args.reps} reps")
    print(format_bench(results))
    from splatt_tpu.bench_algs import roofline_report
    from splatt_tpu.config import resolve_dtype as _rd

    print("Effective bandwidth (first-order bytes model):")
    for line in roofline_report(
            tt, results, args.rank,
            np.dtype(_rd(opts, tt.vals.dtype)).itemsize, layouts):
        print(line)
    if args.check:
        from splatt_tpu.bench_algs import crosscheck_mttkrp
        from splatt_tpu.config import resolve_dtype

        dev = crosscheck_mttkrp(tt, rank=args.rank, algs=algs, opts=opts)
        print(f"cross-check max relative |alg - stream| = {dev:.3e}")
        # tolerance follows the dtype actually computed in (a float64
        # request degrades to float32 when x64 is off)
        tol = (1e-10 if resolve_dtype(opts, tt.vals.dtype) == np.float64  # splint: ignore[SPL005] crosscheck tolerance selection names the dtype on purpose
               else 9e-3)
        if dev > tol:
            print(f"error: algorithms disagree beyond tolerance {tol}")
            return 1
    return 0


def cmd_trace(args) -> int:
    """`splatt trace <file>...` — summarize (and with multiple inputs,
    MERGE) recorded traces (docs/observability.md): top spans by
    self-time, per-iteration breakdown, guard-overhead share,
    point-event counts, and — for fleet traces — per-replica job
    counts and adoption lineage.  Inputs may be Chrome trace-event
    JSON files (``--trace`` exports), flight-recorder ``.jsonl`` rings
    (a SIGKILLed replica's black box), or a directory holding both;
    multiple sources merge onto one wall-clock timeline with flow
    events linking each adopted job's victim and adopter rows."""
    from splatt_tpu import trace

    files = trace.expand_trace_paths(args.file)
    if not files:
        raise ValueError(f"no trace files under {args.file}")
    if len(files) == 1 and not files[0].endswith(".jsonl"):
        events = trace.load_trace(files[0])
    else:
        events = trace.merge_trace_files(files)
    if args.out:
        from splatt_tpu.utils.durable import publish_json

        publish_json(args.out, {"traceEvents": events,
                                "displayTimeUnit": "ms"})
        # stderr: --json's stdout is a machine-readable contract
        print(f"merged trace ({len(files)} source(s)) written to "
              f"{args.out} — load it in ui.perfetto.dev",
              file=sys.stderr)
    s = trace.summarize(events)
    if args.json:
        import json as _json

        # tuples JSON-serialize as lists; drop the redundant "top"
        # ordering (recoverable from "names") for a stable schema
        print(_json.dumps({k: v for k, v in s.items() if k != "top"}))
        return 0
    for line in trace.format_summary(s, top_n=args.top):
        print(line)
    return 0


def cmd_status(args) -> int:
    """`splatt status DIR` / `splatt top DIR` — the fleet dashboard,
    read ONLY from the shared spool (docs/fleet.md): replicas with
    lease freshness, queue depths, per-tenant usage, running jobs with
    age, recent terminal jobs, SLO verdicts.  ``--metrics-out`` writes
    the merged fleet Prometheus exposition; ``--watch`` refreshes
    (`top` watches by default)."""
    import json as _json
    import time as _time

    from splatt_tpu import fleetobs
    from splatt_tpu.utils.env import read_env_float

    interval = float(args.interval if args.interval is not None
                     else read_env_float("SPLATT_STATUS_WATCH_S"))

    def once(clear: bool = False) -> None:
        # ONE aggregation pass feeds both the status view and the
        # optional merged-exposition write (the spool is scanned once
        # per tick, not twice)
        agg = fleetobs.aggregate(args.dir)
        st = fleetobs.fleet_status(args.dir, agg=agg)
        out = []
        if args.metrics_out:
            path = fleetobs.write_fleet_metrics(agg, args.metrics_out)
            out.append(f"fleet metrics written to {path}")
        if args.json:
            out.append(_json.dumps(st))
        else:
            out.extend(fleetobs.format_status(st))
        if clear:
            print("\x1b[2J\x1b[H", end="")
        print("\n".join(out), flush=True)

    if not args.watch or interval <= 0:
        # SPLATT_STATUS_WATCH_S=0 (or --interval 0) means run-once even
        # for the watch-by-default `splatt top` — what tests and
        # scripted status reads set instead of killing a sleep loop
        once()
        return 0
    try:
        while True:
            once(clear=not args.json)
            _time.sleep(max(interval, 0.1))
    except KeyboardInterrupt:
        return 0


def cmd_predict(args) -> int:
    """`splatt predict DIR` — file one generation-fenced predict
    against a committed model and (optionally) wait for the answer
    (docs/predict.md).  Speaks only the spool filed-request API
    (file_request + read_status), so it works against any replica of
    a live fleet, exactly like `splatt serve --submit`."""
    import json as _json
    import time as _time

    from splatt_tpu import serve

    spec: dict = {"kind": "predict", "model": args.model}
    if args.id:
        spec["id"] = args.id
    if args.tenant:
        spec["tenant"] = args.tenant
    if args.coords:
        spec["coords"] = [[int(x) for x in c.split(",")]
                          for c in args.coords]
    if args.top_k:
        fixed = {}
        for kv in (args.fix or []):
            m, _, i = kv.partition("=")
            fixed[int(m)] = int(i)
        spec["top_k"] = {"mode": args.mode, "k": args.top_k,
                         "fixed": fixed}
    jid = serve.file_request(args.dir, spec)
    if not args.wait:
        print(_json.dumps({"job": jid, "filed": True}))
        return 0
    end = _time.time() + float(args.wait)
    while _time.time() < end:
        st = serve.read_status(args.dir, jid)
        if st.get("state") in serve.TERMINAL:
            out = st.get("result") or {"job": jid,
                                       "state": st.get("state")}
            print(_json.dumps(out))
            return 0 if out.get("status") == "served" else 1
        _time.sleep(0.2)
    print(_json.dumps({"job": jid, "state": "pending",
                       "error": "timed out waiting for the answer"}))
    return 1


def cmd_check(args) -> int:
    """≙ splatt_check_cmd (src/cmds/cmd_check.c:63-116): find (and
    optionally fix) duplicate nonzeros and empty slices."""
    from splatt_tpu.io import load, save

    tt = load(args.tensor)
    # out-of-range first: the histogram-based stats below assume
    # in-range indices (negative ones crash np.bincount)
    noob = sum(int(np.count_nonzero((tt.inds[m] < 0)
                                    | (tt.inds[m] >= tt.dims[m])))
               for m in range(tt.nmodes))
    if noob:
        print(f"out-of-range: {noob}")
        print("error: tensor declares indices outside its dimensions "
              "(corrupt file?)")
        return 1
    ndup = tt.count_duplicates()
    nempty = sum(tt.dims[m] - tt.nslices_nonempty(m)
                 for m in range(tt.nmodes))
    print(f"duplicates: {ndup}  empty slices: {nempty}  "
          f"out-of-range: 0")
    if args.fix:
        fixed = tt.deduplicate().remove_empty_slices()
        save(fixed, args.fix)
        print(f"wrote fixed tensor: {args.fix} "
              f"(nnz {tt.nnz} -> {fixed.nnz}, dims {tt.dims} -> {fixed.dims})")
    return 0 if (ndup == 0 and nempty == 0) else 1


def cmd_convert(args) -> int:
    """≙ splatt_convert_cmd (src/cmds/cmd_convert.c)."""
    from splatt_tpu.convert import convert
    from splatt_tpu.io import load

    if args.type == "bin":
        # streaming text→binary when the native runtime is built:
        # bounded memory, scales past RAM (1.7B-nnz-class ingest)
        with open(args.tensor, "rb") as f:
            is_binary = f.read(4) == b"SPTT"
        if not is_binary:
            from splatt_tpu import native

            if native.stream_to_bin(args.tensor, args.output):
                print(f"wrote bin (streamed): {args.output}")
                return 0
    tt = load(args.tensor)
    convert(tt, args.type, args.output, mode=args.mode)
    print(f"wrote {args.type}: {args.output}")
    return 0


def cmd_reorder(args) -> int:
    """≙ splatt_reorder_cmd (src/cmds/cmd_reorder.c)."""
    from splatt_tpu.io import load, save, write_permutation
    from splatt_tpu.reorder import reorder

    tt = load(args.tensor)
    perm = reorder(tt, args.type, seed=args.seed or 0)
    out = perm.apply(tt)
    save(out, args.output)
    for m, p in enumerate(perm.perms):
        if p is not None and args.write_perms:
            write_permutation(p, f"{args.output}.perm{m}")
    print(f"wrote reordered tensor: {args.output}")
    return 0


def cmd_stats(args) -> int:
    """≙ splatt_stats_cmd (src/cmds/cmd_stats.c; -p gives the hypergraph
    partition-quality stats, src/stats.c:53-170)."""
    from splatt_tpu.io import load, read_permutation
    from splatt_tpu.stats import (density_stats_text,
                                  partition_quality_text, skew_stats_text,
                                  tensor_stats)

    tt = load(args.tensor)
    print(tensor_stats(tt, args.tensor))
    if args.partition:
        print(partition_quality_text(tt, read_permutation(args.partition)))
    for m in range(tt.nmodes):
        hist = tt.mode_histogram(m)
        nz = hist[hist > 0]
        print(f"  mode {m}: dim={tt.dims[m]} nonempty={nz.size} "
              f"nnz/slice min={nz.min() if nz.size else 0} "
              f"avg={tt.nnz / max(nz.size, 1):.1f} "
              f"max={nz.max() if nz.size else 0}")
    # slice/fiber skew (docs/layout-balance.md): uniform vs power-law
    # is the first question the layout/tuner answer depends on
    print(skew_stats_text(tt))
    # per-mode density (docs/dense.md): dense-tile vs sparse-blocked is
    # the other axis the layout/tuner answer depends on
    print(density_stats_text(tt))
    return 0


def build_parser() -> argparse.ArgumentParser:
    from splatt_tpu.version import __version__

    ap = argparse.ArgumentParser(
        prog="splatt-tpu",
        description="Sparse tensor factorization on TPU "
                    "(CPD-ALS over blocked sparse formats)")
    ap.add_argument("-V", "--version", action="version",
                    version=f"splatt-tpu {__version__}")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("cpd", help="compute the CPD of a sparse tensor")
    _common_opts(p)
    p.add_argument("-r", "--rank", type=int, default=10)
    p.add_argument("-t", "--tol", type=float)
    p.add_argument("-i", "--iters", type=int)
    p.add_argument("--reg", type=float)
    p.add_argument("--seed", type=int)
    p.add_argument("--alloc", choices=["onemode", "twomode", "allmode"])
    p.add_argument("--mode-order", dest="mode_order",
                   choices=["smallfirst", "bigfirst", "inorder_minusone",
                            "sorted_minusone"],
                   help="secondary mode ordering within a layout "
                        "(reference csf_find_mode_order policies)")
    p.add_argument("--block", type=int, help="nnz per block")
    p.add_argument("--f64", action="store_true", help="double precision")
    p.add_argument("--nowrite", action="store_true",
                   help="skip writing factor files")
    p.add_argument("-s", "--stem", default="./", metavar="PATH",
                   help="file stem for factor output files (default: ./) "
                        "— reference semantics: <stem>.mode1.mat etc.; a "
                        "trailing / (or an existing directory) writes "
                        "plain mode1.mat into that directory")
    # distributed flags (≙ mpirun splatt cpd -d IxJxK / -d f -p partfile)
    p.add_argument("--decomp", choices=["medium", "coarse", "fine"],
                   help="run distributed over all devices with this "
                        "decomposition")
    p.add_argument("--grid", metavar="IxJxK",
                   help="device grid for the medium decomposition")
    p.add_argument("-p", "--partition", metavar="FILE",
                   help="per-nonzero partition file (fine decomposition)")
    p.add_argument("--comm", choices=["all2all", "point2point",
                                      "async_ring"],
                   help="row-exchange pattern for --decomp fine "
                        "(default: $SPLATT_COMM, else all2all): "
                        "point2point = ppermute ring, memory-lean; "
                        "async_ring = Pallas remote-copy ring "
                        "(refused on TPU until its at-scale stall is "
                        "found; elsewhere the ppermute dataflow, "
                        "docs/ring.md)")
    p.add_argument("--rowdist", choices=["greedy", "balanced"],
                   help="factor-row distribution: greedy = comm-"
                        "minimizing row claiming for --decomp fine "
                        "(reference mpi_mat_distribute semantics); "
                        "balanced = nnz-weighted fences (chains-on-"
                        "chains LPT, docs/layout-balance.md) for fine "
                        "and coarse, so a device owning hot slices no "
                        "longer gates the exchange")
    p.add_argument("--local-engine", choices=["blocked", "stream"],
                   dest="local_engine",
                   help="per-device MTTKRP engine for distributed runs "
                        "(default auto: blocked sorted layouts; "
                        "memmapped tensors build them via streamed "
                        "chunked passes)")
    p.add_argument("--scratch-dir", dest="scratch_dir", metavar="DIR",
                   help="disk-backed scratch for distributed "
                        "decomposition arrays: with a memmapped tensor "
                        "the whole build is out-of-core (bounded host "
                        "RSS at any scale)")
    p.add_argument("--mmap", action="store_true",
                   help="memory-map a binary tensor instead of loading "
                        "it (O(1) host RAM for the LOAD; pair with a "
                        "distributed --decomp and --scratch-dir for a "
                        "fully out-of-core build — the single-chip "
                        "blocked build still materializes its layouts)")
    p.add_argument("--checkpoint", metavar="FILE",
                   help="write an atomic .npz checkpoint (checksummed; "
                        "previous generation kept as .bak) every "
                        "--checkpoint-every iterations and resume from "
                        "it when present (single-device and "
                        "distributed; checkpoints are device-count-"
                        "independent; a corrupt file degrades to the "
                        ".bak generation instead of crashing the resume)")
    p.add_argument("--checkpoint-every", type=_positive_int, default=10,
                   metavar="N", help="iterations between checkpoints")
    p.add_argument("--engine-fallback", choices=["on", "off"],
                   dest="engine_fallback",
                   help="runtime engine fallback (default on): a "
                        "failing MTTKRP engine is demoted and the next "
                        "engine in the chain runs instead of the "
                        "failure killing the run; 'off' fails loudly "
                        "(docs/resilience.md)")
    p.add_argument("--autotune", choices=["on", "off"],
                   help="consult the autotuner's plan cache for the "
                        "MTTKRP engine/block/scan plan (default on; "
                        "pre-tune with `splatt tune` — docs/autotune.md)")
    p.add_argument("--json", action="store_true",
                   help="also print a machine-readable JSON run "
                        "summary (fit, run-report events including "
                        "health rollbacks, engine demotions)")
    _trace_opt(p)
    p.set_defaults(fn=cmd_cpd)

    p = sub.add_parser(
        "chaos", help="chaos-schedule soak of the guarded ALS layer",
        epilog="Runs a small seeded synthetic CPD under a declarative "
               "fault schedule (same grammar as SPLATT_FAULTS, plus "
               "iter=k / p=x:seed=N / after=t schedule modifiers) and "
               "asserts: converged or gracefully degraded, zero "
               "unhandled exceptions, a complete run report, finite "
               "factors or an explicit degraded verdict "
               "(docs/guarded-als.md).  Exit 0 iff the invariant held.")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.add_argument("--schedule", metavar="SPEC",
                   help="fault schedule (default: "
                        "$SPLATT_CHAOS_SCHEDULE, else a seeded "
                        "NaN+deadline+transient recipe)")
    p.add_argument("--smoke", action="store_true",
                   help="seconds-scale seeded run on a tiny tensor "
                        "(the tier-1 CI entry)")
    p.add_argument("--bench-gate", action="store_true",
                   help="additionally run `python bench.py --gate` "
                        "(smoke-sized under --smoke): a >10% time or "
                        "encoded-bytes regression vs the newest "
                        "same-metric BENCH_*.json prior fails the run "
                        "(docs/format.md)")
    p.add_argument("--serve", action="store_true",
                   help="soak the serve daemon instead: SIGKILL a "
                        "real daemon mid-queue, restart it, and "
                        "assert no accepted job is lost and one "
                        "tenant's injected NaN never demotes a "
                        "neighbor's engines (docs/serve.md)")
    p.add_argument("--fleet", action="store_true",
                   help="soak a serve FLEET instead: N replica "
                        "daemons over one spool under multi-tenant "
                        "load, SIGKILL-and-restart a replica mid-job, "
                        "and assert no accepted job is lost, the "
                        "single-owner lineage holds, adoptions are "
                        "accounted in metrics, and adopted same-"
                        "regime jobs hit warm caches (docs/fleet.md)")
    p.add_argument("--replicas", type=int, default=None, metavar="N",
                   help="fleet soak: replica count (default 2 under "
                        "--smoke, else 3)")
    p.add_argument("--ingest", action="store_true",
                   help="soak the streaming-ingest plane instead: "
                        "SIGKILL a real `splatt ingest` subprocess "
                        "mid-stream, restart it, and audit the chunk "
                        "journal ALONE for zero lost and zero "
                        "duplicated records with every quarantined "
                        "record accounted (docs/ingest.md)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("-r", "--rank", type=int, default=4)
    p.add_argument("-i", "--iters", type=int, default=8)
    p.add_argument("--deadline", type=float, default=0.5, metavar="S",
                   help="watchdog budget for the run (seconds; the "
                        "slow fault kind blows it deliberately)")
    p.add_argument("--json", action="store_true",
                   help="also print the full ChaosResult as JSON")
    p.add_argument("--trace", metavar="OUT_JSON",
                   help="run the soak with span tracing on, export the "
                        "Chrome trace to OUT_JSON, and additionally "
                        "assert that every fired fault left a matching "
                        "point event ON THE TRACE (the exporter leg of "
                        "the invariant; docs/observability.md)")
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser(
        "ingest", help="stream a raw record file into a COO tensor",
        epilog="Chunked, crash-resumable ingest (docs/ingest.md): "
               "SOURCE is cut into chunks of --chunk records; each "
               "chunk parses (malformed records quarantined to "
               "DEST/quarantine.jsonl with classified events), "
               "vocab-maps string keys, publishes its segment "
               "atomically, and journals LAST — so a SIGKILL at any "
               "point resumes from DEST/journal.jsonl with zero lost "
               "and zero duplicated records.  A finalized run lands "
               "DEST/tensor.bin in the binary memmap layout "
               "(`splatt cpd DEST/tensor.bin --mmap ...`).")
    p.add_argument("source", help="record stream: .tns text, CSV, or "
                                  "JSONL arrays [i0, ..., val]")
    p.add_argument("dest", help="ingest state directory (journal, "
                                "seg/, vocab/, quarantine sidecar, "
                                "tensor.bin)")
    p.add_argument("--format", choices=["auto", "tns", "csv", "jsonl"],
                   default="auto",
                   help="record format (default: by file extension)")
    p.add_argument("--chunk", type=_positive_int, metavar="N",
                   help="records per chunk commit (default: "
                        "$SPLATT_INGEST_CHUNK; a resume must match "
                        "the journal's value)")
    p.add_argument("--dims", metavar="IxJxK",
                   help="declared mode sizes: out-of-range indices "
                        "quarantine as bad_index instead of growing "
                        "the tensor (required when chaining updates "
                        "against a served model)")
    p.add_argument("--quarantine-max", type=int, dest="quarantine_max",
                   metavar="N",
                   help="absolute bad-record budget (default: "
                        "$SPLATT_INGEST_QUARANTINE_MAX); past it the "
                        "run degrades classified")
    p.add_argument("--quarantine-rate", type=float,
                   dest="quarantine_rate", metavar="X",
                   help="max quarantined/parsed ratio (default: "
                        "$SPLATT_INGEST_QUARANTINE_RATE)")
    p.add_argument("--json", action="store_true",
                   help="also print the machine-readable run summary")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.set_defaults(fn=cmd_ingest)

    p = sub.add_parser(
        "serve", help="run the multi-tenant decomposition daemon",
        epilog="A journal-backed job queue over DIR: clients drop job "
               "specs into DIR/requests/ (or --submit them), the "
               "daemon runs each CPD under the guarded drivers with "
               "per-job isolation of demotions/health verdicts, "
               "results appear in DIR/results/<id>.json with the "
               "--json run-report schema.  Crash-resumable: a killed "
               "daemon replays its journal on restart and resumes "
               "every accepted job from its checkpoint; SIGTERM "
               "drains gracefully (docs/serve.md).")
    p.add_argument("dir", help="serve state directory (journal, "
                               "requests/, results/, ckpt/)")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.add_argument("--workers", type=_positive_int,
                   help="concurrent job-supervisor threads "
                        "(default: $SPLATT_SERVE_WORKERS)")
    p.add_argument("--queue-max", type=int, dest="queue_max",
                   help="bounded pending-queue depth; submissions past "
                        "it are load-shed with an explicit queue_full "
                        "rejection (default: $SPLATT_SERVE_QUEUE_MAX; "
                        "<= 0 unbounded)")
    p.add_argument("--poll", type=float,
                   help="seconds between request-spool scans "
                        "(default: $SPLATT_SERVE_POLL_S)")
    p.add_argument("--job-deadline", type=float, dest="job_deadline",
                   help="default per-job deadline in seconds; a blown "
                        "deadline classifies TIMEOUT and the job is "
                        "marked failed, releasing its worker (default: "
                        "$SPLATT_SERVE_JOB_DEADLINE_S; <= 0 off)")
    p.add_argument("--once", action="store_true",
                   help="process the spool and queue to completion, "
                        "then exit (batch/CI mode; nonzero exit iff "
                        "a job failed outright)")
    p.add_argument("--fleet", action="store_true",
                   help="fleet mode (docs/fleet.md): run as one of N "
                        "replicas over this shared DIR — job "
                        "ownership via leases, heartbeat membership, "
                        "dead-peer adoption, cache-affinity routing")
    p.add_argument("--replica", metavar="ID",
                   help="fleet: this replica's stable id (default: "
                        "$SPLATT_FLEET_REPLICA, else a fresh "
                        "pid+random id)")
    p.add_argument("--lease", type=float, metavar="S",
                   help="fleet: lease duration in seconds — the "
                        "failure-detection horizon (default: "
                        "$SPLATT_FLEET_LEASE_S)")
    p.add_argument("--heartbeat", type=float, metavar="S",
                   help="fleet: heartbeat/renewal cadence (default: "
                        "$SPLATT_FLEET_HEARTBEAT_S, else lease/3)")
    p.add_argument("--tenant-quota", type=int, dest="tenant_quota",
                   help="admission control: max non-terminal jobs per "
                        "tenant, shed past it with a quota_rejected "
                        "event (default: $SPLATT_FLEET_TENANT_QUOTA; "
                        "<= 0 off)")
    p.add_argument("--batch-min", type=int, dest="batch_min",
                   help="auto-coalescing (docs/batched.md): dispatch "
                        ">= this many queued same-regime jobs as ONE "
                        "vmapped batched CPD (default: "
                        "$SPLATT_SERVE_BATCH_MIN; <= 0 off)")
    p.add_argument("--submit", metavar="SPEC_JSON",
                   help="client mode: file this job-spec JSON into "
                        "DIR/requests/ and exit")
    p.add_argument("--status", metavar="JOB_ID",
                   help="client mode: print the job's journal-derived "
                        "state (and result, when terminal) as JSON")
    p.add_argument("--json", action="store_true",
                   help="print the full per-job state map on exit")
    _trace_opt(p)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "tune", help="pre-tune the MTTKRP plan for a tensor",
        epilog="Times candidate plans (engine x nnz_block x "
               "scan_target) per mode with short warm+timed runs and "
               "persists the winners in the plan cache; later cpd runs "
               "in the same shape regime dispatch straight to the "
               "measured winner (docs/autotune.md)")
    _common_opts(p)
    p.add_argument("-r", "--rank", type=int, default=10)
    p.add_argument("--reps", type=int, default=2,
                   help="timed repetitions per candidate (median wins)")
    p.add_argument("--force", action="store_true",
                   help="re-measure even when the plan cache already "
                        "holds an unexpired winner")
    p.add_argument("--alloc", choices=["onemode", "twomode", "allmode"])
    p.add_argument("--f64", action="store_true")
    _trace_opt(p)
    p.set_defaults(fn=cmd_tune)

    p = sub.add_parser(
        "bench", help="benchmark MTTKRP algorithms",
        epilog="Per-path effective-bandwidth (roofline) lines are "
               "printed with the timings.  For a device-count scaling "
               "sweep (≙ the reference's thread scaling) run the "
               "repo-root bench driver: SPLATT_BENCH_DEVICES=1,2,4,8 "
               "python bench.py")
    _common_opts(p)
    p.add_argument("-r", "--rank", type=int, default=16)
    p.add_argument("-a", "--alg", action="append",
                   help="algorithm (repeatable): stream/blocked/"
                        "blocked_pallas/scatter/ttbox")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--seed", type=int)
    p.add_argument("--alloc", choices=["onemode", "twomode", "allmode"])
    p.add_argument("--mode-order", dest="mode_order",
                   choices=["smallfirst", "bigfirst", "inorder_minusone",
                            "sorted_minusone"],
                   help="secondary mode ordering within a layout "
                        "(reference csf_find_mode_order policies)")
    p.add_argument("--block", type=int)
    p.add_argument("--f64", action="store_true")
    p.add_argument("--permute", choices=list(PERM_TYPES),
                   help="reorder the tensor first")
    p.add_argument("--check", action="store_true",
                   help="cross-validate algorithm outputs against stream "
                        "(≙ the reference's --write dumps)")
    _trace_opt(p)
    p.set_defaults(fn=cmd_bench)

    p = sub.add_parser(
        "trace", help="summarize (and merge) recorded span traces",
        epilog="Reads Chrome trace-event JSON files (the --trace "
               "<path> export of cpd/tune/bench/serve/chaos), flight-"
               "recorder .jsonl rings (a SIGKILLed replica's black "
               "box), or a directory of both; prints top spans by "
               "self-time, the per-iteration breakdown, the guard-"
               "overhead share, point-event counts, and the fleet "
               "block (per-replica jobs, adoption lineage).  Multiple "
               "inputs merge onto one wall-clock timeline with flow "
               "events linking an adopted job's victim and adopter "
               "(docs/observability.md).  Load the (merged) file in "
               "ui.perfetto.dev for the interactive view.")
    p.add_argument("file", nargs="+",
                   help="trace file(s): Chrome JSON, flight .jsonl, "
                        "or a directory holding them")
    p.add_argument("--out", metavar="OUT_JSON",
                   help="also write the merged Chrome trace-event "
                        "file (atomic) for perfetto")
    p.add_argument("--top", type=int, default=12, metavar="N",
                   help="rows in the top-spans table (default 12)")
    p.add_argument("--json", action="store_true",
                   help="print the aggregate summary as JSON instead")
    p.set_defaults(fn=cmd_trace)

    for verb, watching in (("status", False), ("top", True)):
        p = sub.add_parser(
            verb,
            help=("watch-mode textual fleet dashboard" if watching
                  else "one-shot fleet status from the shared spool"),
            epilog="Reads ONLY the shared serve spool (journal, "
                   "fleet/ heartbeats + leases, per-replica metrics "
                   "snapshots, persisted SLO verdicts) — no daemon "
                   "RPC, so it works on a live fleet, a draining one "
                   "and a post-mortem alike (docs/fleet.md, "
                   "docs/observability.md).  Shows replicas with "
                   "lease freshness, queue depths, per-tenant usage, "
                   "running jobs with age, recent terminal jobs and "
                   "the SLO burn summary.")
        p.add_argument("dir", help="the serve spool directory")
        p.add_argument("--json", action="store_true",
                       help="print the machine-readable status object")
        p.add_argument("--metrics-out", dest="metrics_out",
                       metavar="PROM",
                       help="also write the merged fleet Prometheus "
                            "exposition (counters summed, gauges "
                            "per-replica, histograms bucket-merged, "
                            "dead replicas' gauges dropped) to this "
                            "file, atomically")
        if watching:
            p.add_argument("--once", dest="watch",
                           action="store_false",
                           help="one-shot instead of watching")
        else:
            p.add_argument("--watch", action="store_true",
                           help="refresh continuously (the `splatt "
                                "top` default)")
        p.add_argument("--interval", type=float, metavar="S",
                       help="watch refresh seconds (default: "
                            "$SPLATT_STATUS_WATCH_S)")
        p.set_defaults(fn=cmd_status, watch=watching)

    p = sub.add_parser(
        "predict",
        help="query a served model: reconstruct entries / top-k",
        epilog="Files a generation-fenced predict job into DIR's serve "
               "spool (docs/predict.md): a daemon answers from an "
               "intact model generation or refuses classified — never "
               "stale, never torn.  --coords reconstructs entries "
               "x̂ = Σ_r λ_r Π_m U_m[i_m,r]; --top-k scans one mode "
               "with every other mode pinned by --fix.")
    p.add_argument("dir", help="the serve spool directory")
    p.add_argument("--model", required=True,
                   help="the committed model's job id")
    p.add_argument("--id", help="predict job id (default: generated)")
    p.add_argument("--tenant", help="tenant label for quota accounting")
    p.add_argument("--coords", action="append", metavar="I,J,K",
                   help="an index tuple to reconstruct (repeatable)")
    p.add_argument("--top-k", dest="top_k", type=int, metavar="K",
                   help="return the K best indices along --mode")
    p.add_argument("--mode", type=int, default=0,
                   help="the scanned mode for --top-k (default 0)")
    p.add_argument("--fix", action="append", metavar="MODE=INDEX",
                   help="pin a non-scanned mode for --top-k "
                        "(repeatable; every mode but --mode needs one)")
    p.add_argument("--wait", type=float, default=0.0, metavar="S",
                   help="poll up to S seconds for the answer "
                        "(default: file-and-exit, exit 0 on served)")
    p.set_defaults(fn=cmd_predict)

    p = sub.add_parser("check", help="check for duplicates/empty slices")
    _common_opts(p)
    p.add_argument("--fix", metavar="OUT",
                   help="write a fixed tensor to OUT")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("convert", help="convert to other formats")
    _common_opts(p)
    p.add_argument("type", choices=["graph", "fibmat", "fibhgraph",
                                    "nnzhgraph", "bin", "coord"])
    p.add_argument("output")
    p.add_argument("-m", "--mode", type=int, default=0)
    p.set_defaults(fn=cmd_convert)

    p = sub.add_parser("reorder", help="relabel tensor indices")
    _common_opts(p)
    p.add_argument("type", choices=list(PERM_TYPES))
    p.add_argument("output")
    p.add_argument("--seed", type=int)
    p.add_argument("--write-perms", action="store_true")
    p.set_defaults(fn=cmd_reorder)

    p = sub.add_parser("stats", help="print tensor statistics")
    _common_opts(p)
    p.add_argument("-p", "--partition", metavar="FILE",
                   help="also report quality of this nonzero partition")
    p.set_defaults(fn=cmd_stats)

    return ap


def main(argv: Optional[List[str]] = None) -> int:
    # the persistent compile cache: processes sharing it (fleet
    # replicas, restarts) reuse each other's serialized executables
    apply_compile_cache()
    args = build_parser().parse_args(argv)
    if getattr(args, "rank", 1) < 1:
        print(f"splatt-tpu: error: rank must be >= 1 (got {args.rank})",
              file=sys.stderr)
        return 2
    # --trace <path> (docs/observability.md): enable span recording
    # process-wide for this invocation — timers, build, cpd/serve spans
    # all land in one tree — and export on the way out, success or
    # error (a crash's partial trace is exactly when you want one).
    # The chaos verb owns its own trace leg (run_chaos arms, exports
    # and ASSERTS on the trace), so it is excluded here.
    trace_out = (getattr(args, "trace", None)
                 if getattr(args, "cmd", "") != "chaos" else None)
    if trace_out:
        from splatt_tpu import trace

        trace.set_enabled(True)
    try:
        return args.fn(args)
    except (OSError, ValueError) as e:
        print(f"splatt-tpu: error: {e}", file=sys.stderr)
        return 1
    finally:
        if trace_out:
            ev = trace.write_chrome_trace(trace_out)
            trace.set_enabled(None)
            print(f"splatt-tpu: trace "
                  + (f"written to {trace_out} ({ev.get('spans')} spans, "
                     f"{ev.get('events')} point events); summarize "
                     f"with: splatt trace {trace_out}"
                     if ev.get("ok") else
                     f"export to {trace_out} FAILED "
                     f"({ev.get('failure_class')}: {ev.get('error')})"),
                  file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
