"""Seconds JAX spent tracing, lowering and compiling (or loading from
the compile cache) inside the timed ``cpd_als`` call: the program's
``splatt_jit_seconds_total`` over that call, every stage and program,
as the call's ``cpd.als`` span records it (``jit_s``, with tracing on,
as in a ``--trace 1`` run).  Prints the call's programs, each with its
stage runs and seconds, for the log."""

import json


def read(ctx):
    from splatt_tpu import trace

    roots = [s["args"] for s in trace.spans("cpd.als")
             if "jit_s" in s["args"]]
    if not roots:
        return None
    rows = sorted(roots[-1]["jit"].items(), key=lambda kv: -kv[1][1])
    print("bench: window compile work per program [stage runs, seconds]: "
          + json.dumps({p: [int(n), s] for p, (n, s) in rows}), flush=True)
    return roots[-1]["jit_s"]
