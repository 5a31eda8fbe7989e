"""Share of the factor rows the MTTKRP's scans gather from tables the
compiled program holds in HBM rather than VMEM, in percent, read from
the device trace of a ``--trace 1`` run.

Each mode's MTTKRP is a scan loop, one ``while`` op on the device per
call.  Its op name in the trace (``Summary.device_ops``, the window's
heaviest ops) is the compiled HLO, with the loop state's shapes and
layouts: the per-step layout chunks (3-D ``s32``) and the factor tables
the mode gathers (2-D floats), each in VMEM where its layout names
memory space ``S(1)``.  Every mode gathers each table in its state once
per nonzero, so the tables without ``S(1)``, over all the loops read,
are that share of the gathered rows.  A loop not among the heaviest
ops is not read; the log line gives how many were.

Prints, for the log, each loop's tables and where they sit, and beside
them the program's own reckoning from shapes (each mode's last
``mttkrp.dispatch`` span: ``step_blocks``, ``tables_vmem``,
``tables_hbm``, ``kernel_vmem``), where its spans carry it.  A trace
with no such loop gives nothing."""

import json
import re

#: the result tuple of a ``while`` op: ``%while... = (...) while(``
_STATE = re.compile(r"^%while[.\d]* = \((.*?)\) while\(")
_ARRAY = re.compile(r"\b(\w+)\[([\d,]*)\]\{([^}]*)\}")
_FLOATS = ("f32", "bf16", "f16")


def scan_tables(name):
    """``[(shape, in_vmem), ...]`` of the factor tables a scan loop's
    op `name` carries, or None when `name` is no MTTKRP scan loop."""
    m = _STATE.match(name)
    if not m:
        return None
    arrays = [(dt, tuple(int(d) for d in dims.split(",") if d), lay)
              for dt, dims, lay in _ARRAY.findall(m.group(1))]
    if not any(dt == "s32" and len(shape) == 3 for dt, shape, _ in arrays):
        return None
    tables = [(shape, lay.endswith("S(1)")) for dt, shape, lay in arrays
              if dt in _FLOATS and len(shape) == 2]
    return tables or None


def _reckoned():
    from splatt_tpu import trace

    last = {}
    for s in trace.spans("mttkrp.dispatch"):
        if "tables_hbm" in s["args"]:
            last[int(s["args"]["mode"])] = s["args"]
    return {m: {k: a.get(k) for k in ("engine", "step_blocks", "tables_vmem",
                                      "tables_hbm", "kernel_vmem")}
            for m, a in sorted(last.items())}


def read(ctx):
    ops = ctx.summary.device_ops if ctx.summary else []
    loops = [t for t in (scan_tables(n) for n, _ in ops) if t]
    if not loops:
        return None
    print("bench: window MTTKRP scan loops' tables [rows, cols, in VMEM] "
          + json.dumps([[[*shape, vmem] for shape, vmem in t]
                        for t in loops]), flush=True)
    reckoned = _reckoned()
    if reckoned:
        print("bench: window MTTKRP placement per mode as the program "
              "reckons it " + json.dumps(reckoned), flush=True)
    tables = [vmem for t in loops for _, vmem in t]
    return 100.0 * tables.count(False) / len(tables)
