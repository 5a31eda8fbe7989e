"""Run a cell once with ``--trace 1`` as ``benchmark/run.py`` does, keep
its profiler trace, and print the MTTKRP's device time per mode and
phase (``benchmark/opscope.py``) next to the run's result line.  Run on
the chip:

    python3 benchmark/tests/phase_table.py WORKLOAD SEED SECONDS [OUT]

(OUT: where to copy the ``.xplane.pb``, when it should be kept.)

Prints the result object, then one ``phases`` line: milliseconds per
traced iteration for each mode's ``operands`` / ``reduce`` / ``merge``,
the MTTKRP program's ops under no phase scope, the three phases' sum
and the program's leaf-op time beside ``mttkrp_ms_per_iter``.
"""

import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import opscope  # noqa: E402
import run  # noqa: E402


def main(workload: str, seed: int, seconds: float, out=None) -> int:
    spec = run.load_cell(workload)
    run.use_compile_cache()
    keep = tempfile.mkdtemp(prefix="phase_table_")
    run.tempfile.mkdtemp = lambda prefix="": keep
    run.shutil.rmtree = lambda *a, **k: None
    result = run.run_cell(spec, seed, seconds, True)
    print(json.dumps(result), flush=True)
    trace = max(glob.glob(os.path.join(keep, "**", "*.xplane.pb"),
                          recursive=True))
    ph = opscope.load(trace)
    per = 1e3 / ph.iterations
    rows = {name: {k: round(v, 3) for k, v in cols.items()}
            for name, cols in ph.table()}
    total = per * sum(ph.phase_s(p) for p in opscope.PHASES)
    print("phases " + json.dumps({
        "iterations": ph.iterations, "ms_per_iter": rows,
        "phases_sum_ms": round(total, 3),
        "mttkrp_leaf_ms": round(per * ph.program_s(r"mttkrp"), 3),
        "mttkrp_ms_per_iter": result["metrics"].get(
            "mttkrp_ms_per_iter", {}).get("value"),
        "containers_left_out": ph.containers}), flush=True)
    if out:
        shutil.copyfile(trace, out)
    shutil.rmtree(keep, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                  *sys.argv[4:5]))
