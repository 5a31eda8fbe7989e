"""Per-phase profile of the flagship CPU bench config (VERDICT r5 item
5): where do the ~1.4 s/it go — MTTKRP (native engine), solve/normalize/
gram, or fit?  Uses the single-device profiled path (split-jit phases +
warm-then-reset timers, ≙ splatt cpd -v -v per-mode timer output,
src/cpd.c:357-367) on the same synthetic NELL-2-shaped tensor as
bench.py.

Usage: python tools/cpu_profile.py [nnz] [rank] [iters]
Writes tools/cpu_profile.json.
"""
import json
import os
import sys
import time

# a CPU profile: pinned before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    nnz = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000_000
    rank = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 3

    from bench import synthetic_nell2_like
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.cpd import cpd_als
    from splatt_tpu.utils.timers import timers

    tt = synthetic_nell2_like(nnz)
    opts = Options(random_seed=7, verbosity=Verbosity.HIGH,
                   val_dtype=np.float32, max_iterations=iters,
                   tolerance=0.0)
    X = BlockedSparse.from_coo(tt, opts)
    t0 = time.perf_counter()
    cpd_als(X, rank, opts=opts)
    wall = time.perf_counter() - t0

    rec = dict(nnz=nnz, rank=rank, iters=iters,
               wall_sec=round(wall, 2),
               phase_sec_per_iter={}, phase_total_sec={})
    for name, t in sorted(timers._timers.items()):
        if t.seconds > 0:
            rec["phase_total_sec"][name] = round(t.seconds, 4)
            rec["phase_sec_per_iter"][name] = round(t.seconds / iters, 4)
    print(timers.report(level=3))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "cpu_profile.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
