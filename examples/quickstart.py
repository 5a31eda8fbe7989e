"""Quickstart: factor a sparse tensor and inspect the result.

Run:  python examples/quickstart.py [tensor.tns]
(with no argument, a small synthetic tensor is generated)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import splatt_tpu
from splatt_tpu.config import Options, Verbosity


def main() -> None:
    if len(sys.argv) > 1:
        tt = splatt_tpu.load(sys.argv[1])
    else:
        tt = splatt_tpu.SparseTensor.random((200, 150, 120), 20_000, seed=0)
    print(f"tensor: dims={tt.dims} nnz={tt.nnz}")

    # compile into the blocked device format and factor
    opts = Options(random_seed=42, max_iterations=25,
                   verbosity=Verbosity.LOW)
    bs = splatt_tpu.BlockedSparse.from_coo(tt, opts)
    out = splatt_tpu.cpd_als(bs, rank=16, opts=opts)

    print(f"fit = {float(out.fit):.4f}")
    print(f"lambda = {np.asarray(out.lam)[:5].round(3)} ...")
    # factors are (dim, rank) jax arrays with unit-norm columns
    for m, U in enumerate(out.factors):
        print(f"  factor {m}: {U.shape}")

    # persist like the reference CLI (modeN.mat + lambda.mat)
    out.save("quickstart_output")
    print("factors written to quickstart_output/")


if __name__ == "__main__":
    main()
