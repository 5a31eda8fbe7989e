"""Distributed CPD across all available devices.

Run on any device count (simulate a mesh on CPU with:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/distributed.py
).  Exercises all three decompositions; each reproduces the
single-device factors for the same seed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import splatt_tpu
from splatt_tpu.config import CommPattern, Decomposition, Options, Verbosity
from splatt_tpu.parallel import distributed_cpd_als


def main() -> None:
    tt = splatt_tpu.SparseTensor.random((300, 240, 180), 50_000, seed=3)
    print(f"devices: {len(jax.devices())}  tensor: {tt.dims}, {tt.nnz} nnz")

    for decomp in Decomposition:
        opts = Options(random_seed=7, max_iterations=10,
                       verbosity=Verbosity.NONE, decomposition=decomp)
        out = distributed_cpd_als(tt, rank=8, opts=opts)
        print(f"{decomp.value:8s} fit = {float(out.fit):.5f}")

    # the memory-lean ppermute-ring variant (for modes whose factors
    # don't fit on one device)
    opts = Options(random_seed=7, max_iterations=10,
                   verbosity=Verbosity.NONE,
                   decomposition=Decomposition.FINE,
                   comm_pattern=CommPattern.POINT2POINT)
    out = distributed_cpd_als(tt, rank=8, opts=opts)
    print(f"ring     fit = {float(out.fit):.5f}")


if __name__ == "__main__":
    main()
