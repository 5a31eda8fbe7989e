"""What splatt-tpu knows about each TPU generation: one table, keyed by
``jax.Device.device_kind``.

The roofline (bench_algs) reads the HBM and MXU peaks; the Pallas
kernels read the most scoped VMEM a kernel may ask Mosaic for.  On a
TPU whose kind is not in the table, :func:`device_spec` raises: a
guessed peak or VMEM budget would make every roofline share and kernel
gate a fiction.
Add a row, with its source, to support another generation.
"""

from __future__ import annotations

from typing import NamedTuple, Optional


class DeviceSpec(NamedTuple):
    hbm_gbs: float        # peak HBM bandwidth, GB/s
    mxu_gflops: float     # peak MXU compute (bf16), GFLOP/s
    hbm_bytes: int        # HBM capacity per chip
    vmem_limit: int       # most scoped VMEM a kernel may request from Mosaic
    vmem_bytes: int       # VMEM per core


#: Sources: Google Cloud documentation, "TPU v5e" (819 GB/s HBM,
#: 197 TFLOP/s bf16, 16 GB HBM per chip).  VMEM is 128 MiB per core.
#: A kernel may ask for up to 100 MiB of it (Mosaic's default scoped
#: limit is ~16 MiB); the one-hot reduce kernels ask for what their
#: blocks need (pallas_kernels.onehot_vmem_request).  What a kernel
#: leaves unreserved is not Mosaic scratch: it goes to XLA, which keeps
#: the program's live buffers there, such as the factor tables an
#: MTTKRP scan gathers from.
DEVICE_SPECS = {
    "TPU v5 lite": DeviceSpec(hbm_gbs=819.0, mxu_gflops=197000.0,
                              hbm_bytes=16 * 10**9,
                              vmem_limit=100 << 20,
                              vmem_bytes=128 << 20),
}

#: the generation the kernels are written for: interpret mode (CPU
#: tests) sizes its VMEM gates as this chip would
KERNEL_TARGET = "TPU v5 lite"


def device_spec(device=None) -> Optional[DeviceSpec]:
    """The spec of `device` (default: JAX's first device); None off-TPU.
    Raises on a TPU whose kind is not in :data:`DEVICE_SPECS`."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    if dev.platform != "tpu":
        return None
    spec = DEVICE_SPECS.get(dev.device_kind)
    if spec is None:
        raise RuntimeError(
            f"unknown TPU kind {dev.device_kind!r}: splatt_tpu/devices.py "
            f"has peaks and VMEM only for {sorted(DEVICE_SPECS)}")
    return spec
