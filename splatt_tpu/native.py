"""ctypes bindings to the native host runtime (native/splatt_native.cpp).

The reference implements its host-side hot paths (text parsing
src/io.c:62-108, sorting src/sort.c) in C; this module provides the
same for splatt-tpu: a buffered single-pass `.tns` parser and a
bucket+std::sort permutation used by the blocked-layout compiler.

The shared library is built on first use (g++ is assumed present, as on
the target image); every entry point degrades gracefully — callers fall
back to the numpy implementations when the library is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path
from typing import Optional, Sequence, Tuple

import numpy as np

_SRC_PATH = Path(__file__).resolve().parent.parent / "native" / "splatt_native.cpp"

_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _host_key() -> bytes:
    """What a ``-march=native`` build depends on besides its source: the
    architecture and the CPU's feature flags."""
    import platform

    flags = b""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    flags = line
                    break
    except OSError:
        pass
    return platform.machine().encode() + b"|" + flags


def _so_path() -> Path:
    """The library built from THIS source on THIS kind of host: the name
    carries a hash of both, so a build copied from another machine (the
    chip tool copies the tree as it stands) is never loaded here."""
    import hashlib

    h = hashlib.sha256(_SRC_PATH.read_bytes() + b"|" + _host_key())
    return Path(__file__).resolve().parent / f"_native-{h.hexdigest()[:16]}.so"


def _build(so_path: Path) -> bool:
    # built beside, then renamed into place: concurrent processes never
    # load a half-written library
    tmp = so_path.with_name(f"{so_path.name}.{os.getpid()}.tmp")
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            "-o", str(tmp), str(_SRC_PATH)]
    # -march=native vectorizes the MTTKRP rank loops; retry without it
    # for toolchains that reject the flag
    for flags in (base[:2] + ["-march=native"] + base[2:], base):
        try:
            subprocess.run(flags, check=True, capture_output=True,
                           timeout=300)
            os.replace(tmp, so_path)
            return True
        except (OSError, subprocess.SubprocessError):
            continue
    return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    if not _SRC_PATH.exists():
        _load_failed = True
        return None
    so_path = _so_path()
    if not so_path.exists() and not _build(so_path):
        _load_failed = True
        return None
    try:
        lib = ctypes.CDLL(str(so_path))
    except OSError:
        _load_failed = True
        return None
    lib.tns_open.restype = ctypes.c_void_p
    lib.tns_open.argtypes = [ctypes.c_char_p]
    lib.tns_rows.restype = ctypes.c_int64
    lib.tns_rows.argtypes = [ctypes.c_void_p]
    lib.tns_cols.restype = ctypes.c_int
    lib.tns_cols.argtypes = [ctypes.c_void_p]
    lib.tns_fill.restype = ctypes.c_int
    lib.tns_fill.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                             ctypes.c_void_p]
    lib.tns_close.argtypes = [ctypes.c_void_p]
    lib.sort_perm.restype = ctypes.c_int
    lib.sort_perm.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                              ctypes.c_void_p, ctypes.c_void_p,
                              ctypes.c_void_p]
    lib.tns_stream_to_bin.restype = ctypes.c_int
    lib.tns_stream_to_bin.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    for name in ("mttkrp_f32", "mttkrp_f64"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                       ctypes.c_int64, ctypes.c_int, ctypes.c_int,
                       ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
                       ctypes.c_int, ctypes.c_void_p, ctypes.c_int]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def parse_tns(path: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Parse a coordinate text file; None → caller should fall back."""
    lib = _load()
    if lib is None:
        return None
    h = lib.tns_open(os.fsencode(path))
    if not h:
        return None
    try:
        nrows = lib.tns_rows(h)
        ncols = lib.tns_cols(h)
        nmodes = ncols - 1
        inds = np.empty((nmodes, nrows), dtype=np.int64)
        vals = np.empty(nrows, dtype=np.float64)  # splint: ignore[SPL005] C++ ABI: the shared library exports an f64 ingest buffer
        rc = lib.tns_fill(h, inds.ctypes.data_as(ctypes.c_void_p),
                          vals.ctypes.data_as(ctypes.c_void_p))
        if rc != 0:
            raise ValueError(f"{path}: malformed tensor file "
                             f"(native parser rc={rc})")
        return inds, vals
    finally:
        lib.tns_close(h)


def stream_to_bin(src: str, dst: str) -> bool:
    """Two-pass streaming text→binary conversion with ~8MB memory
    (for tensors larger than RAM).  False → caller should fall back to
    the in-memory path; raises on malformed input.
    """
    lib = _load()
    if lib is None:
        return False
    rc = lib.tns_stream_to_bin(os.fsencode(src), os.fsencode(dst))
    if rc != 0:
        # never leave a partial binary with a valid header behind
        try:
            os.unlink(dst)
        except OSError:
            pass
        if rc in (1, 5):
            raise OSError(f"cannot open {src if rc == 1 else dst}")
        if rc in (6, 7):
            raise OSError(
                f"{dst}: write failed during conversion (disk full or "
                f"I/O error, rc={rc})")
        raise ValueError(f"{src}: malformed tensor file "
                         f"(stream converter rc={rc})")
    return True


def sort_perm(inds: np.ndarray, dims: Sequence[int],
              mode_order: Sequence[int]) -> Optional[np.ndarray]:
    """Lexicographic nnz permutation by mode_order; None → fall back."""
    lib = _load()
    if lib is None:
        return None
    inds = np.ascontiguousarray(inds, dtype=np.int64)
    nmodes, nnz = inds.shape
    order = list(mode_order)
    # the C comparator walks mode_order[1..nmodes); a partial order has
    # different semantics (remaining modes unordered) — numpy handles it
    if len(order) != nmodes or sorted(order) != list(range(nmodes)):
        return None
    dims_arr = np.asarray(dims, dtype=np.int64)
    order_arr = np.asarray(order, dtype=np.int32)
    perm = np.empty(nnz, dtype=np.int64)
    rc = lib.sort_perm(inds.ctypes.data_as(ctypes.c_void_p),
                       ctypes.c_int64(nnz), ctypes.c_int(nmodes),
                       dims_arr.ctypes.data_as(ctypes.c_void_p),
                       order_arr.ctypes.data_as(ctypes.c_void_p),
                       perm.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        return None
    return perm


def mttkrp(inds: np.ndarray, vals: np.ndarray, factors, mode: int,
           dims: Sequence[int], sorted_by_mode: bool,
           nnz: int) -> Optional[np.ndarray]:
    """Native single-core MTTKRP over a blocked layout's arrays
    (≙ the reference's register-blocked fiber loops, src/mttkrp.c:427-463
    — re-designed as a flat pass with run accumulation).

    inds: (nmodes, nnz_pad) int32; vals: (nnz_pad,) f32/f64; factors:
    per-mode (dims[k], rank) arrays matching vals' dtype.  `nnz` is the
    true nonzero count and is REQUIRED: padding entries trail the sort
    and carry a sentinel index equal to `dim` on the sort-mode row —
    out of range for the factor gather — so a loop bound that includes
    them is undefined behavior (the round-2 nondeterminism bug).  Pass
    nnz == inds.shape[1] only for genuinely unpadded arrays.
    None → caller should fall back to the XLA engines.
    """
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals)
    dtype = vals.dtype
    if dtype == np.float32:  # splint: ignore[SPL005] C++ ABI gate: the library exports exactly f32/f64 kernels
        fn = lib.mttkrp_f32
    elif dtype == np.float64:  # splint: ignore[SPL005] C++ ABI gate: the library exports exactly f32/f64 kernels
        fn = lib.mttkrp_f64
    else:
        return None
    if any(np.asarray(f).dtype != dtype for f in factors):
        return None  # mixed dtypes: let the XLA paths apply promotion
    inds = np.ascontiguousarray(inds, dtype=np.int32)
    nmodes, nnz_pad = inds.shape
    if nmodes > 8:
        return None
    facs = [np.ascontiguousarray(f, dtype=dtype) for f in factors]
    rank = facs[0].shape[1]
    fac_ptrs = (ctypes.c_void_p * nmodes)(
        *[f.ctypes.data_as(ctypes.c_void_p).value for f in facs])
    dims_arr = np.asarray(dims, dtype=np.int64)
    out = np.zeros((dims[mode], rank), dtype=dtype)
    fn(inds.ctypes.data_as(ctypes.c_void_p),
       vals.ctypes.data_as(ctypes.c_void_p),
       ctypes.c_int64(min(nnz, nnz_pad)), ctypes.c_int64(nnz_pad),
       ctypes.c_int(nmodes), ctypes.c_int(mode),
       fac_ptrs, dims_arr.ctypes.data_as(ctypes.c_void_p),
       ctypes.c_int(rank), out.ctypes.data_as(ctypes.c_void_p),
       ctypes.c_int(1 if sorted_by_mode else 0))
    return out
