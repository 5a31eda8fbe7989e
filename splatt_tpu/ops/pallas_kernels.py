"""Pallas TPU kernels for the MTTKRP hot path.

The performance-critical reduction in blocked MTTKRP is

    out[b, s, :] = Σ_j  [local[b, j] == s] · val[b, j] · ⊛_k rows_k[b, j, :]

i.e. a per-block one-hot contraction (S×B)@(B×R) — the TPU replacement
for the reference's scattered accumulation with its mutex pool /
privatization / tile scheduling (src/mttkrp.c:104-236).  XLA executes
the same einsum but materializes the one-hot operand (nb·S·B elements)
in HBM; the Pallas kernels build it on the fly in VMEM with a
broadcasted iota-compare, fold the values into it, form the Hadamard
product of the gathered factor rows on the VPU and feed both to the
MXU, so HBM traffic is just the ids, values and gathered rows in +
partials out: the (nb, B, R) products never exist in HBM.

Two variants:
- :func:`onehot_reduce_sorted`  — per-block partials (sorted layouts,
  combined by a small scatter outside);
- :func:`onehot_reduce_full`    — full-width accumulation across the
  whole grid (privatized short modes, no scatter at all).

Both take `interpret=` so the differential tests run on CPU
(≙ tests running the real kernels at 7 threads, tests/mttkrp_test.c).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from splatt_tpu.ops.mttkrp import (MERGE, OPERANDS, REDUCE, _acc_dtype,
                                   mxu_precision)
from splatt_tpu.utils.env import ceil_to

# Blocks per grid step of a direct kernel call; the MTTKRP scan passes
# vmem_chunk()'s one block instead.
_CHUNK = 8

@functools.cache
def _spec():
    """The kernels' row of splatt_tpu/devices.py: the device's own on a
    TPU, the kernels' target chip in interpret mode."""
    from splatt_tpu.devices import DEVICE_SPECS, KERNEL_TARGET, device_spec

    return device_spec() or DEVICE_SPECS[KERNEL_TARGET]


def _vmem_limit() -> int:
    """Most scoped VMEM a kernel may request."""
    return _spec().vmem_limit


def vmem_bytes() -> int:
    """VMEM per core: what XLA shares among a program's live buffers
    and the scoped request of the kernel it runs."""
    return _spec().vmem_bytes


def _vmem_budget() -> int:
    return (_vmem_limit() * 24) // 25


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit())


def vmem_chunk(width: int, block: int, rank: int, nrows: int,
               dtype) -> int:
    """Blocks per grid step of the MTTKRP scan's one-hot reduce kernels
    reading `nrows` gathered row arrays: 1 where one block's
    :func:`onehot_vmem_request` fits the kernel budget, else 0, and
    callers fall back to the XLA engine, which streams the one-hot
    through HBM instead.

    One block is the least a kernel can reserve, and what it does not
    reserve goes to XLA, which keeps the scan's live buffers in VMEM
    when they fit: the gathered factor tables and the step's gathered
    row arrays, each of which would otherwise make an HBM round trip.
    A larger chunk would save only per-grid-step overhead.
    """
    need = _onehot_vmem_bytes(1, width, block, rank, nrows, dtype)
    return 1 if need <= _vmem_budget() else 0


def padded_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array of `shape`: its last two dims padded to
    the dtype's (sublane, 128-lane) tile — 8 sublanes at 32 bits, 16 at
    16 bits."""
    *lead, rows, lanes = shape
    n = (ceil_to(rows, 8 * max(1, 4 // itemsize)) * ceil_to(lanes, 128)
         * itemsize)
    for d in lead:
        n *= d
    return n


#: room above :func:`onehot_vmem_request`'s counted buffers
_ONEHOT_VMEM_MARGIN = 4 << 20

#: whole (chunk, width, block) f32 copies of the weighted one-hot that
#: Mosaic's dot keeps when the rank spans more than one 128-lane tile;
#: they cover the kernel's other temporaries too.  At rank 200 a 512- or
#: 1024-wide full kernel needs 48.4 / 80.6 MiB of scoped VMEM, 3.9
#: copies above its blocks (AOT compile for a v5e).  Within one lane
#: tile the dot streams the one-hot and keeps no copy: a 1717-wide full
#: kernel at rank 50 or 128 needs 11.4 / 12.4 MiB.
_DOT_ONEHOT_COPIES = 4


def _onehot_vmem_bytes(chunk: int, width: int, block: int, rank: int,
                       nrows: int, dtype) -> int:
    """:func:`onehot_vmem_request` before its cap at the device's
    ``vmem_limit``."""
    item = jnp.dtype(dtype).itemsize
    acc = jnp.dtype(_acc_dtype(dtype)).itemsize
    ids = padded_bytes((chunk, 1, block), 4)
    vals = padded_bytes((chunk, 1, block), item)
    rows = padded_bytes((chunk, block, rank), item)
    out = padded_bytes((chunk, width, rank), acc)
    copies = _DOT_ONEHOT_COPIES if rank > 128 else 0
    blocks = 2 * (ids + vals + nrows * rows + out)
    temps = max(rows + out,
                copies * padded_bytes((chunk, width, block), 4))
    return blocks + temps + _ONEHOT_VMEM_MARGIN


def onehot_vmem_request(chunk: int, width: int, block: int, rank: int,
                        nrows: int, dtype) -> int:
    """Scoped VMEM the one-hot reduce kernels ask Mosaic for at a grid
    step of `chunk` blocks and `nrows` gathered row arrays: the
    double-buffered input blocks (local ids (chunk, 1, block) i32,
    values (chunk, 1, block), each row array (chunk, block, rank)), the
    double-buffered output block (chunk, width, rank) at the accumulator
    dtype (an upper bound for onehot_reduce_full's (width, rank)), and
    the larger of the rows' product (chunk, block, rank) with the dot
    result and, where the rank passes one 128-lane tile,
    :data:`_DOT_ONEHOT_COPIES` of the one-hot, each tile-padded, plus
    :data:`_ONEHOT_VMEM_MARGIN`; at most the device's ``vmem_limit``.
    The values are counted at the rows' dtype: a one-row block pads to
    the same bytes at 16 and 32 bits.
    What a kernel does not reserve stays with XLA, which keeps the
    scan's live buffers (the gathered factor tables) in VMEM when they
    fit."""
    return min(_onehot_vmem_bytes(chunk, width, block, rank, nrows, dtype),
               _vmem_limit())


def _onehot_compiler_params(chunk: int, width: int, block: int, rank: int,
                            nrows: int, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=onehot_vmem_request(
        chunk, width, block, rank, nrows, dtype))


def _partials(local_ref, vals_ref, row_refs, width: int, out_dtype):
    """(C, width, R) block partials ``W @ P`` of one grid step: ``P``
    the Hadamard product of the gathered rows, formed on the VPU, and
    ``W`` the one-hot of the local ids with each hit carrying its
    nonzero's value — the values share the ids' lane layout, so they
    fold into the one-hot with no transpose."""
    prod = row_refs[0][...]                     # (C, B, R)
    for ref in row_refs[1:]:
        prod = prod * ref[...]
    local = local_ref[:, 0, :]                  # (C, B) int32
    vals = vals_ref[:, 0, :].astype(prod.dtype)
    C, B = local.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (C, width, B), 1)
    weights = jnp.where(local[:, None, :] == iota, vals[:, None, :],
                        jnp.zeros((), prod.dtype))
    return jax.lax.dot_general(
        weights, prod,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=out_dtype,
        precision=mxu_precision(prod.dtype))


def _sorted_kernel(local_ref, vals_ref, *refs, seg_width: int):
    *row_refs, out_ref = refs
    out_ref[...] = _partials(local_ref, vals_ref, row_refs, seg_width,
                             out_ref.dtype)


def _full_kernel(local_ref, vals_ref, *refs, width: int):
    *row_refs, out_ref = refs
    acc = jnp.sum(_partials(local_ref, vals_ref, row_refs, width,
                            out_ref.dtype), axis=0,
                  dtype=_acc_dtype(out_ref.dtype))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(pl.program_id(0) != 0)
    def _accum():
        out_ref[...] += acc


def _pad_blocks(local: jax.Array, vals: jax.Array, rows, chunk: int):
    """Pad to whole chunks (ids -1, values and rows 0); local and vals
    gain a singleton middle dim so their Mosaic block shape
    (chunk, 1, B) is legal for any chunk (the last two block dims must
    divide (8, 128) or equal the array dims).

    The pad is a fresh HBM copy of every row array; only direct callers
    pay it.  ``_scan_fused`` passes :func:`vmem_chunk`'s one block,
    so nothing is padded here."""
    nb = local.shape[0]
    nb_pad = ceil_to(max(nb, 1), chunk)
    if nb_pad != nb:
        pad = ((0, nb_pad - nb), (0, 0))
        local = jnp.pad(local, pad, constant_values=-1)
        vals = jnp.pad(vals, pad)
        rows = [jnp.pad(r, pad + ((0, 0),)) for r in rows]
    return local[:, None, :], vals[:, None, :], list(rows), nb_pad


@functools.partial(jax.jit,
                   static_argnames=("seg_width", "interpret", "chunk"))
def onehot_reduce_sorted(local: jax.Array, vals: jax.Array, rows,
                         seg_width: int, interpret: bool = False,
                         chunk: int = _CHUNK) -> jax.Array:
    """(nb, B) local ids, (nb, B) values and the gathered factor rows
    (a sequence of (nb, B, R) arrays) → (nb, S, R) block partials
    ``Σ_j [local == s] · vals · ⊛ rows``."""
    nb = local.shape[0]
    B = local.shape[1]
    R = rows[0].shape[-1]
    dtype = rows[0].dtype
    row_specs = [pl.BlockSpec((chunk, B, R), lambda i: (i, 0, 0))
                 for _ in rows]
    local, vals, rows, nb_pad = _pad_blocks(local, vals, rows, chunk)
    grid = (nb_pad // chunk,)
    out = pl.pallas_call(
        functools.partial(_sorted_kernel, seg_width=seg_width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            *row_specs,
        ],
        out_specs=pl.BlockSpec((chunk, seg_width, R), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, seg_width, R),
                                       _acc_dtype(dtype)),
        interpret=interpret,
        name="onehot_reduce_sorted",
        compiler_params=_onehot_compiler_params(chunk, seg_width, B, R,
                                                len(rows), dtype),
    )(local, vals, *rows)
    return out[:nb]


@functools.partial(jax.jit,
                   static_argnames=("width", "interpret", "chunk"))
def onehot_reduce_full(local: jax.Array, vals: jax.Array, rows, width: int,
                       interpret: bool = False,
                       chunk: int = _CHUNK) -> jax.Array:
    """(nb, B) ids, (nb, B) values and the gathered factor rows (a
    sequence of (nb, B, R) arrays) → (width, R) total (privatized)."""
    B = local.shape[1]
    R = rows[0].shape[-1]
    dtype = rows[0].dtype
    row_specs = [pl.BlockSpec((chunk, B, R), lambda i: (i, 0, 0))
                 for _ in rows]
    local, vals, rows, nb_pad = _pad_blocks(local, vals, rows, chunk)
    grid = (nb_pad // chunk,)
    out = pl.pallas_call(
        functools.partial(_full_kernel, width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((chunk, 1, B), lambda i: (i, 0, 0)),
            *row_specs,
        ],
        out_specs=pl.BlockSpec((width, R), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((width, R), _acc_dtype(dtype)),
        interpret=interpret,
        name="onehot_reduce_full",
        compiler_params=_onehot_compiler_params(chunk, width, B, R,
                                                len(rows), dtype),
    )(local, vals, *rows)
    return out


#: outcome of each capability probe, keyed by kernel name — "ok",
#: "compile_failed", "resource", "infra", or absent if never probed.
#: "infra" means the verdict is *unproven* (a transient or unrecognized
#: failure, not a rejected kernel); "resource" means the probe ran out
#: of memory: a capacity verdict scoped to this (regime, block) shape.
#: Every verdict but "ok" is reported on stderr and in the run report.
PROBE_STATES: dict = {}


# -- persistent capability cache --------------------------------------------
#
# A capability probe costs a compile and its verdict depends only on
# (jax version, device kind, kernel sources, regime, block) — none of
# which change between the processes of one environment.  This cache
# stores proven verdicts ("ok"/"compile_failed", and the shape-scoped
# "resource") on disk; "infra" is stored for reporting but NEVER
# short-circuits a later process — an unproven verdict is retried, not
# inherited.  Every entry additionally expires after a TTL
# (SPLATT_PROBE_CACHE_TTL_S, default 14 days): drivers and libtpu drift
# under a fixed env key, so even a proven verdict is re-earned
# occasionally.

_CACHE_ENV = "SPLATT_PROBE_CACHE"
_CACHE_TTL_ENV = "SPLATT_PROBE_CACHE_TTL_S"
# the default TTL (14 days) lives in utils/env.py:ENV_VARS — the
# single registry the docs and the SPL007 check read


def probe_cache_ttl() -> float:
    """Seconds a cached verdict stays fresh (<= 0 disables expiry)."""
    from splatt_tpu.utils.env import read_env_float

    return read_env_float(_CACHE_TTL_ENV)


def _cache_path():
    import pathlib

    from splatt_tpu.utils.env import read_env

    p = read_env(_CACHE_ENV)
    if p:
        return pathlib.Path(p)
    root = pathlib.Path(__file__).resolve().parents[2]
    # a real repo-checkout marker — the bare existence of a sibling
    # "tools" dir would misfire inside site-packages
    if (root / "pyproject.toml").exists() and (root / "tools").is_dir():
        return root / "tools" / "probe_cache.json"
    return pathlib.Path.home() / ".cache" / "splatt_tpu" / "probe_cache.json"


@functools.cache
def _kernel_src_hash() -> str:
    """Hash of the sources a probe verdict depends on — this module
    plus the layout/tensor builders the probe compiles through
    (blocked.py, coo.py) and the helpers the kernels import from
    ops/mttkrp.py (_acc_dtype, onehot_precision) and utils/env.py
    (ceil_to): editing any of them changes what the probe compiles, so
    it must invalidate every cached verdict — a fixed Mosaic crash is
    re-probed instead of staying disabled behind a stale
    "compile_failed" (and a stale "ok" cannot mask a new rejection)."""
    import hashlib
    import pathlib

    h = hashlib.sha256()
    pkg = pathlib.Path(__file__).resolve().parents[1]
    try:
        for src in (pathlib.Path(__file__), pkg / "blocked.py",
                    pkg / "coo.py", pkg / "config.py",
                    pkg / "ops" / "mttkrp.py", pkg / "utils" / "env.py"):
            h.update(src.read_bytes())
        return h.hexdigest()[:12]
    # splint: ignore[SPL002] sources unreadable (zipped/frozen install):
    # the sentinel keys one shared cache namespace, a safe degradation
    except Exception:
        return "nosrc"


def _cache_env_key() -> str:
    try:
        kind = jax.devices()[0].device_kind
    # splint: ignore[SPL002] device discovery off-accelerator: the
    # cache key degrades to a shared "unknown" namespace
    except Exception:
        kind = "unknown"
    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    # splint: ignore[SPL002] optional-package version probe: absence
    # is a legitimate environment, encoded as "?" in the cache key
    except Exception:
        jl = "?"
    return f"{jax.__version__}|jaxlib{jl}|{kind}|{_kernel_src_hash()}"


def _cache_io_error(op: str, exc) -> None:
    """Report a probe-cache IO failure through the failure taxonomy.

    Cache IO stays best-effort by contract (a broken cache must never
    break dispatch), but the failure used to vanish in a bare
    ``except`` — a cache silently losing every verdict re-spends a
    probe compile per kernel per process, which is exactly the
    silent degradation the run report exists to surface."""
    from splatt_tpu import resilience

    resilience.run_report().add(
        "probe_cache_io_error", op=op,
        failure_class=resilience.classify_failure(exc).value,
        error=resilience.failure_message(exc)[:200])


def _json_cache_load(path, on_error=None):
    """The shared read side of the JSON cache protocol — used by the
    capability-probe cache here and the autotuner's plan cache
    (splatt_tpu/tune.py), and the ONLY sanctioned way to read a shared
    cache file (splint rule SPL011 flags inline ``open`` on cache
    paths): a missing file is the normal first-run path (-> None), any
    other failure is routed to `on_error(op, exc)` (classified into
    the run report) and degrades to None — a broken cache must never
    break dispatch.  Writers use :func:`_json_cache_update`; readers
    need no lock because writes are atomic replaces."""
    import json

    if on_error is None:
        on_error = _cache_io_error
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None  # first run in this environment: nothing cached yet
    except Exception as e:
        on_error("load", e)
        return None


def probe_cache_load(state_key: str):
    """Cached verdict for `state_key` in this environment, or None.
    Returns whatever was stored ("ok"/"compile_failed"/"resource"/
    "timeout"/"infra") — the CALLER decides which states are
    authoritative.  Entries older than :func:`probe_cache_ttl` are
    expired (returned as None) so every verdict, even a proven one, is
    re-earned occasionally on drifting infrastructure."""
    import time

    from splatt_tpu import trace

    data = _json_cache_load(_cache_path())
    if data is None:
        trace.metric_inc("splatt_probe_cache_total", outcome="miss")
        return None
    try:
        entry = data.get(_cache_env_key(), {}).get(state_key)
        if not entry:
            trace.metric_inc("splatt_probe_cache_total", outcome="miss")
            return None
        ttl = probe_cache_ttl()
        if ttl > 0 and time.time() - float(entry.get("ts", 0)) > ttl:
            trace.metric_inc("splatt_probe_cache_total",
                             outcome="expired")
            return None
        trace.metric_inc("splatt_probe_cache_total", outcome="hit")
        return entry["state"]
    except Exception as e:
        # a malformed entry (hand-edited file, schema drift) is an
        # unusable verdict, not a dispatch failure: report and re-probe
        _cache_io_error("load", e)
        return None


#: intra-process serialization of cache writes, complementing the
#: inter-process flock below: concurrent serve jobs (threads in ONE
#: process) tuning simultaneously must not interleave their
#: read-modify-writes.  flock on separate fds does conflict within a
#: process too, but holding a plain Lock makes the thread contract
#: independent of that platform detail and keeps the (open, flock)
#: pair itself race-free.
_JSON_CACHE_THREAD_LOCK = threading.Lock()


def _json_cache_update(path, mutate, on_error=None) -> None:
    """Locked atomic read-modify-write of a small JSON cache file —
    shared by the capability-probe cache here and the autotuner's plan
    cache (splatt_tpu/tune.py).  `mutate(data) -> data` transforms the
    loaded dict (``{}`` when absent/corrupt).  Serialized against other
    processes (flock) AND other threads of this process (concurrent
    serve jobs share the warm caches — docs/serve.md), so two writers
    never drop each other's entries.  Best-effort by contract:
    cache IO must never break dispatch, so every failure is routed to
    `on_error(op, exc)` (classified into the run report) and swallowed.
    """
    import json

    if on_error is None:
        on_error = _cache_io_error
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        # serialize concurrent read-modify-writes (two processes proving
        # different kernels must not drop each other's verdicts)
        import fcntl

        with _JSON_CACHE_THREAD_LOCK, \
                open(str(path) + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                with open(path) as f:
                    data = json.load(f)
            except FileNotFoundError:
                data = {}  # first write creates the file
            except Exception as e:
                # unreadable/corrupt cache: replaced wholesale below —
                # reported, because it drops every other entry
                on_error("store", e)
                data = {}
            data = mutate(data)
            from splatt_tpu.utils.durable import publish_json

            publish_json(path, data, indent=1, sort_keys=True)
    except Exception as e:
        # best-effort by contract (cache IO must never break dispatch):
        # degrade to an uncached probe/plan, but say so in the run report
        on_error("store", e)


def probe_cache_store(state_key: str, state: str) -> None:
    """Record a probe verdict on disk (atomic replace; best-effort —
    cache IO must never break dispatch).  Timestamps let a TPU session
    commit the file as evidence of when each verdict was proven."""
    import time

    env_key = _cache_env_key()
    entry = {"state": state, "ts": time.time()}

    def mutate(data):
        data.setdefault(env_key, {})[state_key] = entry
        return data

    _json_cache_update(_cache_path(), mutate)


def probe_regime(dims, block: int) -> str:
    """Which regime a (dims, block) config falls in: "multick" when a
    block holds more nonzeros than some gathered factor has rows
    (padded to 128), else "ck1".  Capability probes and per-shape
    demotions are keyed by it, so a failure in one regime never vetoes
    the other."""
    return ("multick"
            if any(block > ceil_to(int(d), 128) for d in dims)
            else "ck1")


def _probe_compiles(kernel_fn, name: str, regime: str, block: int,
                    case) -> bool:
    """Whether `kernel_fn` COMPILES for this backend, by running
    ``case(kernel_fn, regime, block)``: a compile at a shape
    representative of `regime` at the CALLER's block size.  Lowering
    alone is not enough: Mosaic layout inference (e.g. the "Invalid
    input layout" broadcast restriction) only runs at compile time."""
    state_key = f"{name}:{regime}:b{block}"
    if jax.default_backend() != "tpu":
        PROBE_STATES[state_key] = "not_tpu"
        return False

    # Proven verdicts persist across processes ("resource" is proven
    # too, but scoped: the state_key already carries regime+block, so a
    # capacity rejection only gates this shape); "infra" (and a
    # "timeout" left by an older build) does not short-circuit: an
    # unproven verdict is re-probed, never inherited.  Every verdict
    # but "ok" is reported loudly — a probe must never quietly move a
    # run off its Pallas engine.
    cached = probe_cache_load(state_key)
    if cached in ("ok", "compile_failed", "resource"):
        PROBE_STATES[state_key] = cached
        if cached != "ok":
            _report_probe(state_key, cached, "cached verdict")
        return cached == "ok"

    from splatt_tpu import resilience
    from splatt_tpu.utils import faults

    # Failure taxonomy (splatt_tpu.resilience): only a recognized
    # DETERMINISTIC rejection may be persisted as "compile_failed" —
    # the cache makes any misclassification permanent for the whole
    # environment, so the persisted-negative set is a whitelist (Mosaic
    # compiler crash/rejection signatures), not a transient-error
    # blocklist.  TRANSIENT failures are retried in place with capped
    # backoff + jitter and, if they persist, recorded as "infra":
    # rejected for THIS process, re-probed by the next.  RESOURCE
    # failures (OOM/VMEM) are proven capacity verdicts scoped to this
    # (regime, block) shape.

    def attempt():
        faults.maybe_fail("probe_compile")
        return case(kernel_fn, regime, block)

    try:
        state = ("ok" if resilience.retry_transient(attempt,
                                                    label=state_key)
                 else "compile_failed")
        err = None
    except Exception as e:
        cls = resilience.classify_failure(e)
        err = resilience.failure_message(e)[:300]
        if cls is resilience.FailureClass.DETERMINISTIC:
            state = "compile_failed"
        elif cls is resilience.FailureClass.RESOURCE:
            state = "resource"
        else:
            # transient (retries exhausted) or unknown: unproven
            state = "infra"
    PROBE_STATES[state_key] = state
    probe_cache_store(state_key, state)
    if state != "ok":
        _report_probe(state_key, state, err or "probe returned False")
    return state == "ok"


def _report_probe(state_key: str, state: str, why: str) -> None:
    """A probe verdict other than "ok": run-report event + stderr line."""
    import sys

    from splatt_tpu import resilience

    resilience.run_report().add("probe_downgrade", state_key=state_key,
                                verdict=state)
    print(f"splatt-tpu: WARNING: {state_key} capability probe: {state} "
          f"({why}); this engine is skipped", file=sys.stderr, flush=True)


# -- dense-mode MXU engine (docs/dense.md) ----------------------------------
#
# A DenseModeLayout's MTTKRP is X_(m) @ KR(other factors): a batched
# (tile, span) @ (span, R) matmul — the one shape the MXU is literally
# built for, with NO index streams, gathers or one-hots anywhere.  The
# kernel stages the two Khatri-Rao operands (the chained outer-factor
# product w and the lane-padded inner factor u, built ONCE by
# ops.mttkrp.dense_operands and shared with the XLA reference for bit
# parity) whole in VMEM, builds the (span, R) KR tile in registers via
# a broadcast multiply — the column space is a regular grid, so no
# arbitrary gather is ever needed (the construct Mosaic cannot lower) —
# and drives one dot_general per row tile.

def _dense_kernel(tiles_ref, w_ref, u_ref, out_ref, *, rank: int,
                  precision):
    w = w_ref[...]                           # (n_outer, R)
    u = u_ref[...]                           # (inner_pad, R)
    tiles = tiles_ref[0].astype(w.dtype)     # (tile, span)
    kr = (w[:, None, :] * u[None, :, :]).reshape(-1, rank)   # (span, R)
    out_ref[0] = jax.lax.dot_general(
        tiles, kr,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
        precision=precision)


def dense_vmem_ok(layout, factors, mode: int,
                  budget_bytes: int = None) -> bool:
    """VMEM plan of the dense MXU kernel: one (tile, span) value tile
    resident per step, both KR operands whole, the (span, R) Khatri-Rao
    product built in registers, and the (tile, R) output block at the
    accumulator width."""
    if budget_bytes is None:
        budget_bytes = _vmem_budget()
    geo = layout.geometry
    R = int(factors[0].shape[1])
    itemsize = jnp.dtype(factors[0].dtype).itemsize
    tile_bytes = layout.tile * layout.span * layout.tiles.dtype.itemsize
    work = ((geo.n_outer + geo.inner_pad) * R * itemsize      # w + u
            + layout.span * R * itemsize                      # kr
            + layout.tile * R * max(itemsize, 4))             # out block
    return tile_bytes + work <= budget_bytes


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def fused_dense(layout, factors, mode: int,
                interpret: bool = False) -> jax.Array:
    """Dense-mode MTTKRP on the MXU over a
    :class:`splatt_tpu.blocked.DenseModeLayout`.

    Interpret mode is bit-identical to :func:`ops.mttkrp.dense_mttkrp`
    by construction: both build (w, u) through the same
    ``dense_operands``, form the same (span, R) KR product, and reduce
    each output element with ONE dot_general over span at the same
    precision and accumulator dtype.  Output: (dim, R) at the
    accumulator dtype — pad rows trimmed, pad columns contributing
    exact zeros (the inner factor is zero-padded)."""
    from splatt_tpu.ops.mttkrp import dense_operands, mxu_precision

    if mode != layout.mode:
        raise ValueError("fused_dense requires the layout's own mode")
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    with jax.named_scope(OPERANDS):
        w, u = dense_operands(layout, factors, mode)
    ntiles, tile, span = (int(s) for s in layout.tiles.shape)
    acc = _acc_dtype(dtype)
    with jax.named_scope(REDUCE):
        out = pl.pallas_call(
            functools.partial(_dense_kernel, rank=R,
                              precision=mxu_precision(dtype)),
            grid=(ntiles,),
            in_specs=[
                pl.BlockSpec((1, tile, span), lambda i: (i, 0, 0)),
                pl.BlockSpec((int(w.shape[0]), R), lambda i: (0, 0)),
                pl.BlockSpec((int(u.shape[0]), R), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, tile, R), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((ntiles, tile, R), acc),
            interpret=interpret,
            name="fused_dense",
            compiler_params=_compiler_params(),
        )(layout.tiles, w, u)
    with jax.named_scope(MERGE):
        return out.reshape(-1, R)[:layout.dim]


def _probe_case_dense(kernel_fn, regime: str, block: int) -> bool:
    """The dense-engine probe compile: a synthetic near-dense mode at a
    production-like (tile, span); the Mosaic-sensitive step the probe
    exercises is the in-kernel (n_outer, inner_pad, R) -> (span, R)
    Khatri-Rao reshape."""
    import numpy as np

    from splatt_tpu.blocked import build_dense_layout
    from splatt_tpu.coo import SparseTensor

    rng = np.random.default_rng(0)
    dims = (64, 32, 256)
    nnz = 65536
    rank = 48 if _vmem_limit() >= (32 << 20) else 16
    inds = np.stack([rng.integers(0, d, nnz) for d in dims])
    tt = SparseTensor(inds=inds.astype(np.int64), vals=np.ones(nnz),
                      dims=dims)
    from splatt_tpu.config import fit_dtype

    lay = build_dense_layout(tt, 0)
    fac = [jnp.zeros((d, rank), fit_dtype()) for d in dims]
    kernel_fn.lower(lay, fac, mode=0, interpret=False).compile()
    return True


@functools.cache
def fused_dense_supported(regime: str = "ck1", block: int = 4096) -> bool:
    """Whether the dense-mode MXU kernel compiles here (the in-kernel
    broadcast-multiply + (n_outer·inner_pad, R) reshape that builds the
    Khatri-Rao tile is the Mosaic-sensitive step), probed per
    (regime, tile) — an unlowerable form demotes cleanly to the
    ``dense_xla`` reference path."""
    return _probe_compiles(fused_dense, "fused_dense", regime, block,
                           case=_probe_case_dense)
