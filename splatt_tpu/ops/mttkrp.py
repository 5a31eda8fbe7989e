"""MTTKRP — the hot kernel (≙ src/mttkrp.c, 1931 LoC in the reference).

``mttkrp(X, factors, mode)`` computes, for every output row i of `mode`::

    M[i, :] = Σ_{nnz n : ind_mode[n] = i}  val[n] · ∏_{k≠mode} U_k[ind_k[n], :]

Four execution paths replace the reference's root/internal/leaf ×
locked/nolock × tiled traversal matrix (src/mttkrp.c:104-1341):

- ``stream``        — COO gather + segment_sum.  Trivially correct; the
  differential-test gold oracle (≙ mttkrp_stream, src/mttkrp.c:1697-1757).
- ``sorted_onehot`` — blocked layout sorted by the output mode: per-block
  partial products reduced by a small one-hot matmul on the MXU, then a
  block-level scatter combine.  ≙ the root-mode CSF traversal — scatter
  contention is gone by construction, like CSF's accumulate-up-the-tree.
- ``privatized``    — short output modes: full-width one-hot per block and
  a pure tree-sum over blocks, no scatter at all.  ≙ per-thread output
  replicas + parallel reduction (p_reduce_privatized, src/mttkrp.c:56-87).
- ``scatter``       — generic path for modes the layout is not sorted for
  (≙ internal/leaf traversals with the mutex pool): XLA scatter-add via
  segment_sum, flagged sorted when the layout mode matches.

Path choice (≙ mttkrp_csf dispatch src/mttkrp.c:1287-1341 +
p_is_privatized :221-236) is static at trace time.
"""

from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from splatt_tpu.blocked import BlockedSparse, ModeLayout
from splatt_tpu.config import Options
from splatt_tpu.coo import SparseTensor
from splatt_tpu.utils.env import read_env_int

PATHS = ("stream", "sorted_onehot", "privatized", "scatter",
         "sorted_scatter", "dense")

#: engines that consume a compact layout's encoded streams NATIVELY —
#: decode runs per chunk inside the scan step (xla_scan) or fused into
#: the scatter/segment sum (xla), so the decoded i32 temp never lands in HBM and achieved bytes
#: track the encoded streams (docs/format.md).  Everything else
#: decodes at operand prep; bench's decode_overhead model and the
#: format_decode run-report event both read this set.
STREAM_NATIVE_ENGINES = ("xla_scan", "xla")


def _gather_prod(inds: jax.Array, vals: jax.Array,
                 factors: Sequence[jax.Array], mode: int) -> jax.Array:
    """(nnz, R) partial products: val · ⊛_{k≠mode} U_k[ind_k].

    Gathers lower to XLA dynamic-gather; the Hadamard chain fuses.
    Out-of-range (sentinel) indices clamp — their values are zero.
    """
    dtype = factors[0].dtype
    prod = vals.astype(dtype)[:, None]
    for k, U in enumerate(factors):
        if k != mode:
            prod = prod * jnp.take(U, inds[k], axis=0, mode="clip",
                                   indices_are_sorted=False)
    return prod


def _gather_prod_layout(layout: ModeLayout, factors: Sequence[jax.Array],
                        mode: int) -> jax.Array:
    """:func:`_gather_prod` over a layout's ENCODED streams: v2 local
    indices decode per mode (``local + base``, fused into the gather's
    index computation) and bf16-stored values decode at the gather
    (``astype`` to the factor dtype) — the layout never rematerializes
    a global-i32/f32 copy of itself."""
    dtype = factors[0].dtype
    prod = layout.vals.astype(dtype)[:, None]
    for k, U in enumerate(factors):
        if k != mode:
            prod = prod * jnp.take(U, layout.mode_ids(k), axis=0,
                                   mode="clip", indices_are_sorted=False)
    return prod


def _acc_dtype(dtype):
    """Accumulate bf16/f16 operands in f32 (the MXU-native mixed
    pattern: low-precision reads, full-precision accumulation).
    Delegates to :func:`splatt_tpu.config.acc_dtype` — the config
    module owns dtype policy; this name survives as the engines'
    local spelling (and the probe cache hashes config.py so policy
    edits invalidate cached verdicts)."""
    from splatt_tpu.config import acc_dtype

    return acc_dtype(dtype)


acc_dtype = _acc_dtype  # public name for the sharded sweeps


def mxu_precision(dtype):
    """MXU pass policy for dots with `dtype` operands.

    The TPU MXU multiplies in bf16: a DEFAULT-precision f32 dot rounds
    each operand to one bf16 pass (measured max_err ~7e-2 on the one-hot
    contraction on a v5e — outside even the reference's float tolerance,
    tests/mttkrp_test.c:25-30).  HIGHEST decomposes each f32 operand
    into bf16 pieces for f32-faithful products; bf16 operands are native
    single-pass and keep DEFAULT.
    """
    if dtype == jnp.float32:  # splint: ignore[SPL005] mxu_precision IS dtype-policy code, colocated with the kernels it guards
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def onehot_precision(dtype, onehot_side: str = "lhs"):
    """Per-operand MXU precision for one-hot contractions.

    A one-hot operand holds only 0.0/1.0 — exactly representable in one
    bf16 pass — so only the *values* operand needs the HIGHEST bf16
    decomposition for f32-faithful products.  Per-operand precision
    keeps exactness while dropping the pass count versus HIGHEST on
    both sides.  `onehot_side` names which dot operand is the one-hot.
    """
    if dtype != jnp.float32:  # splint: ignore[SPL005] onehot_precision IS dtype-policy code, colocated with the kernels it guards
        p = jax.lax.Precision.DEFAULT
        return (p, p)
    if onehot_side == "lhs":
        return (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST)
    return (jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT)


# -- stream (oracle) -------------------------------------------------------

@partial(jax.jit, static_argnames=("mode", "dim"))
def mttkrp_stream(inds: jax.Array, vals: jax.Array,
                  factors: List[jax.Array], mode: int, dim: int) -> jax.Array:
    """COO streaming MTTKRP — the gold oracle (≙ src/mttkrp.c:1697-1757)."""
    prod = _gather_prod(inds, vals, factors, mode)
    acc = _acc_dtype(prod.dtype)
    return jax.ops.segment_sum(prod.astype(acc), inds[mode],
                               num_segments=dim)


def mttkrp_batched_stream(inds: jax.Array, vals: jax.Array,
                          factors: Sequence[jax.Array], mode: int,
                          dim: int) -> jax.Array:
    """Vmapped stream MTTKRP over a stacked same-regime batch
    (docs/batched.md): `inds` is ``(K, nmodes, nnz_pad)`` global i32,
    `vals` ``(K, nnz_pad)``, `factors` per-mode ``(K, dim_m, R)`` —
    each slot computes exactly :func:`mttkrp_stream`'s gather/
    segment-sum dataflow over its own lane (pads are additive
    identities), with the engines' f32 accumulation under bf16
    storage.  Pure jnp and un-jitted here: the batched sweep
    (cpd._make_batched_sweep) owns the one jit wrapping K tenants."""
    def one(inds_s, vals_s, factors_s):
        prod = _gather_prod(inds_s, vals_s, factors_s, mode)
        acc = _acc_dtype(prod.dtype)
        return jax.ops.segment_sum(prod.astype(acc), inds_s[mode],
                                   num_segments=dim)

    return jax.vmap(one)(inds, vals, list(factors))


@partial(jax.jit, static_argnames=("mode", "dim"))
def mttkrp_ttbox(inds: jax.Array, vals: jax.Array,
                 factors: List[jax.Array], mode: int, dim: int) -> jax.Array:
    """Column-major rank loop (≙ mttkrp_ttbox, src/mttkrp.c:1655-1695).

    Historical Tensor-Toolbox formulation: one pass over the nonzeros
    per rank column.  Kept as a bench baseline — rank sequentialism is
    exactly what the MXU-batched paths avoid.  (The GigaTensor CSR
    variant, src/mttkrp.c:1604-1649, is deliberately not reproduced:
    it materializes the Khatri-Rao column space, the one thing a
    TPU formulation must never do.)
    """

    def col(r):
        p = vals.astype(factors[0].dtype)
        for k, U in enumerate(factors):
            if k != mode:
                p = p * jnp.take(U[:, r], inds[k], mode="clip")
        # upcast-before-reduce like mttkrp_stream: bf16 columns must
        # not accumulate at 8 mantissa bits (SPL024)
        return jax.ops.segment_sum(p.astype(_acc_dtype(p.dtype)),
                                   inds[mode], num_segments=dim)

    rank = factors[0].shape[1]
    cols = jax.lax.map(col, jnp.arange(rank))
    return cols.T


# -- dense path (docs/dense.md) --------------------------------------------

def dense_operands(layout, factors: Sequence[jax.Array], mode: int):
    """The two Khatri-Rao operands of the dense-mode matmul: ``w``, the
    chained Khatri-Rao product of the OUTER non-target factors
    ((n_outer, R), all-ones when the mode has only one other), and
    ``u``, the INNER factor zero-padded to the tile span's 128-lane
    boundary ((inner_pad, R)) — so the pad columns of the value tiles
    meet exact-zero KR entries and contribute nothing, with no mask
    read on the hot path.

    Column c of the unfolding is ``outer_lin * inner_pad + inner_idx``
    (build_dense_layout's scatter), which is exactly the row order of
    ``(w[:, None, :] * u[None, :, :]).reshape(span, R)`` — the KR tile
    is a regular grid, so no gather is ever needed to build it.  ONE
    definition shared by the Pallas kernel and the XLA reference: bit
    parity between the engines starts with identical operands."""
    geo = layout.geometry
    dtype = factors[0].dtype
    R = int(factors[0].shape[1])
    w = jnp.ones((1, R), dtype=dtype)
    for k in geo.others[:-1]:
        w = (w[:, None, :] * factors[k][None, :, :]).reshape(-1, R)
    u = factors[geo.inner]
    pad = geo.inner_pad - int(u.shape[0])
    if pad:
        u = jnp.pad(u, ((0, pad), (0, 0)))
    return w, u


def dense_mttkrp(layout, factors: Sequence[jax.Array],
                 mode: int) -> jax.Array:
    """Dense-mode MTTKRP, XLA reference engine (``dense_xla``): the
    mode's unfolding tiles contracted against the Khatri-Rao'd factors
    in one batched dot_general — no index streams, no gathers, no
    scatter.  The always-works terminal of the dense engine chain
    (plain dot_general: no kernel or VMEM preconditions); the Pallas
    ``fused_dense`` engine computes the identical reduction per row
    tile (same operands, same precision, same accumulator dtype)."""
    if mode != layout.mode:
        raise ValueError("dense_mttkrp requires the layout's own mode")
    dtype = factors[0].dtype
    R = int(factors[0].shape[1])
    w, u = dense_operands(layout, factors, mode)
    kr = (w[:, None, :] * u[None, :, :]).reshape(-1, R)   # (span, R)
    out = jax.lax.dot_general(
        layout.tiles.astype(dtype), kr,
        dimension_numbers=(((2,), (0,)), ((), ())),
        preferred_element_type=_acc_dtype(dtype),
        precision=mxu_precision(dtype))                   # (ntiles, tile, R)
    return out.reshape(-1, R)[:layout.dim]


# -- blocked paths ---------------------------------------------------------

#: elements of one-hot materialized per scan step of the XLA engine —
#: the fallback's main tuning knob (more = fewer, bigger fused steps).
#: Env-overridable so a tuning sweep can measure it.  The unfused
#: Pallas engine sizes its steps by the same budget, counted in
#: materialized (C, B, R) products instead.
_SCAN_TARGET = read_env_int("SPLATT_SCAN_TARGET_ELEMS")


def _block_chunks(nblocks: int, elems_per_block: int,
                  target_elems: Optional[int] = None) -> int:
    """Blocks per scan step, sized to bound one-hot materialization."""
    if target_elems is None:
        target_elems = _SCAN_TARGET
    c = max(1, target_elems // max(elems_per_block, 1))
    return min(c, nblocks)


def _scan_fused(layout: ModeLayout, factors: Sequence[jax.Array], mode: int,
                width: int, accumulate: bool,
                target_elems: Optional[int] = None,
                pallas: bool = False, interpret: bool = False) -> jax.Array:
    """Gather + Hadamard + one-hot reduce as a scan over block chunks.

    The (nnz, R) partial-product tensor never exists whole in HBM: each
    scan step gathers the factor rows for one chunk of blocks, forms
    the Hadamard products, and reduces them with the one-hot
    contraction.  ≙ the reference's hot loop reading factor rows once
    per fiber inside the traversal (src/mttkrp.c:427-463) rather than
    staging an intermediate.

    The reduction is an XLA einsum (the ``xla_scan`` engine), or with
    `pallas` the Mosaic one-hot kernels (the ``unfused_pallas`` engine:
    onehot_reduce_sorted / onehot_reduce_full), which build the one-hot
    in VMEM instead of materializing it.  `target_elems` bounds the
    elements one step materializes in HBM: the one-hot for the einsum,
    the (C, B, R) products for the kernels.
    """
    nb, B = layout.nblocks, layout.block
    R = int(factors[0].shape[1])
    dtype = factors[0].dtype
    nmodes = layout.nmodes
    C = _block_chunks(nb, (R if pallas else width) * B, target_elems)
    nsteps = -(-nb // C)
    nb_pad = nsteps * C

    # per-mode encoded streams through the stream-consumer interface
    # (blocked.ModeStreams): v1 = global i32 rows, the compact
    # encodings = narrow local/segment/delta/RLE streams + per-block
    # bases.  Decoding happens inside the scan step via the SHARED
    # decode helpers (blocked.decode_gather_ids/decode_segment_ids),
    # one chunk at a time, so the global-i32 form never exists whole in HBM for
    # encoded layouts.
    from splatt_tpu.blocked import decode_global_ids, decode_segment_ids

    streams, bases, encs = layout.mode_streams()
    streams = list(streams)
    vals = layout.vals
    row_start = layout.row_start
    if nb_pad != nb:
        # pad with whole sentinel blocks: mode index = dim (falls in the
        # dropped tail rows; for v2 the BASE carries the sentinel and
        # the stored locals stay 0 — an RLE pad block's count vector is
        # [B, 0, ...], every entry in segment 0), other indices 0,
        # values 0
        pad = (nb_pad - nb) * B
        for k, s in enumerate(streams):
            if encs[k] == "rle":
                s = jnp.pad(s, ((0, nb_pad - nb), (0, 0)))
                streams[k] = s.at[nb:, 0].set(B)
            else:
                streams[k] = jnp.pad(
                    s, (0, pad),
                    constant_values=(layout.dim
                                     if bases is None and k == mode
                                     else 0))
        vals = jnp.pad(vals, (0, pad))
        row_start = jnp.pad(row_start, (0, nb_pad - nb),
                            constant_values=layout.dim)
        if bases is not None:
            bases = [jnp.pad(b, (0, nb_pad - nb),
                             constant_values=(layout.dim if k == mode
                                              else 0))
                     for k, b in enumerate(bases)]

    inds_s = tuple(s.reshape(nsteps, C, -1) for s in streams)
    vals_s = vals.reshape(nsteps, C, B)
    rs_s = row_start.reshape(nsteps, C)
    base_s = (None if bases is None
              else tuple(b.reshape(nsteps, C) for b in bases))

    iota = jnp.arange(width, dtype=jnp.int32)
    acc = _acc_dtype(dtype)

    def step(carry, xs):
        # per-mode (C,B) encoded chunks ((C,S) counts under RLE),
        # (C,B) vals, (C,) run starts, per-mode (C,) bases (None for
        # v1) — decoded here, in registers, via the shared helpers
        inds_c, vals_c, rs_c, base_c = xs
        prod = vals_c.astype(dtype)[..., None]
        for k in range(nmodes):
            if k != mode:
                # decode_global_ids handles every stream kind — incl.
                # gathering the layout's SORTED mode (its segment/RLE
                # stream expands here) when dispatching another mode
                g = decode_global_ids(
                    inds_c[k],
                    None if base_c is None else base_c[k][:, None],
                    encs[k], B)
                rows = jnp.take(factors[k], g.reshape(-1), axis=0,
                                mode="clip").reshape(C, B, R)
                prod = prod * rows
        if accumulate:
            if base_c is None:
                local = inds_c[mode]
            else:
                local = decode_global_ids(inds_c[mode],
                                          base_c[mode][:, None],
                                          encs[mode], B)
        elif base_c is None:
            local = inds_c[mode] - rs_c[:, None]
        else:
            local = decode_segment_ids(inds_c[mode], encs[mode], B)
        if pallas:
            from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                                       onehot_reduce_sorted,
                                                       vmem_chunk)

            kchunk = vmem_chunk(width, B, R, jnp.dtype(dtype).itemsize)
            if accumulate:
                return carry + onehot_reduce_full(
                    local, prod, width, interpret=interpret,
                    chunk=kchunk), None
            return carry, onehot_reduce_sorted(local, prod, width,
                                               interpret=interpret,
                                               chunk=kchunk)
        onehot = (local[:, None, :] == iota[None, :, None]).astype(dtype)
        part = jnp.einsum("cwb,cbr->cwr", onehot, prod,
                          preferred_element_type=acc,
                          precision=onehot_precision(dtype, "lhs"))
        if accumulate:
            return carry + jnp.sum(part, axis=0), None
        return carry, part

    if accumulate:
        init = jnp.zeros((width, R), dtype=acc)
        out, _ = jax.lax.scan(step, init, (inds_s, vals_s, rs_s, base_s))
        return out
    _, parts = jax.lax.scan(step, None, (inds_s, vals_s, rs_s, base_s))
    return parts.reshape(nb_pad, width, R)[:nb]


def _tuned_plan_for(layout: ModeLayout, factors: Sequence[jax.Array],
                    mode: int, path: str,
                    autotune: Optional[bool] = None,
                    shape_key: Optional[str] = None):
    """The applicable cached autotuner plan for this dispatch, or None.

    Applicability is strict — the plan was measured for exactly this
    (path, nnz_block) configuration, so a dispatch whose layout block
    or chosen path disagrees keeps the heuristic chain, and an engine
    the resilience registry demoted mid-run is never resurrected by a
    stale plan.  The tuner can make dispatch faster, never wronger.
    """
    from splatt_tpu import resilience, tune

    if not tune.autotune_enabled(autotune):
        return None
    nnz = getattr(layout, "nnz", None)
    if nnz is None:
        return None  # partial layout (gate-probing tests): no plan key
    plan = tune.cached_plan([int(f.shape[0]) for f in factors],
                            nnz, mode, int(factors[0].shape[1]),
                            factors[0].dtype,
                            skew=getattr(layout, "skew", ""),
                            mode_density=getattr(layout,
                                                 "density_bucket", ""))
    if (plan is None or plan.path != path
            or plan.nnz_block != layout.block
            or plan.idx_width != getattr(layout, "idx_width", "i32")
            or plan.val_storage != getattr(layout, "val_storage", "auto")
            or plan.packing != getattr(layout, "packing", "fixed")
            or plan.reorder != getattr(layout, "reorder", "identity")):
        # the format AND the layout-balance axes (packing, reorder —
        # docs/layout-balance.md) are part of the measured
        # configuration: a plan for the v2 encoding never steers a v1
        # layout's dispatch, a balanced-packing plan never steers a
        # fixed layout (and vice versa) — the tuner can make dispatch
        # faster, never wronger
        return None
    # per-shape (OOM) demotions only match with the shape_key, so it
    # must be computed when the caller (engine_plan, the cpd_als plan
    # report) did not thread one through — otherwise reporting would
    # promote an engine dispatch refuses to run
    if shape_key is None:
        shape_key = _engine_shape_key(layout, factors, mode)
    if resilience.is_demoted(plan.engine, shape_key):
        return None
    return plan


#: (engine, shape_key) pairs whose first (compile-bearing) dispatch
#: already ran under the deadline watchdog — warm calls skip the timer
_DEADLINE_ARMED: set = set()


def mttkrp_blocked(layout: ModeLayout, factors: List[jax.Array], mode: int,
                   path: str = "sorted_onehot",
                   impl: str = "xla",
                   scan_target: Optional[int] = None,
                   fallback: Optional[bool] = None,
                   autotune: Optional[bool] = None) -> jax.Array:
    """Blocked MTTKRP over one :class:`ModeLayout`.

    `path` picks the algorithm (static dispatch); `impl` picks the
    one-hot reduction engine:

    - "xla": fused scan — gather, Hadamard and the one-hot contraction
      all live inside one scan step, so the (nnz, R) partial-product
      tensor never hits HBM;
    - "pallas" (TPU): the fused Mosaic kernels (gather + Hadamard +
      reduce in VMEM) where Mosaic can lower their gather — every
      gathered factor within one 128-lane vreg — else the unfused
      engine: the same block-chunk scan as "xla", its one-hot reduce a
      Mosaic kernel;
    - "pallas_interpret": kernel semantics on CPU, for tests.

    `scan_target` tunes how much one-hot the XLA engine's scan step
    materializes (default: the autotuned plan's value when one applies,
    else SPLATT_SCAN_TARGET_ELEMS).  Resolved here — outside the jit —
    so it is part of the cache key and changing it always takes effect.

    Autotuning (`autotune`, default from Options.autotune /
    SPLATT_AUTOTUNE): when the plan cache (splatt_tpu/tune.py) holds a
    measured winner for this exact (shape regime, rank, dtype, path,
    nnz_block), that engine heads the chain; everything below — lazy
    probes, demotion, runtime fallback — applies to it unchanged.

    Runtime graceful degradation (`fallback`, default from
    SPLATT_ENGINE_FALLBACK / resilience.fallback_enabled): the ordered
    engine chain from :func:`engine_chain` is walked engine by engine;
    a failure of the selected engine demotes it in the resilience
    registry (process-wide, or per-shape for RESOURCE failures) and the
    next engine runs — one backend's failure degrades, not kills, the
    run.  The terminal "xla" engine (the stream/scatter formulation)
    has no kernel/VMEM preconditions, so the chain cannot run dry.
    """
    from splatt_tpu import resilience
    from splatt_tpu.utils import faults

    if fallback is None:
        fallback = resilience.fallback_enabled()
    # dense tile layouts have no streams to decode — they skip the
    # format-decode machinery entirely and dispatch on their own
    # engine chain (fused_dense -> dense_xla, docs/dense.md).  The
    # layout's encoding is authoritative over the `path` default, so a
    # caller handing us a dense layout without asking choose_path first
    # still lands on the dense matmul, never a sparse body that would
    # choke on the missing index streams.
    if getattr(layout, "encoding", "v1") == "dense":
        path = "dense"
    if getattr(layout, "encoding", "v1") not in ("v1", "dense"):
        from splatt_tpu.blocked import decode_to_v1
        from splatt_tpu.config import resolve_decode

        if resolve_decode() == "prep":
            # the A/B lever (docs/format.md): materialize the decoded
            # global-i32 form BEFORE any engine runs, so every path —
            # Pallas and XLA alike — executes the pre-format-v2
            # operand-prep dataflow the decode_overhead model prices
            layout = decode_to_v1(layout)
        else:
            # the format.decode fault site (docs/format.md): native
            # stream consumption failing at dispatch must degrade the
            # RUN, not kill it — classify, report format_fallback
            # evidence, and fall back to the materialized global-i32
            # v1 path every engine can always consume (bit-identical
            # by construction: decode_to_v1 runs the same
            # stream-consumer decode)
            try:
                faults.maybe_fail("format.decode")
            except Exception as e:
                cls = resilience.classify_failure(e)
                resilience.run_report().add(
                    "format_fallback", mode=int(mode), site="decode",
                    idx_width=getattr(layout, "idx_width", "?"),
                    failure_class=cls.value,
                    error=resilience.failure_message(e)[:200])
                layout = decode_to_v1(layout)
    # regime/shape_key are computed ONCE per dispatch and threaded
    # through the chain build — this runs once per mode per sweep
    # iteration, and the three consumers must agree on the regime
    regime = _chain_regime(layout, factors, mode)
    shape_key = _engine_shape_key(layout, factors, mode, regime=regime)
    chain = engine_chain(layout, factors, mode, path, impl,
                         shape_key=shape_key)
    # the autotuner's plan is the new head of dispatch: a measured
    # winner for this exact (path, block) is tried first, and everything
    # below — probes, demotion, fallback on failure — applies to it
    # unchanged, so a stale plan degrades to the heuristic chain
    tuned = _tuned_plan_for(layout, factors, mode, path,
                            autotune=autotune, shape_key=shape_key)
    if tuned is not None and tuned.engine in chain:
        if scan_target is None and tuned.engine == "xla_scan":
            scan_target = tuned.scan_target
        chain = [tuned.engine] + [e for e in chain if e != tuned.engine]
    if scan_target is None:
        scan_target = _SCAN_TARGET
    interpret = impl == "pallas_interpret"
    last = len(chain) - 1
    for i, engine in enumerate(chain):
        if i < last and not _engine_probed_ok(engine, regime,
                                              layout.block, interpret):
            continue

        def attempt(engine=engine):
            faults.maybe_fail(f"engine.{engine}")
            # deadline watchdog (docs/guarded-als.md): bounds this
            # engine's FIRST call per shape — the one that compiles
            # (off by default; a blown deadline classifies TIMEOUT and
            # demotes per-shape below, exactly like OOM).  Warm
            # dispatches are microsecond async launches: skipping the
            # watchdog there saves a Timer thread per MTTKRP call.
            first = (engine, shape_key) not in _DEADLINE_ARMED
            if first:
                _DEADLINE_ARMED.add((engine, shape_key))
                if getattr(layout, "encoding", "v1") == "dense":
                    # first (compile-bearing) dispatch over a dense
                    # tile layout: record the hybrid dispatcher's
                    # verdict as evidence (docs/dense.md) — once per
                    # (engine, shape), like the deadline arming
                    resilience.run_report().add(
                        "dense_dispatch", engine=engine, mode=int(mode),
                        tile=int(layout.block), span=int(layout.span),
                        density_bucket=getattr(layout,
                                               "density_bucket", ""))
                elif getattr(layout, "encoding", "v1") != "v1":
                    # first (compile-bearing) dispatch over an encoded
                    # layout: record WHERE its decode runs — natively
                    # in-kernel/per-chunk, or at operand prep — next
                    # to the consumed encoding (docs/format.md); once
                    # per (engine, shape), like the deadline arming
                    resilience.run_report().add(
                        "format_decode", engine=engine, mode=int(mode),
                        enc=layout.format_desc(),
                        strategy=("kernel"
                                  if engine in STREAM_NATIVE_ENGINES
                                  else "prep"))
                with resilience.deadline(f"engine.{engine}"):
                    out = _mttkrp_blocked_jit(layout, factors, mode,
                                              path, impl, scan_target,
                                              engine)
            else:
                out = _mttkrp_blocked_jit(layout, factors, mode, path,
                                          impl, scan_target, engine)
            # chaos hook: a poison-armed engine fault corrupts this
            # engine's OUTPUT with non-finite values (under a fused
            # whole-sweep trace the poison is baked into the traced
            # program — flushed by the sweep rebuild a health rollback
            # performs)
            return faults.poison(f"engine.{engine}", out)

        try:
            resilience.note_engine_attempt(engine, shape_key)
            # TRANSIENT failures (a compile service hiccuping on
            # this engine's first jit) are retried in place with capped
            # backoff per the taxonomy contract — without this, one
            # transient HTTP 500 at compile time would demote the
            # flagship engine for the whole run, the PR 1 bug class at
            # run scope.  Deterministic/resource/unknown failures
            # propagate immediately to the demotion below.  The span
            # records host-side dispatch cost with the CHOSEN engine;
            # under a jitted sweep it fires at trace time, once per
            # compilation (docs/observability.md).
            from splatt_tpu import trace

            with trace.span("mttkrp.dispatch", mode=int(mode), path=path,
                            engine=engine, block=int(layout.block),
                            enc=getattr(layout, "format_desc",
                                        lambda: "i32/glob/?")()):
                return resilience.retry_transient(attempt,
                                                  label=f"engine.{engine}")
        except Exception as e:
            if not fallback or i == last:
                raise
            resilience.demote_engine(engine, e, shape_key=shape_key)
    raise AssertionError("engine chain exhausted")  # pragma: no cover


@partial(jax.jit, static_argnames=("mode", "path", "impl", "scan_target",
                                   "engine"))
def _mttkrp_blocked_jit(layout: ModeLayout, factors: List[jax.Array],
                        mode: int, path: str, impl: str,
                        scan_target: int, engine: str) -> jax.Array:
    from splatt_tpu.ops.pallas_kernels import fused_mttkrp_t, fused_mttkrp_tg

    dim = int(factors[mode].shape[0])
    R = factors[mode].shape[1]
    interpret = impl == "pallas_interpret"

    if path == "dense":
        # the dense tile layout's batched matmul (docs/dense.md): the
        # MXU kernel when probed/VMEM-fit, else the dot_general
        # reference — bit-identical engines, so demotion costs speed,
        # never numerics
        if engine == "fused_dense":
            from splatt_tpu.ops.pallas_kernels import fused_dense

            return fused_dense(layout, factors, mode,
                               interpret=interpret)
        return dense_mttkrp(layout, factors, mode)

    if path in ("scatter", "sorted_scatter") or engine == "xla":
        if path == "sorted_scatter" and mode != layout.mode:
            # indices_are_sorted=True on unsorted indices is a
            # correctness-affecting XLA hint, not just a pessimization.
            raise ValueError("sorted_scatter requires the layout's own mode")
        # XLA fuses the gather+Hadamard producers into the scatter-add,
        # so this path has no (nnz, R) HBM intermediate either.  As the
        # `engine == "xla"` terminal-fallback of the blocked paths it is
        # the stream formulation over the layout's arrays: correct for
        # any mode, no kernel or VMEM preconditions.  v2 layouts decode
        # per mode inside the same fusion (mode_ids/_gather_prod_layout).
        sorted_seg = (path == "sorted_scatter"
                      or (path not in ("scatter",) and mode == layout.mode))
        prod = _gather_prod_layout(layout, factors, mode)
        nseg = dim + 1 if mode == layout.mode else dim
        out = jax.ops.segment_sum(prod.astype(_acc_dtype(prod.dtype)),
                                  layout.mode_ids(mode),
                                  num_segments=nseg,
                                  indices_are_sorted=sorted_seg)
        return out[:dim]

    # the resolved engine is a static arg: mttkrp_blocked walks the
    # engine_chain outside the jit, so a runtime demotion retraces with
    # the next engine instead of recompiling the same failing one
    plan = engine

    if path == "privatized":
        width = -(-(dim + 1) // 8) * 8  # +1: room for the sentinel row
        if plan == "fused_t":
            return fused_mttkrp_t(layout, factors, mode, width,
                                  accumulate=True,
                                  interpret=interpret)[:dim]
        if plan == "fused_tg":
            return fused_mttkrp_tg(layout, factors, mode, width,
                                   accumulate=True,
                                   interpret=interpret)[:dim]
        return _scan_fused(layout, factors, mode, width,
                           accumulate=True, target_elems=scan_target,
                           pallas=plan == "unfused_pallas",
                           interpret=interpret)[:dim]

    if path == "sorted_onehot":
        if mode != layout.mode:
            raise ValueError("sorted_onehot requires the layout's own mode")
        S = layout.seg_width
        if plan == "fused_t":
            parts = fused_mttkrp_t(layout, factors, mode, S,
                                   accumulate=False, interpret=interpret)
        elif plan == "fused_tg":
            parts = fused_mttkrp_tg(layout, factors, mode, S,
                                    accumulate=False, interpret=interpret)
        else:
            parts = _scan_fused(layout, factors, mode, S,
                                accumulate=False,
                                target_elems=scan_target,
                                pallas=plan == "unfused_pallas",
                                interpret=interpret)    # (nb, S, R)
        idx = (layout.row_start[:, None] + jnp.arange(S, dtype=jnp.int32)).reshape(-1)
        out = jnp.zeros((dim + S + 1, R), dtype=parts.dtype)
        out = out.at[idx].add(parts.reshape(-1, R))
        return out[:dim]

    raise ValueError(f"unknown path {path!r}")


mttkrp_blocked.clear_cache = _mttkrp_blocked_jit.clear_cache


def _chain_regime(layout: ModeLayout, factors: Sequence[jax.Array],
                  mode: int) -> str:
    """Probe regime of this call — per lane-chunk regime: a Mosaic
    crash in the many-chunk (small-dims) regime must not veto the
    flagship single-chunk production shapes, and vice versa.  Only the
    GATHERED (non-target) factors are lane-chunked, so the target
    mode's dim does not enter the classification."""
    from splatt_tpu.ops.pallas_kernels import probe_regime

    return probe_regime([int(f.shape[0])
                         for k, f in enumerate(factors) if k != mode],
                        layout.block)


def _engine_shape_key(layout: ModeLayout, factors: Sequence[jax.Array],
                      mode: int, regime: Optional[str] = None) -> str:
    """Demotion scope for RESOURCE failures — the same (regime, block)
    granularity the capability probes use, so an OOM at one shape never
    demotes the engine for shapes that fit.  The single owner of the
    key format: demotions recorded at dispatch and the chain pruning in
    engine_plan must agree on it.  `regime` skips recomputation when
    the caller already classified the call.

    The v2 compact encoding is part of the scope (a ``:v2`` suffix;
    v1 keys stay byte-identical to the pre-format-v2 era): an OOM under
    a v2 plan demotes the engine for v2 dispatches only — the v1 path
    keeps its standing, and vice versa."""
    if regime is None:
        regime = _chain_regime(layout, factors, mode)
    key = f"{regime}:b{layout.block}"
    # getattr: gate-probing tests pass partial layout stand-ins
    enc = getattr(layout, "encoding", "v1")
    if enc == "dense":
        # the dense tile scope (docs/dense.md): a dense-engine OOM
        # demotes the engine for dense dispatches only — the sparse
        # path's standing is untouched, and vice versa
        key += ":dn"
    elif enc != "v1":
        key += f":{enc}"
    # layout-balance axes scope their own demotions exactly like :v2
    # (docs/layout-balance.md): an OOM under a balanced/reordered
    # layout never demotes the engine for the default layouts, and
    # vice versa — default-layout keys stay byte-identical to the
    # pre-balance era
    if getattr(layout, "packing", "fixed") != "fixed":
        key += ":bal"
    if getattr(layout, "reorder", "identity") != "identity":
        key += ":ro"
    return key


def _engine_probed_ok(engine: str, regime: str, block: int,
                      interpret: bool) -> bool:
    """Capability gate of one chain candidate, probed LAZILY: each
    probe costs a compile — an engine never reached because an earlier
    one won must not be probed at all, which is why engine_chain defers
    this check to selection/fallback time instead of resolving the
    whole chain eagerly."""
    from splatt_tpu.ops.pallas_kernels import (fused_t_supported,
                                               fused_tg_supported)

    if interpret or engine in ("unfused_pallas", "xla_scan", "xla",
                               "dense_xla"):
        return True
    if engine == "fused_dense":
        from splatt_tpu.ops.pallas_kernels import fused_dense_supported

        return fused_dense_supported(regime, block)
    if engine == "fused_t":
        return fused_t_supported(regime, block)
    if engine == "fused_tg":
        return fused_tg_supported(regime, block)
    return True


def engine_chain(layout: ModeLayout, factors: List[jax.Array], mode: int,
                 path: str = "sorted_onehot", impl: str = "xla",
                 *, shape_key: Optional[str] = None) -> List[str]:
    """The ORDERED engine fallback chain for this call: every engine
    whose cheap gates (VMEM plan, Mosaic's lane-gather limit, runtime
    demotions) pass, best first — fused Pallas (fused_t → fused_tg) →
    unfused Pallas (the chip's engine at real widths) → xla_scan → the
    terminal
    "xla" stream/scatter formulation, which has no preconditions and
    cannot fail to apply.
    Capability probes are NOT consulted here (they cost a compile
    each); :func:`_engine_probed_ok` runs them lazily when an
    engine is actually selected.  :func:`mttkrp_blocked` walks this
    chain at dispatch and again on runtime failure, so one engine's
    failure degrades the run to the next engine instead of killing it.
    """
    from splatt_tpu import resilience
    from splatt_tpu.ops.pallas_kernels import (fused_t_vmem_ok,
                                               fused_tg_vmem_ok, vmem_chunk)

    if path in ("scatter", "sorted_scatter", "stream"):
        return ["xla"]
    if (path == "dense"
            or getattr(layout, "encoding", "v1") == "dense"):
        # the dense tile layout's own chain (docs/dense.md): the MXU
        # kernel when the tile working set fits VMEM, then the
        # dot_general reference — which has no kernel or VMEM
        # preconditions, so the dense chain cannot run dry either
        from splatt_tpu.ops.pallas_kernels import dense_vmem_ok

        if shape_key is None:
            shape_key = _engine_shape_key(layout, factors, mode)
        chain = []
        if (impl in ("pallas", "pallas_interpret")
                and not resilience.is_demoted("fused_dense", shape_key)
                and dense_vmem_ok(layout, factors, mode)):
            chain.append("fused_dense")
        chain.append("dense_xla")
        return chain
    dim = int(factors[mode].shape[0])
    R = int(factors[0].shape[1])
    B = layout.block
    itemsize = jnp.dtype(factors[0].dtype).itemsize
    pallas = impl in ("pallas", "pallas_interpret")
    # the in-kernel gather family (fused_t/fused_tg) gathers factor
    # rows with lane-wise take_along_axis, which Mosaic (jax 0.9.0)
    # lowers only within ONE 128-lane vreg: compiled for a v5e, any
    # wider table is refused ("Multiple source vregs along gather
    # dimension"; tests/test_tpu_compile.py).  Interpret mode has no
    # such limit, so the tests still run their math at any width.
    interp = impl == "pallas_interpret"
    gather = pallas and (interp or all(
        int(f.shape[0]) <= 128 for k, f in enumerate(factors) if k != mode))
    if path == "privatized":
        width = -(-(dim + 1) // 8) * 8
    else:
        width = layout.seg_width
    if shape_key is None:
        shape_key = _engine_shape_key(layout, factors, mode)

    def live(name):
        return not resilience.is_demoted(name, shape_key)

    chain = []
    if gather and live("fused_t") and fused_t_vmem_ok(factors, mode,
                                                      width, B):
        chain.append("fused_t")
    if gather and live("fused_tg") and fused_tg_vmem_ok(factors, mode,
                                                        width, B):
        chain.append("fused_tg")
    if (pallas and live("unfused_pallas")
            and vmem_chunk(width, B, R, itemsize) >= 1):
        chain.append("unfused_pallas")
    if live("xla_scan"):
        chain.append("xla_scan")
    # terminal engine: the stream/scatter formulation — always appended,
    # never demotable out of the chain, so dispatch cannot run dry
    chain.append("xla")
    return chain


def engine_plan(layout: ModeLayout, factors: List[jax.Array], mode: int,
                path: str = "sorted_onehot", impl: str = "xla",
                autotune: Optional[bool] = None) -> str:
    """Which engine :func:`mttkrp_blocked` will actually run for this
    call — the applicable autotuned plan's engine when one is cached,
    else the first :func:`engine_chain` entry whose (lazily probed)
    capability gate passes.  Dispatch falls back silently (VMEM gates,
    Mosaic capability, runtime demotions), so benches and tests use
    this to label results truthfully.
    """
    chain = engine_chain(layout, factors, mode, path, impl)
    regime = _chain_regime(layout, factors, mode)
    interpret = impl == "pallas_interpret"
    tuned = _tuned_plan_for(layout, factors, mode, path, autotune=autotune)
    if tuned is not None and tuned.engine in chain:
        chain = [tuned.engine] + [e for e in chain if e != tuned.engine]
    for engine in chain[:-1]:
        if _engine_probed_ok(engine, regime, layout.block, interpret):
            return engine
    return chain[-1]


class Plan(NamedTuple):
    """One MTTKRP dispatch decision: the resolved engine family
    (`impl`), the algorithm (`path`), and the reduction engine that
    will actually execute (`engine`).  :func:`plan_mttkrp` is the single
    source of this truth — :func:`mttkrp` executes the plan it returns
    and :func:`describe_plan`/benches/tests print the same object, so
    the reported plan cannot desynchronize from what runs."""

    impl: str    # "native" | "pallas" | "pallas_interpret" | "xla"
    path: str    # one of PATHS
    engine: str  # "native" | "fused_t" | "fused_tg" |
                 # "unfused_pallas" | "xla_scan" | "xla"


def _native_runnable(layout: ModeLayout, factors: Sequence[jax.Array],
                     path: Optional[str]) -> bool:
    """Exactly the conditions under which the native C++ engine runs —
    each mirrors a bailout inside :func:`native.mttkrp` or the trace
    check in dispatch, so `plan.engine == "native"` iff it executes."""
    if path is not None:
        return False  # explicit path = the caller wants that jit engine
    if any(isinstance(U, jax.core.Tracer) for U in factors):
        return False  # inside a jit trace (e.g. the fused sweep)
    if layout.encoding != "v1":
        return False  # the C++ ABI reads contiguous global i32 indices
    if getattr(layout, "block_nnz", None) is not None:
        # balanced packing pads mid-stream: the native engine reads the
        # first `nnz` positions as the real prefix, which no longer
        # holds (docs/layout-balance.md) — the XLA paths decode pads as
        # additive identities instead
        return False
    vdt = layout.vals.dtype
    if vdt not in (jnp.float32, jnp.float64):  # splint: ignore[SPL005] native-engine f32/f64 ABI gate
        return False
    if any(f.dtype != vdt for f in factors):
        return False  # mixed dtypes: the XLA paths own promotion
    if layout.nmodes > 8:
        return False
    return native_available()


def _resolve_dispatch(X: "BlockedSparse", factors: Sequence[jax.Array],
                      mode: int, path: Optional[str],
                      impl: Optional[str]) -> tuple:
    """Resolve (impl, path) — the part of the dispatch decision
    :func:`mttkrp` needs to execute.  The engine-within-impl choice is
    made by engine_plan inside mttkrp_blocked; plan_mttkrp surfaces it
    for reporting without making the hot path compute it twice."""
    if impl is None:
        impl = choose_impl(X.opts)
    if impl == "native":
        if _native_runnable(X.layout_for(mode), factors, path):
            return "native", path or _choose_path_bs(X, mode)
        impl = "xla"
    if path is None:
        path = _choose_path_bs(X, mode)
    return impl, path


def plan_mttkrp(X: "BlockedSparse", factors: Sequence[jax.Array], mode: int,
                path: Optional[str] = None,
                impl: Optional[str] = None) -> Plan:
    """Compute the dispatch decision :func:`mttkrp` will execute for
    this call (≙ mttkrp_csf dispatch, src/mttkrp.c:1287-1341 — but
    reified as a value so benches/CLI/tests can consume the same
    decision instead of hand-mirroring the conditions)."""
    impl, path = _resolve_dispatch(X, factors, mode, path, impl)
    if impl == "native":
        return Plan("native", path, "native")
    return Plan(impl, path,
                engine_plan(X.layout_for(mode), factors, mode, path, impl,
                            autotune=X.opts.autotune))


def describe_plan(X: "BlockedSparse", factors: List[jax.Array]) -> str:
    """One-line human-readable dispatch plan for a CPD run over `X` —
    which impl (native/pallas/xla) and, per mode, which path/engine
    mttkrp() will actually execute.  Dispatch falls back silently (VMEM
    gates, Mosaic capability probes), so the CLI prints this at
    Verbosity.LOW to make the chosen engine observable
    (≙ the reference's CSF/tile report lines, src/stats.c:226-296).
    Built from the same :func:`plan_mttkrp` objects dispatch executes.
    """
    impl = choose_impl(X.opts)
    parts = []
    for m in range(X.nmodes):
        plan = plan_mttkrp(X, factors, m)
        parts.append(f"mode{m}={plan.path}/{plan.engine}")
    note = ""
    from splatt_tpu.ops.pallas_kernels import PROBE_STATES

    unproven = {k: v for k, v in PROBE_STATES.items()
                if v in ("timeout", "infra")}
    if unproven:
        labels = [f"{k} {'timed out' if v == 'timeout' else 'service error'}"
                  for k, v in sorted(unproven.items())]
        note = f" [probe {'; '.join(labels)}: unproven, not rejected]"
    from splatt_tpu import resilience

    demoted = resilience.demotions()
    if demoted:
        labels = [d.engine + (f"@{d.shape_key}" if d.shape_key else "")
                  for d in demoted]
        note += f" [demoted this run: {', '.join(sorted(set(labels)))}]"
    return f"engine plan: impl={impl} " + " ".join(parts) + note


def _onehot_pays(opts: Options) -> bool:
    """Whether the one-hot contraction paths are worth choosing.

    The redundant MACs are only free where a matrix unit executes them
    (measured: sorted_scatter ≈ 2x faster than the one-hot on CPU at
    2M nnz).  ``use_pallas=True`` forces them on any backend (mirrors
    choose_impl's force semantics — tests rely on it).
    """
    return opts.use_pallas is True or jax.default_backend() == "tpu"


def choose_path(layout: ModeLayout, mode: int, opts: Options) -> str:
    """Static path selection (≙ mttkrp_csf dispatch + p_is_privatized)."""
    if getattr(layout, "encoding", "v1") == "dense":
        return "dense"
    if mode == layout.mode:
        if layout.seg_width <= opts.onehot_cap and _onehot_pays(opts):
            return "sorted_onehot"
        return "sorted_scatter"
    return "scatter"


def _choose_path_bs(bs: BlockedSparse, mode: int) -> str:
    layout = bs.layout_for(mode)
    if getattr(layout, "encoding", "v1") == "dense":
        # the hybrid per-mode dispatcher (docs/dense.md): a mode whose
        # compiled layout is dense tiles runs the dense matmul path;
        # every other mode keeps its sparse-blocked path
        return "dense"
    dim = bs.dims[mode]
    if mode != layout.mode:
        if (_onehot_pays(bs.opts)
                and dim + 16 <= bs.opts.priv_cap
                and dim <= bs.opts.priv_threshold * max(bs.nnz, 1)):
            return "privatized"
        return "scatter"
    return choose_path(layout, mode, bs.opts)


def native_available() -> bool:
    """Whether the native C++ MTTKRP engine can run here."""
    from splatt_tpu import native

    return native.available()


def choose_impl(opts: Options) -> str:
    """Pick the MTTKRP engine: Pallas on TPU (or when forced), the
    native C++ host kernel on CPU when the library is available,
    scanned-XLA otherwise; forcing Pallas off-TPU uses interpret mode.
    ``use_pallas=False`` forces pure-XLA (the differential tests' way to
    pin the jit engines)."""
    backend = jax.default_backend()
    if opts.use_pallas is None:
        if backend == "tpu":
            return "pallas"
        return "native" if native_available() else "xla"
    if not opts.use_pallas:
        return "xla"
    return "pallas" if backend == "tpu" else "pallas_interpret"


def mttkrp(X: Union[SparseTensor, BlockedSparse], factors: List[jax.Array],
           mode: int, path: Optional[str] = None,
           impl: Optional[str] = None) -> jax.Array:
    """Public MTTKRP (≙ splatt_mttkrp, include/splatt/api_kernels.h:98-119).

    Accepts a host COO tensor (oracle path) or a compiled BlockedSparse.
    `path` forces a specific execution path and `impl` a reduction
    engine (tests sweep both).
    """
    if isinstance(X, SparseTensor):
        if path is not None and path != "stream":
            raise ValueError(
                f"path={path!r} requires a BlockedSparse input; a COO "
                f"SparseTensor only supports the stream path")
        inds = jnp.asarray(X.inds)
        vals = jnp.asarray(X.vals)
        return mttkrp_stream(inds, vals, factors, mode, X.dims[mode])
    rimpl, rpath = _resolve_dispatch(X, factors, mode, path, impl)
    layout = X.layout_for(mode)
    if rimpl == "native":
        out = _run_native(layout, factors, mode)
        if out is not None:
            return out
        # the shared library failed at call time (not a planned
        # condition — e.g. deleted mid-session); degrade to XLA
        rimpl = "xla"
    return mttkrp_blocked(layout, factors, mode, path=rpath, impl=rimpl,
                          fallback=X.opts.engine_fallback,
                          autotune=X.opts.autotune)


def _run_native(layout: ModeLayout, factors: List[jax.Array],
                mode: int) -> Optional[jax.Array]:
    """Execute the native C++ host engine for a planned "native" call.
    Runnability was decided by :func:`_native_runnable`; native.mttkrp
    still re-validates defensively and returns None on surprise."""
    from splatt_tpu import native

    dims = [int(f.shape[0]) for f in factors]
    out = native.mttkrp(
        np.asarray(layout.inds), np.asarray(layout.vals),
        [np.asarray(U) for U in factors], mode, dims,
        sorted_by_mode=(mode == layout.mode), nnz=layout.nnz)
    if out is None:
        return None
    return jnp.asarray(out)
