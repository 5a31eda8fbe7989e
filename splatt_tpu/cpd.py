"""CPD-ALS driver (≙ src/cpd.c: splatt_cpd_als / cpd_als_iterate).

One ALS sweep (all modes) is a single jitted function; the convergence
loop runs on host (data-dependent stopping is host logic, exactly the
split XLA wants).  Per-sweep semantics mirror the reference
(src/cpd.c:271-387):

  for each mode m:  M ← MTTKRP(X, U, m); U_m ← solve(⊛_{k≠m} Gram_k + ρI, M);
                    (U_m, λ) ← normalize (2-norm on iteration 0, max-norm
                    after — src/cpd.c:343-347); Gram_m ← U_mᵀU_m
  fit = 1 − √(⟨X,X⟩ + ⟨Z,Z⟩ − 2⟨X,Z⟩)/√⟨X,X⟩, with ⟨Z,Z⟩ = λᵀ(⊛ Grams)λ
  (p_kruskal_norm, src/cpd.c:116-152) and ⟨X,Z⟩ from the last mode's
  MTTKRP result (p_tt_kruskal_inner, src/cpd.c:171-218).
  converge when |fit − fit_prev| < tolerance (src/cpd.c:368-370).

Post-processing renormalizes every factor into λ (cpd_post_process,
src/cpd.c:391-411).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from splatt_tpu import trace
from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import (Options, Verbosity, acc_dtype, default_opts,
                               resolve_dtype)
from splatt_tpu.coo import SparseTensor
from splatt_tpu.kruskal import KruskalTensor, post_process
from splatt_tpu.ops.linalg import (form_normal_lhs, gram, normalize_columns,
                                   solve_normals)
from splatt_tpu.ops.mttkrp import mttkrp, mttkrp_stream
from splatt_tpu.utils.timers import timers


def init_factors(dims: Tuple[int, ...], rank: int, seed: int,
                 dtype=jnp.float32) -> List[jax.Array]:  # splint: ignore[SPL005] init_factors signature default; cpd_als resolves through config.resolve_dtype
    """Seed-stable random factor init (≙ mat_rand; per-mode fold_in keeps
    initialization independent of device layout, ≙ mpi_mat_rand's
    rank-count invariance, src/splatt_mpi.h:368-386)."""
    key = jax.random.PRNGKey(seed)
    out = []
    for m, d in enumerate(dims):
        out.append(jax.random.uniform(jax.random.fold_in(key, m), (d, rank),
                                      dtype=dtype))
    return out


def _mttkrp_closure(X: Union[SparseTensor, BlockedSparse]) -> Callable:
    """The per-tensor MTTKRP callable both sweep builders share."""
    if isinstance(X, SparseTensor):
        inds = jnp.asarray(X.inds)
        vals = jnp.asarray(X.vals)
        dims = X.dims

        def do_mttkrp(factors, m):
            return mttkrp_stream(inds, vals, factors, m, dims[m])
    else:
        def do_mttkrp(factors, m):
            return mttkrp(X, factors, m)
    return do_mttkrp


def _zz_inner(lam, grams, M, U_last):
    """⟨Z,Z⟩ = λᵀ(⊛ Grams)λ and ⟨X,Z⟩ from the last-mode MTTKRP result
    (p_kruskal_norm / p_tt_kruskal_inner, src/cpd.c:116-218) — shared by
    both sweep builders."""
    acc = acc_dtype(M.dtype)
    had = jnp.outer(lam, lam)
    for g in grams:
        had = had * g
    # <X,Z> as ONE pinned contraction: under bf16 factors, M (the wide
    # MTTKRP accumulator) times U_last (narrow) would materialize a
    # wide (dim, R) product ahead of the reduce — doubled hot-loop
    # bytes (SPL028) and an unpinned accumulation (SPL024)
    inner = jnp.einsum("dr,dr,r->", M, U_last, lam,
                       preferred_element_type=acc)
    return jnp.sum(had, dtype=acc), inner


def _make_sweep(X: Union[SparseTensor, BlockedSparse], nmodes: int,
                reg: float, donate: bool = False) -> Callable:
    """Build the jitted one-sweep function for this tensor.

    With `donate`, the factor/gram arguments are donated
    (``donate_argnums``): XLA aliases the output factor/gram buffers
    onto the inputs, so a sweep updates state in place instead of
    round-tripping a copy of every factor per iteration — dispatch
    overhead the autotuner would otherwise mis-attribute to the
    engines it measures.  A donated sweep CONSUMES its inputs: callers
    must not reuse the arrays they passed in (cpd_als re-materializes
    from its host snapshot on an engine rescue).
    """
    do_mttkrp = _mttkrp_closure(X)

    def sweep(factors, grams, first: bool):
        lam = None
        M = None
        for m in range(nmodes):
            factor_dtype = factors[m].dtype
            M = do_mttkrp(factors, m)
            lhs = form_normal_lhs(grams, m, reg)
            U = solve_normals(lhs, M)
            U, lam = normalize_columns(U, "2" if first else "max")
            # mixed precision: factors stay in their (possibly bf16)
            # storage dtype; MTTKRP/Gram/solve accumulated in f32 above
            factors[m] = U.astype(factor_dtype)
            grams[m] = gram(factors[m])
        znormsq, inner = _zz_inner(lam, grams, M, factors[nmodes - 1])
        return factors, grams, lam, znormsq, inner

    return jax.jit(sweep, static_argnames=("first",),
                   donate_argnums=(0, 1) if donate else ())


def _make_phased_sweep(X: Union[SparseTensor, BlockedSparse], nmodes: int,
                       reg: float, donate: bool = False) -> Callable:
    """Same contract as :func:`_make_sweep`, but each ALS phase is its
    own small jitted program (per-mode MTTKRP, one solve+normalize+gram
    update, one fit) chained asynchronously — no host syncs, so timing
    behaves like the fused sweep.

    The TPU default.  The fused whole-sweep program compiles too (for a
    v5e at NELL-2 scale in ~11 s ahead of time, PR 21); which of the two
    runs faster on the chip is not measured yet.  Dispatch overhead
    between phases is host-side microseconds against 100 ms-scale
    kernels.

    With `donate`, every phase but the last donates its MTTKRP result
    `M` — the (dim, R) buffer the solve consumes and the updated factor
    aliases onto, so the per-phase factor update stops allocating and
    copying a fresh buffer.  The grams stay undonated (every phase
    reads the full gram list) and the LAST phase keeps its M live: the
    fit phase still needs it.  That is why the last mode is updated
    OUTSIDE the donating loop rather than by a conditional wrapper
    pick inside it — the donated M is then never live at the fit read,
    a property splint's SPL008 dataflow verifies statically instead of
    jax discovering a deleted buffer at runtime.
    """
    do_mttkrp = _mttkrp_closure(X)

    def update(grams, M, m: int, first: bool, factor_dtype):
        U = solve_normals(form_normal_lhs(grams, m, reg), M)
        U, lam = normalize_columns(U, "2" if first else "max")
        U = U.astype(factor_dtype)
        return U, lam, gram(U)

    statics = ("m", "first", "factor_dtype")
    update_mid = jax.jit(update, static_argnames=statics,
                         donate_argnums=(1,) if donate else ())
    update_last = jax.jit(update, static_argnames=statics)

    fit_phase = jax.jit(_zz_inner)
    last = nmodes - 1

    def sweep(factors, grams, first: bool):
        # contract parity with the jitted _make_sweep: never mutate the
        # caller's lists (bench reuses one factor list across paths)
        factors = list(factors)
        grams = list(grams)
        lam = None
        for m in range(last):
            M = do_mttkrp(factors, m)
            factors[m], lam, grams[m] = update_mid(
                grams, M, m, first, factors[m].dtype)
        M = do_mttkrp(factors, last)
        factors[last], lam, grams[last] = update_last(
            grams, M, last, first, factors[last].dtype)
        znormsq, inner = fit_phase(lam, grams, M, factors[last])
        return factors, grams, lam, znormsq, inner

    return sweep


def _make_profiled_sweep(X: Union[SparseTensor, BlockedSparse], nmodes: int,
                         reg: float) -> Callable:
    """Split-jit sweep for `-v -v`: each ALS phase is its own jitted
    call bracketed by blocking timers, so mttkrp/solve/normalize/gram/
    fit wall-clock is attributed truthfully (≙ the reference bracketing
    TIMER_MTTKRP / TIMER_INV / TIMER_FIT around each call,
    src/cpd.c:318-352).  Costs cross-phase fusion — use the fused
    :func:`_make_sweep` when not profiling.
    """
    do_mttkrp = _mttkrp_closure(X)

    @partial(jax.jit, static_argnames=("m",))
    def solve_phase(grams, M, m: int):
        return solve_normals(form_normal_lhs(grams, m, reg), M)

    @partial(jax.jit, static_argnames=("first",))
    def normalize_phase(U, first: bool):
        return normalize_columns(U, "2" if first else "max")

    gram_phase = jax.jit(gram)
    fit_phase = jax.jit(_zz_inner)

    from splatt_tpu.utils.env import host_fence as sync

    def sweep(factors, grams, first: bool):
        lam = None
        M = None
        for m in range(nmodes):
            factor_dtype = factors[m].dtype
            # per-mode timers at level 3: the CLI prints them in its own
            # per-mode block, keeping them out of the level-2 report
            timers.get(f"mttkrp_mode{m}", level=3)
            with timers.time("mttkrp"), timers.time(f"mttkrp_mode{m}"):
                M = sync(do_mttkrp(factors, m))
            with timers.time("solve"):
                U = sync(solve_phase(grams, M, m))
            with timers.time("normalize"):
                U, lam = sync(normalize_phase(U, first))
            factors[m] = U.astype(factor_dtype)
            with timers.time("gram"):
                grams[m] = sync(gram_phase(factors[m]))
        with timers.time("fit"):
            znormsq, inner = sync(
                fit_phase(lam, grams, M, factors[nmodes - 1]))
        return factors, grams, lam, znormsq, inner

    return sweep


def _try_engine_rescue(X, opts: Options, err: Exception) -> bool:
    """Whether a failed sweep should be rebuilt and retried: demotes
    the engine implicated in `err` (the dispatch layer notes each
    attempt because accelerator failures can surface asynchronously,
    with no call-site context).  False — re-raise — when fallback is
    off, the input has no engine chain (COO oracle), the terminal
    engine itself failed, no NEW engine was attempted since the last
    demotion (retrying would livelock), or the error does not LOOK like
    an accelerator/engine failure at all (UNKNOWN class): a LinAlgError
    from the solve or a user shape bug must surface, not burn sweep
    recompiles demoting healthy engines one by one.  (Synchronous
    engine failures of any class are already handled one level down,
    inside mttkrp_blocked's chain walk.)"""
    from splatt_tpu import resilience

    if not isinstance(X, BlockedSparse):
        return False
    enabled = (opts.engine_fallback if opts.engine_fallback is not None
               else resilience.fallback_enabled())
    if not enabled:
        return False
    if resilience.classify_failure(err) in (
            resilience.FailureClass.UNKNOWN,
            resilience.FailureClass.NUMERICAL):
        # UNKNOWN: a LinAlgError or user shape bug must surface;
        # NUMERICAL: non-finite outputs are the sentinel's to roll
        # back, not evidence against the engine that computed them
        return False
    attempt = resilience.last_engine_attempt()
    if attempt is None:
        return False
    engine, shape_key = attempt
    if engine == "xla" or resilience.is_demoted(engine, shape_key):
        return False
    resilience.demote_engine(engine, err, shape_key=shape_key)
    if opts.verbosity >= Verbosity.LOW:
        print(f"  engine {engine} failed at runtime "
              f"({type(err).__name__}); falling back to the next engine "
              f"in the chain")
    return True


def _fit(xnormsq: float, znormsq: jax.Array, inner: jax.Array) -> jax.Array:
    residual = jnp.sqrt(jnp.maximum(xnormsq + znormsq - 2.0 * inner, 0.0))
    return 1.0 - residual / np.sqrt(xnormsq)


# -- numerical-health sentinel (docs/guarded-als.md) ------------------------

@jax.jit
def _health_pack(factors, lam, fit):
    """Fold the sentinel's finite-check reduction into ONE small device
    array: ``[fit, isfinite(U_0), ..., isfinite(U_{n-1}), isfinite(λ),
    isfinite(fit)]`` (flags are 1.0/0.0 in fit's dtype).  The drivers
    fetch this at the fit-check host sync they already pay for, so the
    sentinel adds no extra device round-trip."""
    flags = [jnp.isfinite(U).all() for U in factors]
    flags.append(jnp.isfinite(lam).all())
    flags.append(jnp.isfinite(fit))
    fit = jnp.asarray(fit)
    return jnp.concatenate([fit.reshape(1),
                            jnp.stack(flags).astype(fit.dtype)])


def _health_verdict(vec: np.ndarray, nmodes: int):
    """(fitval, offending-mode list, healthy) from a fetched
    :func:`_health_pack` vector.  `offending` lists factor modes whose
    isfinite flag tripped; λ/fit-only blowups report an empty list (the
    rollback then bumps regularization without re-randomizing)."""
    fitval = float(vec[0])
    flags = np.asarray(vec[1:]) > 0.5
    offending = [m for m in range(nmodes) if not flags[m]]
    healthy = bool(flags.all())
    return fitval, offending, healthy


def health_retries() -> int:
    """The sentinel's rollback budget: the active resilience scope's
    per-job override when one is set (serve gives each tenant its own
    budget — docs/serve.md), else SPLATT_HEALTH_RETRIES.  How many
    times a run may roll back to the last-good snapshot before it
    degrades to checkpoint-and-abort.  0 disables the sentinel (and its
    snapshot upkeep) entirely."""
    from splatt_tpu import resilience
    from splatt_tpu.utils.env import read_env_int

    scoped = resilience.scope_health_retries()
    if scoped is not None:
        return int(scoped)
    v = read_env_int("SPLATT_HEALTH_RETRIES")
    return int(v) if v is not None else 0


#: checkpoint schema: v1 = the original field set (no integrity data);
#: v2 adds `schema` and a sha256 `checksum` over every payload field,
#: so a torn/corrupt checkpoint is DETECTED at load instead of
#: resuming from silently wrong factors.
_CKPT_SCHEMA = 2


class CheckpointError(RuntimeError):
    """A checkpoint file is unreadable, truncated, or fails its
    integrity checksum — distinct from a dims/rank MISMATCH (which is a
    caller error and stays a ValueError)."""


def _checkpoint_digest(payload: dict) -> str:
    """sha256 over every payload field in canonical (sorted-key) order,
    covering dtype + shape + bytes so a flipped bit anywhere fails."""
    import hashlib

    h = hashlib.sha256()
    for k in sorted(payload):
        a = np.asarray(payload[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _save_checkpoint(path: str, factors, lam, it: int, fit: float,
                     reorder: str = "identity") -> None:
    """Atomic .npz checkpoint (write + rename) with integrity data.

    The previous generation is kept as `<path>.bak` before the rename:
    if this write is torn (power loss mid-replace is atomic, but a torn
    write through a dying NFS mount is not) the resilient loader falls
    back one generation instead of losing the run.

    `reorder` stamps the row-label space the factors live in
    (docs/layout-balance.md): a reordered run checkpoints RELABELED
    factors, and a resume under a different resolved recipe must not
    silently mix row spaces — the loader refuses on mismatch.
    """
    import os

    from splatt_tpu.utils import faults

    with trace.span("cpd.checkpoint", path=path, it=int(it)):
        faults.maybe_fail("checkpoint_write")
        tmp = path + ".tmp.npz"
        payload = {f"factor{m}": np.asarray(U)
                   for m, U in enumerate(factors)}
        payload.update(nmodes=len(factors), it=it, fit=fit,
                       lam=np.asarray(lam),
                       dims=np.asarray([U.shape[0] for U in factors]),
                       rank=int(factors[0].shape[1]))
        digest = _checkpoint_digest(payload)
        np.savez(tmp, schema=_CKPT_SCHEMA, checksum=digest,
                 reorder=np.str_(reorder), **payload)
        if faults.consume("checkpoint_torn"):
            # injected torn write: drop the tail of the bytes just
            # written, as a crashed writer or dying mount would
            size = os.path.getsize(tmp)
            with open(tmp, "r+b") as f:
                f.truncate(max(size // 2, 1))
        from splatt_tpu.utils.durable import publish_file

        if os.path.exists(path):
            os.replace(path, path + ".bak")
        # fsync + atomic rename through the sanctioned durable-write
        # helper (SPL016) — the .bak shuffle above moves an EXISTING
        # file and needs no durability protocol of its own
        publish_file(tmp, path)


def load_checkpoint(path: str, verify: bool = True,
                    expect_reorder: Optional[str] = None):
    """Load a mid-run ALS checkpoint → (factors, lam, it, fit).

    Schema-v2 checkpoints are checksum-verified (`verify=False` skips);
    v1 files (no integrity fields) still load.  Any unreadable,
    truncated, or checksum-failing file raises :class:`CheckpointError`
    — use :func:`load_checkpoint_resilient` on resume paths, which
    degrades to the `.bak` generation instead of dying mid-resume.

    `expect_reorder` guards the row-label space: when given, a file
    stamped with a DIFFERENT reorder recipe (files predating the stamp
    count as "identity") raises :class:`CheckpointError` — resuming
    relabeled factors under another recipe would silently permute
    every factor against the tensor (docs/layout-balance.md).
    """
    try:
        with np.load(path) as z:
            nmodes = int(z["nmodes"])
            factors_np = [np.asarray(z[f"factor{m}"])
                          for m in range(nmodes)]
            lam = np.asarray(z["lam"])
            it = int(z["it"])
            fit = float(z["fit"])
            dims = np.asarray(z["dims"])
            rank = int(z["rank"])
            stored = str(z["checksum"]) if "checksum" in z.files else None
            ck_reorder = (str(z["reorder"]) if "reorder" in z.files
                          else "identity")
        if expect_reorder is not None and ck_reorder != expect_reorder:
            raise CheckpointError(
                f"checkpoint {path} stores factors in "
                f"reorder={ck_reorder!r} row space but this run "
                f"resolved reorder={expect_reorder!r}; resuming would "
                f"mix row labelings (pass resume=False to overwrite)")
        if verify and stored is not None:
            payload = {f"factor{m}": factors_np[m] for m in range(nmodes)}
            payload.update(nmodes=nmodes, it=it, fit=fit, lam=lam,
                           dims=dims, rank=rank)
            if _checkpoint_digest(payload) != stored:
                raise CheckpointError(
                    f"checkpoint {path} failed its integrity checksum "
                    f"(torn write or on-disk corruption)")
        return ([jnp.asarray(f) for f in factors_np], jnp.asarray(lam),
                it, fit)
    except CheckpointError:
        raise
    except Exception as e:
        raise CheckpointError(
            f"checkpoint {path} is unreadable "
            f"({type(e).__name__}: {e})") from e


def load_checkpoint_resilient(path: str,
                              expect_reorder: Optional[str] = None):
    """Resume-path checkpoint load: try `path`, fall back to the
    previous `.bak` generation on corruption, and return None (start
    fresh) when neither is usable — a corrupt checkpoint must degrade
    the resume, not kill it.  A reorder row-space mismatch
    (`expect_reorder`, docs/layout-balance.md) degrades the same way:
    losing the checkpointed iterations beats silently resuming
    factors whose rows are permuted against the tensor.  Recoveries
    are logged to stderr and recorded in the resilience run report."""
    import os
    import sys

    from splatt_tpu import resilience

    try:
        return load_checkpoint(path, expect_reorder=expect_reorder)
    except CheckpointError as e:
        first_err = str(e)
    bak = path + ".bak"
    if os.path.exists(bak):
        try:
            out = load_checkpoint(bak, expect_reorder=expect_reorder)
            resilience.run_report().add(
                "checkpoint_recovery", path=path, error=first_err,
                action=f"resumed from previous generation {bak}")
            print(f"splatt-tpu: WARNING: {first_err}; resumed from the "
                  f"previous generation {bak}", file=sys.stderr, flush=True)
            return out
        except CheckpointError as e2:
            first_err = f"{first_err}; .bak also unusable ({e2})"
    resilience.run_report().add(
        "checkpoint_recovery", path=path, error=first_err,
        action="no usable generation; starting fresh")
    print(f"splatt-tpu: WARNING: {first_err}; no usable checkpoint "
          f"generation — starting from scratch", file=sys.stderr, flush=True)
    return None


def factor_content_sha(factors, lam) -> str:
    """Content sha over the factor matrices + weights alone — the
    identity a model-generation stamp records (docs/predict.md).
    Deliberately narrower than the checkpoint checksum: two commits of
    bit-identical factors get the SAME sha regardless of iteration
    count or fit, which is what makes a re-commit idempotent at the
    generation fence."""
    payload = {f"factor{m}": np.asarray(U) for m, U in enumerate(factors)}
    payload["lam"] = np.asarray(lam)
    return _checkpoint_digest(payload)


def load_checkpoint_resilient_gen(path: str, stamp: Optional[dict],
                                  bak_stamp: Optional[dict] = None,
                                  expect_reorder: Optional[str] = None):
    """The generation-aware variant of :func:`load_checkpoint_resilient`
    (docs/predict.md): load the newest checkpoint generation whose
    factor CONTENT verifies against a generation stamp, or refuse.

    `stamp` / `bak_stamp` are the parsed current / previous generation
    stamps (``{"gen": int, "sha": str}``, read by predict.py).  Pairs
    are tried newest-first — (path, stamp), then (path.bak, stamp) for
    the commit that advanced the checkpoint but died before the stamp,
    then (path.bak, bak_stamp) — and every torn/mismatched pair
    degrades with a classified ``model_torn`` event.  Returns
    ``(factors, lam, it, fit, gen, sha)`` for the first intact pair,
    or None when nothing survives the fence: a reader must REFUSE
    rather than serve stale-or-torn factors, so a checkpoint with no
    verifying stamp is not servable."""
    import os

    from splatt_tpu import resilience

    candidates = []
    if stamp is not None:
        candidates.append((path, stamp))
        candidates.append((path + ".bak", stamp))
    if bak_stamp is not None:
        candidates.append((path + ".bak", bak_stamp))
    for cpath, cstamp in candidates:
        want = str(cstamp.get("sha") or "")
        try:
            gen = int(cstamp["gen"])
        except (KeyError, TypeError, ValueError):
            continue
        if not want or not os.path.exists(cpath):
            continue
        try:
            factors, lam, it, fit = load_checkpoint(
                cpath, expect_reorder=expect_reorder)
            got = factor_content_sha(factors, lam)
            if got != want:
                raise CheckpointError(
                    f"checkpoint {cpath} factor content {got[:12]} does "
                    f"not match generation {gen} stamp {want[:12]} "
                    f"(torn commit or stale stamp)")
            return factors, lam, it, fit, gen, want
        except CheckpointError as e:
            resilience.run_report().add(
                "model_torn", path=cpath, piece="checkpoint-vs-stamp",
                gen=gen,
                failure_class=resilience.classify_failure(e).value,
                error=str(e)[:200])
    return None


def cpd_als(X: Union[SparseTensor, BlockedSparse], rank: int,
            opts: Optional[Options] = None,
            init: Optional[List[jax.Array]] = None,
            checkpoint_path: Optional[str] = None,
            checkpoint_every: int = 10,
            resume: bool = True,
            stop: Optional[Callable[[], bool]] = None) -> KruskalTensor:
    """Compute a rank-`rank` CPD of X (≙ splatt_cpd_als, src/cpd.c:22-63).

    Checkpoint/resume (beyond the reference, which only writes terminal
    outputs): with `checkpoint_path`, factors are written atomically
    every `checkpoint_every` iterations, and an existing checkpoint is
    resumed from (pass resume=False to overwrite).  ALS is
    self-correcting, so restarting from checkpointed factors continues
    the same optimization.

    `stop` is a cooperative interruption hook, polled at fit-check
    iterations (host syncs already happen there): when it returns True
    the run checkpoints the just-committed state (if `checkpoint_path`
    is set) and returns early — the serve daemon's graceful drain
    (docs/serve.md) hands this a "draining?" probe so a SIGTERM
    checkpoints running jobs instead of abandoning or outliving them.
    """
    opts = (opts or default_opts()).validate()
    # structured tracing (docs/observability.md): Options.trace pins
    # recording on/off for this run (None defers to the process/env
    # default), and every span below nests under the cpd.als root —
    # the tree the Chrome-trace exporter and `splatt trace` summarize
    with trace.enabling(opts.trace):
        with trace.span("cpd.als", rank=int(rank),
                        donate=opts.donate_sweep,
                        max_iterations=int(opts.max_iterations)):
            return _cpd_als_traced(X, rank, opts, init, checkpoint_path,
                                   checkpoint_every, resume, stop)


def _cpd_als_traced(X: Union[SparseTensor, BlockedSparse], rank: int,
                    opts: Options, init, checkpoint_path,
                    checkpoint_every: int, resume: bool,
                    stop) -> KruskalTensor:
    """:func:`cpd_als` body, running inside the ``cpd.als`` root span
    (and the run's tracing override) the public wrapper opened."""
    if isinstance(X, SparseTensor):
        dims, nmodes = X.dims, X.nmodes
        xnormsq = X.normsq()
        dtype = resolve_dtype(opts, X.vals.dtype)
    else:
        dims, nmodes = X.dims, X.nmodes
        xnormsq = X.frobsq()
        dtype = X.layouts[0].vals.dtype
    # a reordered BlockedSparse (docs/layout-balance.md) computes in
    # RELABELED row space: caller-supplied init moves in through the
    # permutation here, and the output factors move back out below.
    # Checkpoints stay in relabeled space (the recipe is deterministic,
    # so a resume under the same plan sees consistent labels) — only
    # the caller-visible boundary translates.
    reorder_perm = getattr(X, "perm", None)
    reorder_label = (getattr(X, "reorder", "identity")
                     if reorder_perm is not None else "identity")
    if reorder_perm is not None and init is not None:
        init = [reorder_perm.permute_factor(U, m)
                for m, U in enumerate(init)]

    start_it = 0
    ck_lam = None
    ck_fit = 0.0
    if checkpoint_path is not None and checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}")
    if checkpoint_path is not None and resume:
        import os

        # .bak counts as an existing checkpoint: a crash between the
        # writer's two renames can leave ONLY the previous generation
        # on disk, and that progress must still be resumed
        if (os.path.exists(checkpoint_path)
                or os.path.exists(checkpoint_path + ".bak")):
            # resilient load: a corrupt/truncated file degrades to the
            # previous .bak generation, or to a fresh start — never a
            # crash mid-resume
            loaded = load_checkpoint_resilient(
                checkpoint_path, expect_reorder=reorder_label)
            if loaded is not None:
                ck_factors, ck_lam, start_it, ck_fit = loaded
                ck_dims = tuple(int(U.shape[0]) for U in ck_factors)
                ck_rank = int(ck_factors[0].shape[1])
                if ck_dims != tuple(dims) or ck_rank != rank:
                    raise ValueError(
                        f"checkpoint {checkpoint_path} is for "
                        f"dims={ck_dims} rank={ck_rank}, not "
                        f"dims={tuple(dims)} rank={rank}; "
                        f"pass resume=False to overwrite it")
                init = ck_factors
                if opts.verbosity >= Verbosity.LOW:
                    print(f"  resuming from {checkpoint_path} "
                          f"(iteration {start_it})")

    donate = opts.donate_sweep if opts.donate_sweep is not None else True
    if init is not None:
        # a PRIVATE copy even when dtypes already match: the donated
        # sweep consumes its inputs, and the caller's init arrays (often
        # reused across runs — the differential tests do) must survive
        factors = [jnp.array(f, dtype=dtype, copy=True) for f in init]
    else:
        factors = init_factors(dims, rank, opts.seed(), dtype=dtype)
    grams = [gram(U) for U in factors]

    if opts.verbosity >= Verbosity.LOW:
        if isinstance(X, BlockedSparse):
            from splatt_tpu.ops.mttkrp import describe_plan

            print(f"  {describe_plan(X, factors)}")
        else:
            print("  engine plan: impl=xla mode*=stream (COO oracle)")

    # surface the autotuned dispatch plan (docs/autotune.md) in the run
    # report: silent tuning would be as unobservable as the silent
    # engine fallback the resilience layer exists to report
    from splatt_tpu import resilience as _resilience
    from splatt_tpu import tune as _tune

    if isinstance(X, BlockedSparse) and _tune.autotune_enabled(opts.autotune):
        # report through the SAME applicability filter dispatch uses
        # (_tuned_plan_for: path/block match, demotion-checked) — a plan
        # the dispatch will reject must not be claimed as in effect
        from splatt_tpu.ops.mttkrp import _choose_path_bs, _tuned_plan_for

        tuned_plans = {}
        for m in range(nmodes):
            lay = X.layout_for(m)
            plan = _tuned_plan_for(lay, factors, m,
                                   _choose_path_bs(X, m),
                                   autotune=opts.autotune)
            if plan is not None:
                tuned_plans[m] = dict(
                    dataclasses.asdict(plan),
                    mode_density=getattr(lay, "density_bucket", ""))
        if tuned_plans:
            _resilience.run_report().add("tuned_plan", plans=tuned_plans)
            if opts.verbosity >= Verbosity.LOW:
                parts = [f"mode{m}={p['path']}/{p['engine']}"
                         f" b{p['nnz_block']} s{p['scan_target']}"
                         f" {p['idx_width']}/{p['val_storage']}"
                         f" {p['packing']}/{p['reorder']}"
                         for m, p in sorted(tuned_plans.items())]
                print("  tuned plan: " + " ".join(parts))

    # -v -v: split-jit profiled sweep with real per-phase attribution.
    # On TPU the default is the phased sweep (see _make_phased_sweep:
    # the whole-sweep program compiles too; which is faster on the chip
    # is not measured yet).
    profiled = opts.verbosity >= Verbosity.HIGH
    from splatt_tpu.ops.mttkrp import choose_impl

    # phased also when the native C++ MTTKRP engine will run: it
    # executes on host and cannot live inside a whole-sweep trace
    phased = (jax.default_backend() == "tpu"
              or (isinstance(X, BlockedSparse)
                  and choose_impl(opts) == "native"))
    # only the fused whole-sweep jit donates the CALLER-visible
    # factor/gram inputs; the phased sweep donates intra-sweep buffers
    # and the profiled sweep donates nothing, so neither needs (or
    # should pay for) the rescue snapshot below
    consumes_inputs = donate and not profiled and not phased

    def build_sweep(reg=opts.regularization):
        # a factory, not a value: after a runtime engine demotion (or a
        # health rollback's regularization bump) the sweep must be
        # REBUILT — the old jit wrapper may hold a compiled executable
        # with the demoted engine (or a fault-poisoned trace) inlined
        with trace.span("cpd.build_sweep", regularization=float(reg),
                        phased=phased, profiled=profiled):
            if profiled:
                return _make_profiled_sweep(X, nmodes, reg)
            return (_make_phased_sweep if phased
                    else _make_sweep)(X, nmodes, reg, donate=donate)

    sweep = build_sweep()
    if profiled:
        # warm both specializations of every split-jit phase on copies,
        # then zero the phase timers: the report shows steady-state
        # kernel cost, not trace+compile time
        for first in (True, False):
            sweep(list(factors), list(grams), first)
        for name in ("mttkrp", "solve", "normalize", "gram", "fit",
                     *(f"mttkrp_mode{m}" for m in range(nmodes))):
            timers.get(name).reset()

    # resuming past max_iterations runs zero sweeps — the checkpointed
    # λ/fit must survive as the result
    fit_prev = ck_fit
    fit = jnp.asarray(ck_fit, dtype=dtype)
    lam = (jnp.asarray(ck_lam, dtype=dtype) if ck_lam is not None
           else jnp.ones((rank,), dtype=dtype))
    # The donated FUSED sweep consumes its factor/gram inputs, so a
    # rescued retry cannot re-run from the pre-sweep device arrays —
    # they are gone.  A host snapshot (factors are MBs, the tensor is
    # the big thing) re-materializes the retry state instead.
    # Refreshed at fit-check iterations, so a rescue loses at most the
    # sweeps since the last check — the same window the deferred-fit-
    # check contract already trades away.  The numerical-health
    # sentinel shares the same snapshot as its rollback target: it is
    # only ever refreshed AFTER a check verified the state finite, so
    # it is last-GOOD, not merely last-checked.
    can_rescue = isinstance(X, BlockedSparse)
    guard = health_retries()
    snap = None

    def snapshot():
        # guard work, explicitly attributed: under the donated fused
        # sweep each refresh is a full host copy of every factor — the
        # prime suspect of ROADMAP open item 1, now a trace query
        with trace.span("cpd.guard.snapshot", host_copy=consumes_inputs):
            if consumes_inputs:
                # the donated sweep will CONSUME these buffers: only a
                # host copy survives as a rollback target
                return ([np.asarray(u) for u in factors],
                        [np.asarray(g) for g in grams],
                        np.asarray(lam))
            # non-donating sweeps never consume their inputs: holding
            # the committed device arrays IS the snapshot — no
            # transfer, just one older generation of factors+grams
            # kept alive per check
            return (list(factors), list(grams), lam)

    if (consumes_inputs and can_rescue) or guard > 0:
        snap = snapshot()
    timers.start("cpd")
    k = opts.fit_check_every
    last_check_it = start_it
    health_attempts = 0
    degraded = False
    from splatt_tpu.utils import faults as _faults
    for it in range(start_it, opts.max_iterations):
        t0 = time.perf_counter()
        # one span per iteration (docs/observability.md): sweep
        # dispatch through the commit — the unit whose span sums the
        # `splatt trace` summarizer reconciles with the printed
        # sec/iter.  begin/end (not `with`) keeps the guarded body at
        # its natural indentation; every exit path funnels through the
        # finally.
        it_span = trace.begin("cpd.iter", it=it + 1)
        try:
            # fetch the fit to host only at check iterations: each fetch
            # is a device-to-host sync, and k sweeps
            # queue back-to-back between checks (k=1 ≙ the reference).
            # A due checkpoint forces a check — the checkpoint_every
            # contract outranks sync batching.
            checkpoint_due = (checkpoint_path is not None
                              and (it + 1) % checkpoint_every == 0)
            check = ((it + 1) % k == 0 or it + 1 == opts.max_iterations
                     or checkpoint_due)
            # runtime graceful degradation: a sweep-level failure (an
            # engine dying at outer-jit compile time, or an async
            # runtime failure surfacing at the next sync) demotes the
            # implicated engine and retries THIS iteration on a rebuilt
            # sweep — the run degrades to the next engine in the chain
            # instead of crashing.  Failures inside mttkrp_blocked's own
            # dispatch are already handled one level down; this catches
            # what escapes it.  The host fetch of the fit is where ASYNC
            # device failures actually surface, so it lives INSIDE the
            # rescued scope — and the sweep outputs are committed to
            # factors/grams only after it succeeds, so a rescued retry
            # re-runs from the pre-sweep state instead of carrying a
            # failed program's poisoned outputs forward.  (On a deferred
            # iteration — fit_check_every > 1, no sync — an async
            # failure can still land one iteration late; that is the
            # documented trade of batching host syncs.)
            rescue_attempts = 0
            while True:
                try:
                    # host-side dispatch only: the device completes
                    # asynchronously and lands in the fit-check span
                    with trace.span("cpd.sweep"):
                        f_new, g_new, lam_new, znormsq, inner = sweep(
                            factors, grams, it == 0)
                    # chaos hook: a poison-armed cpd.sweep fault
                    # corrupts one sweep's factor output with
                    # non-finite values — the silent blowup the
                    # sentinel exists to catch.  The LAST factor: every
                    # next-sweep MTTKRP reads it, so an unguarded run
                    # genuinely diverges (a poisoned FIRST factor would
                    # be silently recomputed by mode 0's own update
                    # before anything reads it)
                    f_new[-1] = _faults.poison("cpd.sweep", f_new[-1])
                    fit = _fit(xnormsq, znormsq, inner)
                    if check and guard > 0:
                        # numerical-health sentinel: the finite-check
                        # reduction rides the fit fetch (ONE host sync).
                        # The fit_check span is that sync; the guard's
                        # incremental work on top of it — building and
                        # fetching the packed vector — is attributed to
                        # its own cpd.guard.health_pack child
                        with trace.span("cpd.fit_check", it=it + 1):
                            with trace.span("cpd.guard.health_pack"):
                                packed = np.asarray(
                                    _health_pack(f_new, lam_new, fit))
                        fitval, offending, healthy = _health_verdict(
                            packed, nmodes)
                        if not healthy:
                            err = _resilience.NumericalHealthError(
                                f"non-finite sweep outputs at iteration "
                                f"{it + 1} (factor modes "
                                f"{offending or 'none'}; λ/fit "
                                f"{'finite' if offending else 'non-finite'})")
                            err.offending = offending
                            raise err
                    elif check:
                        # the one existing host sync batched device
                        # work drains into (SPL003's sanctioned point)
                        with trace.span("cpd.fit_check", it=it + 1):
                            fitval = float(fit)
                    else:
                        fitval = None
                    break
                except _resilience.NumericalHealthError as e:
                    health_attempts += 1
                    offending = getattr(e, "offending", [])
                    _resilience.run_report().add(
                        "health_nonfinite", iteration=it + 1,
                        modes=offending,
                        error=_resilience.failure_message(e)[:200])
                    if health_attempts > guard:
                        # budget exhausted: degrade to checkpoint-and-
                        # abort — return the last-good snapshot instead
                        # of diverging or crashing (docs/guarded-als.md)
                        degraded = True
                        break
                    # rollback: restore the last-good host snapshot,
                    # bump regularization (re-conditioning the normal
                    # equations) and re-randomize the offending
                    # factor(s); the sweep is REBUILT so a
                    # fault-poisoned trace cannot survive
                    with trace.span("cpd.guard.rollback", it=it + 1,
                                    attempt=health_attempts):
                        factors = [jnp.asarray(u) for u in snap[0]]
                        grams = [jnp.asarray(g) for g in snap[1]]
                        lam = jnp.asarray(snap[2])
                        reg = ((opts.regularization
                                if opts.regularization > 0 else 1e-6)
                               * (10.0 ** health_attempts))
                        key = jax.random.PRNGKey(opts.seed() + 7919)
                        for m in offending:
                            factors[m] = jax.random.uniform(
                                jax.random.fold_in(
                                    key, health_attempts * 64 + m),
                                factors[m].shape, dtype=factors[m].dtype)
                            grams[m] = gram(factors[m])
                    _resilience.run_report().add(
                        "health_rollback", iteration=it + 1,
                        attempt=health_attempts, regularization=reg,
                        rerandomized=offending)
                    if opts.verbosity >= Verbosity.LOW:
                        print(f"  non-finite sweep outputs at iteration "
                              f"{it + 1}; rolled back to the last-good "
                              f"snapshot (attempt {health_attempts}/"
                              f"{guard}: reg={reg:g}, re-randomized modes "
                              f"{offending})")
                    sweep = build_sweep(reg)
                except Exception as e:
                    rescue_attempts += 1
                    if (rescue_attempts > 6
                            or not _try_engine_rescue(X, opts, e)):
                        raise
                    sweep = build_sweep()
                    if snap is not None and any(
                            getattr(a, "is_deleted", lambda: False)()
                            for a in [*factors, *grams]):
                        # the failed program consumed the donated
                        # inputs: re-materialize the retry state from
                        # the host snapshot (ALS is self-correcting, so
                        # restarting from the last checked iterate just
                        # continues the same optimization)
                        factors = [jnp.asarray(u) for u in snap[0]]
                        grams = [jnp.asarray(g) for g in snap[1]]
            if degraded:
                # the result is the last-good state; persist it so a
                # later resume (perhaps with more retries or a fixed
                # input) continues from here instead of redoing the work
                factors = [jnp.asarray(u) for u in snap[0]]
                grams = [jnp.asarray(g) for g in snap[1]]
                lam = jnp.asarray(snap[2])
                action = "stopped early with the last-good factors"
                if checkpoint_path is not None:
                    # the snapshot corresponds to the LAST HEALTHY
                    # check, not the iteration the blowup was detected
                    # at — a resume must redo the rolled-back window,
                    # not skip it
                    _save_checkpoint(checkpoint_path, factors, lam,
                                     last_check_it, fit_prev,
                                     reorder=reorder_label)
                    action += f"; checkpointed to {checkpoint_path}"
                _resilience.run_report().add(
                    "health_degraded", iteration=it + 1, action=action)
                if opts.verbosity >= Verbosity.LOW:
                    print(f"  health-retry budget ({guard}) exhausted at "
                          f"iteration {it + 1}; {action}")
                break
            factors, grams, lam = f_new, g_new, lam_new
            if not check:
                if opts.verbosity >= Verbosity.HIGH:
                    print(f"  its = {it + 1:3d} (deferred fit check)")
                continue
            it_span.set(fit=fitval)
            elapsed = time.perf_counter() - t0
            if snap is not None and guard > 0:
                # refresh the rollback target only after a
                # verified-finite check.  With the sentinel disabled
                # (guard == 0) the refresh is SKIPPED entirely — guards
                # must be free when off, and for the donated fused sweep
                # each refresh is a full host copy of every factor.  The
                # initial snapshot is kept for the (rare) engine rescue,
                # which then re-materializes the pre-run state: ALS is
                # self-correcting, so the retry re-converges, just from
                # further back.
                snap = snapshot()
            if opts.verbosity >= Verbosity.LOW:
                print(f"  its = {it + 1:3d} ({elapsed:.3f}s)"
                      f"  fit = {fitval:0.5f}"
                      f"  delta = {fitval - fit_prev:+0.4e}")
            if checkpoint_due:
                _save_checkpoint(checkpoint_path, factors, lam, it + 1,
                                 fitval, reorder=reorder_label)
            if stop is not None and stop():
                # cooperative interruption (serve drain): the state just
                # committed is checkpointed so a later resume redoes
                # nothing, and the caller decides what the early return
                # means (the fit so far is a truthful partial result)
                if checkpoint_path is not None and not checkpoint_due:
                    _save_checkpoint(checkpoint_path, factors, lam,
                                     it + 1, fitval,
                                     reorder=reorder_label)
                fit_prev = fitval
                break
            # tolerance scales with the *actual* delta window: k sweeps
            # between regular checks, but a checkpoint-forced check can
            # land mid-window (≙ the k=1 per-iteration test,
            # src/cpd.c:368-370)
            window = (it + 1) - last_check_it
            last_check_it = it + 1
            if it > 0 and abs(fitval - fit_prev) < opts.tolerance * window:
                fit_prev = fitval
                break
            fit_prev = fitval
        finally:
            trace.end(it_span)
    timers.stop("cpd")

    out = post_process(factors, lam, jnp.asarray(fit_prev, dtype=dtype))
    if reorder_perm is not None:
        # restore ORIGINAL row labels on every factor (Permutation.undo
        # round-trip, docs/layout-balance.md): the relabeling is an
        # internal layout optimization, invisible at the API boundary
        out = dataclasses.replace(
            out, factors=reorder_perm.undo_factors(out.factors))
    return out


# -- batched fleet CPD (docs/batched.md) -------------------------------------
#
# The serving fleet's million-tenant shape: K small same-regime tensors
# decomposed as ONE jitted vmapped computation.  Each slot keeps
# independent semantics — its own init seed, fit trajectory,
# convergence stop and health verdict — as DATA along the batch axis,
# while compile, probe and tuned-plan costs are paid once for the
# whole batch.


@dataclasses.dataclass
class BatchedCPD:
    """Per-slot results + the batch-level evidence the serving layer
    audits: ``compiles`` counts python traces of the one jitted sweep
    (the "K tenants share a single compile" acceptance is
    ``compiles == 1``), ``rollbacks`` the per-slot health-rollback
    counts (a NaN slot's rollbacks never appear on a neighbor)."""

    results: List[KruskalTensor]
    statuses: List[str]            # "converged" | "degraded" per slot
    fits: List[float]
    iterations: int
    compiles: int
    rollbacks: List[int]
    stopped: bool = False          # a cooperative stop() interrupted

    @property
    def k(self) -> int:
        return len(self.results)


@jax.jit
def _health_pack_batched(factors, lam, fit):
    """Per-slot finite flags ``(K, nmodes + 2)`` — the batch-axis
    vectorization of :func:`_health_pack`: one column per factor, then
    λ, then fit.  Fetched at the same fit-check host sync the fit
    already pays for; one slot's NaN trips only its own row."""
    flags = [jnp.isfinite(U).all(axis=(1, 2)) for U in factors]
    flags.append(jnp.isfinite(lam).all(axis=1))
    flags.append(jnp.isfinite(fit))
    return jnp.stack(flags, axis=1).astype(fit.dtype)


def _make_batched_sweep(bb, rank: int, donate: bool, xnormsq,
                        counter: dict) -> Callable:
    """Build the ONE jitted vmapped sweep of a batch (docs/batched.md).

    Per-slot MTTKRP is the segment-sum consumption of the stacked v1
    streams (pads are additive identities, so each slot's lanes compute
    exactly the single-tensor scatter dataflow over its own layout
    order); solve/normalize/gram ride ``jax.vmap`` over the stock
    single-tensor math.  Three contracts keep per-slot semantics intact
    inside one compiled program:

    - ``first`` is a TRACED scalar (both norms computed, selected with
      ``where``), so iteration 0 shares the compile with every later
      sweep — ``counter["traces"]`` counts python traces, which is the
      compile-count evidence the batched acceptance audits;
    - ``reg`` is a ``(K,)`` argument, so a health rollback bumps one
      slot's regularization without rebuilding (= recompiling) the
      sweep;
    - ``active`` is a ``(K,)`` mask: converged/degraded slots are
      frozen bit-exactly (their committed state is re-selected, never
      recomputed), so a slot stopping early keeps the same result the
      sequential loop would have returned.

    With `donate`, the stacked factor/gram/λ buffers are donated — the
    same whole-sweep aliasing the single-tensor fused sweep uses; the
    driver keeps the usual last-good host snapshot as the rollback
    target.
    """
    from splatt_tpu.config import fit_dtype
    from splatt_tpu.ops.mttkrp import mttkrp_batched_stream

    nmodes = bb.nmodes
    dims_pad = bb.dims
    inds_c = bb.inds
    vals_c = bb.vals
    fdt_fit = fit_dtype()
    # kept as plain PYTHON tuples in the closure: the trace
    # materializes them as constants at the asarray inside `sweep`,
    # so the jit never closes over an enclosing-scope array (SPL010)
    xn_t = tuple(float(x) for x in
                 np.sqrt(np.maximum(xnormsq, 1e-300)))
    xn_sq_t = tuple(float(x) for x in xnormsq)

    def norm_sel(U, first):
        # both norms, one compile: `first` is traced, so the 2-norm /
        # max-norm pick is a select, not a retrace (zero-padded bucket
        # rows change neither: they add 0 to the 2-norm sum and the
        # max-norm clamps at 1.0 either way)
        lam2 = jnp.sqrt(jnp.einsum(
            "dr,dr->r", U, U,
            preferred_element_type=acc_dtype(U.dtype)))
        lamm = jnp.maximum(jnp.max(U, axis=0), 1.0)
        lam = jnp.where(first, lam2.astype(U.dtype), lamm)
        safe = jnp.where(lam > 0, lam, 1.0)
        return U / safe, lam

    def sweep(factors, grams, lam, reg, active, first):
        counter["traces"] += 1
        keep2 = active[:, None]
        keep3 = active[:, None, None]
        M = None
        for m in range(nmodes):
            fdt = factors[m].dtype
            M = mttkrp_batched_stream(inds_c, vals_c, factors, m,
                                      dims_pad[m])
            lhs = jax.vmap(form_normal_lhs, in_axes=(0, None))(grams, m)
            lhs = lhs + (reg.astype(lhs.dtype)[:, None, None]
                         * jnp.eye(rank, dtype=lhs.dtype))
            U = jax.vmap(solve_normals)(lhs, M)
            U, lam_m = jax.vmap(norm_sel, in_axes=(0, None))(U, first)
            U = U.astype(fdt)
            factors[m] = jnp.where(keep3, U, factors[m])
            grams[m] = jnp.where(keep3, jax.vmap(gram)(factors[m]),
                                 grams[m])
            lam = jnp.where(keep2, lam_m.astype(lam.dtype), lam)
        # frozen slots recompute the same M from their frozen factors,
        # so the fit below is their committed fit, bit-stable
        znormsq, inner = jax.vmap(_zz_inner)(lam, grams, M, factors[-1])
        fit = 1.0 - jnp.sqrt(jnp.maximum(
            jnp.asarray(xn_sq_t, dtype=fdt_fit)
            + znormsq.astype(fdt_fit)
            - 2.0 * inner.astype(fdt_fit), 0.0)) \
            / jnp.asarray(xn_t, dtype=fdt_fit)
        return factors, grams, lam, fit

    return jax.jit(sweep, donate_argnums=(0, 1, 2) if donate else ())


def cpd_als_batched(tensors, rank: int, opts: Optional[Options] = None,
                    seeds: Optional[List[int]] = None,
                    inits: Optional[List[List[jax.Array]]] = None,
                    stop: Optional[Callable[[], bool]] = None
                    ) -> BatchedCPD:
    """Decompose K same-regime tensors as ONE vmapped ALS
    (docs/batched.md) — the batched half of ROADMAP open item 2.

    `tensors` is a list of COO tensors (stacked here via
    :func:`splatt_tpu.blocked.batch_compile`) or an already-built
    :class:`splatt_tpu.blocked.BatchedBlocked`.  `seeds` gives each
    slot its own factor-init seed (default ``opts.seed() + slot``);
    `inits` overrides with explicit per-slot factor lists at each
    slot's TRUE dims.  `stop` is the serve drain hook, polled at fit
    checks like :func:`cpd_als`.

    Per-slot guarantees:

    - fits, convergence stops and results are independent — a
      converged slot is frozen (bit-stable) while neighbors iterate;
    - the PR 5 health sentinel vectorizes over the batch axis: a
      non-finite slot rolls back ALONE to its last-good snapshot
      (reg bump + re-randomize of the offending factor, per slot),
      and an exhausted budget degrades ONLY that slot to its
      last-good state (status "degraded") — a NaN tenant cannot
      poison its batch neighbors;
    - one compile: ``BatchedCPD.compiles`` counts sweep traces.
    """
    from splatt_tpu.blocked import BatchedBlocked, batch_compile

    opts = (opts or default_opts()).validate()
    with trace.enabling(opts.trace):
        with trace.span("cpd.batch", rank=int(rank),
                        k=(tensors.k if isinstance(tensors, BatchedBlocked)
                           else len(tensors)),
                        max_iterations=int(opts.max_iterations)):
            bb = (tensors if isinstance(tensors, BatchedBlocked)
                  else batch_compile(list(tensors), opts, rank=rank))
            return _cpd_als_batched_traced(bb, rank, opts, seeds, inits,
                                           stop)


def _cpd_als_batched_traced(bb, rank: int, opts: Options, seeds, inits,
                            stop) -> BatchedCPD:
    from splatt_tpu import resilience as _resilience
    from splatt_tpu.config import fit_dtype, host_acc_dtype, \
        host_staging_dtype
    from splatt_tpu.kruskal import unstack_batched
    from splatt_tpu.utils import faults as _faults

    K, nmodes = bb.k, bb.nmodes
    dtype = bb.vals.dtype
    staging = host_staging_dtype(dtype)
    fdt_fit = fit_dtype()
    hacc = host_acc_dtype()
    if seeds is None:
        base = opts.seed()
        seeds = [base + i for i in range(K)]
    if len(seeds) != K or (inits is not None and len(inits) != K):
        raise ValueError(f"need one seed/init per slot (k={K})")

    # per-slot init at TRUE dims (parity with each slot's own
    # sequential run), zero-padded into the bucket rows — zero rows are
    # fixed points of the whole sweep (zero MTTKRP rows → zero solve
    # rows → zero gram contribution), so the padding never leaks into
    # a slot's math
    factors = []
    for m in range(nmodes):
        F = np.zeros((K, bb.dims[m], rank), dtype=staging)
        for i in range(K):
            d = bb.slot_dims[i][m]
            if inits is not None:
                Ui = np.asarray(inits[i][m], dtype=staging)
                if Ui.shape != (d, rank):
                    raise ValueError(
                        f"init for slot {i} mode {m} has shape "
                        f"{Ui.shape}, want {(d, rank)}")
                F[i, :d] = Ui
            else:
                # exactly init_factors' draw for this (seed, mode) at
                # the slot's true dims — widened exactly into the
                # staging buffer, cast back to the storage dtype below
                F[i, :d] = np.asarray(jax.random.uniform(
                    jax.random.fold_in(
                        jax.random.PRNGKey(seeds[i]), m),
                    (d, rank), dtype=dtype), dtype=staging)
        factors.append(jnp.asarray(F).astype(dtype))
    grams = [jax.vmap(gram)(F) for F in factors]
    lam = jnp.ones((K, rank), dtype=fdt_fit)

    xnormsq = bb.slot_frobsq()
    counter = {"traces": 0}
    donate = opts.donate_sweep if opts.donate_sweep is not None else True
    sweep = _make_batched_sweep(bb, rank, donate, xnormsq, counter)

    guard = health_retries()
    reg = np.full(K, float(opts.regularization),
                  dtype=np.dtype(fdt_fit))
    active = np.ones(K, dtype=bool)
    degraded = np.zeros(K, dtype=bool)
    attempts = np.zeros(K, dtype=np.int64)
    fit_prev = np.zeros(K, dtype=hacc)
    fits = np.zeros(K, dtype=hacc)
    last_check_it = 0
    stopped = False

    def snapshot():
        with trace.span("cpd.guard.snapshot", host_copy=True):
            # np.array (not asarray): the per-slot refresh writes
            # individual lanes, so the snapshot must be a WRITABLE
            # host copy, not a read-only device view
            return ([np.array(F) for F in factors],
                    [np.array(G) for G in grams], np.array(lam))

    snap = snapshot() if guard > 0 else None

    def restore_slot(i: int):
        """Put slot i's last-good lanes back into the stacked state."""
        nonlocal factors, grams, lam
        factors = [F.at[i].set(jnp.asarray(snap[0][m][i]))
                   for m, F in enumerate(factors)]
        grams = [G.at[i].set(jnp.asarray(snap[1][m][i]))
                 for m, G in enumerate(grams)]
        lam = lam.at[i].set(jnp.asarray(snap[2][i]))

    kchk = opts.fit_check_every
    it = -1
    for it in range(opts.max_iterations):
        if not bool(active.any()):
            break
        it_span = trace.begin("cpd.batch.sweep", it=it + 1)
        try:
            f_new, g_new, lam_new, fit_dev = sweep(
                factors, grams, lam, jnp.asarray(reg),
                jnp.asarray(active), it == 0)
            # chaos hook (docs/guarded-als.md): a poison-armed
            # cpd.batch.sweep fault corrupts SLOT 0's last factor —
            # the per-slot isolation drill: slot 0 must roll back
            # alone while every neighbor stays bit-clean.  Only while
            # the slot is live: a frozen (converged/degraded) slot's
            # committed lanes are no longer the sweep's to corrupt.
            # The sentinel is a host SCALAR, so the unarmed hot path
            # pays a dict lookup — never a device gather or a
            # whole-buffer functional update.
            if bool(active[0]):
                p = _faults.poison("cpd.batch.sweep", 1.0)
                if not np.isfinite(p):
                    f_new[-1] = f_new[-1].at[:1].set(f_new[-1][:1] * p)
            factors, grams, lam = f_new, g_new, lam_new
            check = ((it + 1) % kchk == 0
                     or it + 1 == opts.max_iterations)
            if not check:
                continue
            fitv = np.asarray(fit_dev, dtype=hacc)
            if guard > 0:
                # the per-slot sentinel pack runs on the COMMITTED
                # state (the poison hook above included) and rides the
                # fit fetch this check already pays for; with the
                # sentinel disabled (guard == 0) it is skipped entirely
                # — guards must be free when off
                with trace.span("cpd.guard.health_pack"):
                    flags = np.asarray(_health_pack_batched(
                        factors, lam, fit_dev))
            else:
                flags = np.ones((K, nmodes + 2))
            if guard > 0:
                for i in np.flatnonzero(active):
                    if flags[i].min() > 0.5:
                        continue
                    offending = [m for m in range(nmodes)
                                 if flags[i][m] <= 0.5]
                    attempts[i] += 1
                    _resilience.run_report().add(
                        "health_nonfinite", iteration=it + 1,
                        slot=int(i), modes=offending,
                        error="non-finite batched sweep outputs")
                    if attempts[i] > guard:
                        degraded[i] = True
                        active[i] = False
                        restore_slot(int(i))
                        fits[i] = fit_prev[i]
                        _resilience.run_report().add(
                            "health_degraded", iteration=it + 1,
                            slot=int(i),
                            action="slot frozen at its last-good "
                                   "snapshot; batch neighbors continue")
                        continue
                    with trace.span("cpd.guard.rollback", it=it + 1,
                                    slot=int(i),
                                    attempt=int(attempts[i])):
                        restore_slot(int(i))
                        reg[i] = ((opts.regularization
                                   if opts.regularization > 0 else 1e-6)
                                  * (10.0 ** attempts[i]))
                        key = jax.random.PRNGKey(seeds[i] + 7919)
                        for m in offending:
                            d = bb.slot_dims[i][m]
                            U = jax.random.uniform(
                                jax.random.fold_in(
                                    key, int(attempts[i]) * 64 + m),
                                (d, rank), dtype=dtype)
                            pad = jnp.zeros((bb.dims[m], rank),
                                            dtype=dtype)
                            pad = pad.at[:d].set(U)
                            factors[m] = factors[m].at[i].set(pad)
                            grams[m] = grams[m].at[i].set(gram(pad))
                    _resilience.run_report().add(
                        "health_rollback", iteration=it + 1,
                        slot=int(i), attempt=int(attempts[i]),
                        regularization=float(reg[i]),
                        rerandomized=offending)
                    if opts.verbosity >= Verbosity.LOW:
                        print(f"  batch slot {i}: non-finite at "
                              f"iteration {it + 1}; rolled back alone "
                              f"(attempt {int(attempts[i])}/{guard})")
            window = max((it + 1) - last_check_it, 1)
            last_check_it = it + 1
            healthy = active & (flags.min(axis=1) > 0.5)
            for i in np.flatnonzero(healthy):
                fits[i] = fitv[i]
                if it > 0 and abs(fitv[i] - fit_prev[i]) \
                        < opts.tolerance * window:
                    active[i] = False   # converged: frozen from here
                fit_prev[i] = fitv[i]
            if guard > 0 and healthy.any():
                # refresh only verified-finite slots' lanes: the
                # snapshot stays last-GOOD per slot
                hs = np.flatnonzero(healthy)
                for m in range(nmodes):
                    snap[0][m][hs] = np.asarray(factors[m])[hs]
                    snap[1][m][hs] = np.asarray(grams[m])[hs]
                snap[2][hs] = np.asarray(lam)[hs]
            if opts.verbosity >= Verbosity.LOW:
                done = K - int(active.sum())
                print(f"  batch its = {it + 1:3d}  "
                      f"fit[0] = {fitv[0]:0.5f}  "
                      f"done {done}/{K}")
            if stop is not None and stop():
                stopped = True
                break
        finally:
            trace.end(it_span)

    statuses = ["degraded" if degraded[i] else "converged"
                for i in range(K)]
    results = unstack_batched(factors, lam, fits, bb.slot_dims)
    return BatchedCPD(results=results, statuses=statuses,
                      fits=[float(f) for f in fits],
                      iterations=it + 1, compiles=counter["traces"],
                      rollbacks=[int(a) for a in attempts],
                      stopped=stopped)


# -- incremental model updates (docs/batched.md) -----------------------------

def touched_rows(delta, nmodes: int) -> Dict[int, np.ndarray]:
    """Per-mode sorted unique row indices a delta COO touches — the
    rows :func:`refresh_touched_rows` re-solves first."""
    return {m: np.unique(np.asarray(delta.inds[m]))
            for m in range(nmodes)}


def refresh_touched_rows(X, factors: List[jax.Array],
                         touched: Dict[int, np.ndarray],
                         reg: float = 0.0) -> List[jax.Array]:
    """The warm-update pre-pass (docs/batched.md): re-solve ONLY the
    rows a delta touched, before the global warm-started sweeps run.

    For each mode the full MTTKRP runs (small tensors — the point of
    the update path is skipping re-CONVERGENCE, not one matvec), but
    only the touched rows of the factor are committed, normalized into
    the warm factors' column scale so untouched rows keep their
    converged values exactly.  Runs under the ``cpd.update`` fault
    site: a raised fault surfaces to the serve update path, which
    degrades CLASSIFIED to the full-refit repair path
    (``refit_scheduled`` event) — never a failed job."""
    from splatt_tpu.ops.mttkrp import mttkrp
    from splatt_tpu.utils import faults as _faults

    _faults.maybe_fail("cpd.update")
    out = list(factors)
    grams = [gram(U) for U in out]
    for m in sorted(touched):
        rows = np.asarray(touched[m], dtype=np.int64)
        if rows.size == 0:
            continue
        M = mttkrp(X, out, m)
        lhs = form_normal_lhs(grams, m, reg)
        U = solve_normals(lhs, M)
        U, _ = normalize_columns(U, "max")
        rows_j = jnp.asarray(rows)
        out[m] = out[m].at[rows_j].set(
            U[rows_j].astype(out[m].dtype))
        grams[m] = gram(out[m])
    return out
