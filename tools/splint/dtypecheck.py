"""Dtype-flow oracle: the dynamic companion of SPL024/SPL028.

The static rules (tools/splint/numerics.py) prove the accumulation-
dtype SHAPE of the code — every reduce on the sparse hot path is
routed through a sanctioned pin.  This module proves the BEHAVIOR:
it traces the REAL production entry points — gram, normalize_columns,
solve_normals, the stream/ttbox MTTKRP oracles, cpd's fit inner
products, the Kruskal norm, and one Pallas reduction in interpret
mode — across the storage×compute dtype matrix (f32 and bf16 factor
storage) with ``jax.eval_shape``, and asserts the accumulation
contract on the OUTPUT dtypes:

  1. every accumulation-carrying result (Gram matrices, column norms,
     MTTKRP outputs, fit inner products) is at least f32, whatever
     the factor storage dtype;
  2. storage contracts survive: ``normalize_columns`` hands back the
     factor in its own storage dtype (the λ it computed wide), so a
     bf16 sweep never silently widens its resident factors;
  3. the runtime tiling policy (``config.tile_packing``) agrees with
     the static tiling table SPL025 judges against — (8, 128) f32,
     (16, 128) bf16;
  4. the one real execution (``onehot_reduce_sorted`` in interpret
     mode over bf16 values and rows) produces a wide output whose VALUES
     match an exactly-accumulated reference — dtype discipline that
     types correctly but sums garbage is still caught.

eval_shape runs the actual tracing machinery over zero bytes of
data, so the whole matrix costs milliseconds and rides in the fast
CI leg next to splint itself.  In a clean run the module also
replays the static analyzer over the same scope and refuses to
certify a tree the static plane flags (or report drift the other
way): the two planes must agree or one of them is lying.

Mutants.  ``--mutant NAME`` wires in a known dtype regression
(in-process monkeypatch — invisible to the static plane, which is
exactly the point: these regressions are what the DYNAMIC oracle
exists to catch) and exits 0 iff the checker catches it:

  acc_identity      config.acc_dtype loses its bf16→f32 promotion
  gram_unpinned     gram reverts to a raw ``U.T @ U``
  stream_narrow_acc the engines' local _acc_dtype loses the promotion
  lam_narrow_norm   normalize_columns accumulates λ² at storage dtype

Usage:
  python -m tools.splint.dtypecheck [--json] [--mutant NAME]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from pathlib import Path
from typing import List, Optional

MUTANTS = ("acc_identity", "gram_unpinned", "stream_narrow_acc",
           "lam_narrow_norm")

#: the accumulation contract: whatever the storage dtype, reductions
#: accumulate at least here
_ACC = "float32"

#: the static rules whose verdict the clean run cross-checks
_STATIC_FAMILY = ("SPL024", "SPL025", "SPL026", "SPL027", "SPL028")


@dataclasses.dataclass
class Violation:
    scenario: str
    storage: str
    invariant: str
    detail: str


@dataclasses.dataclass
class Result:
    checks: int = 0
    violations: List[Violation] = dataclasses.field(default_factory=list)
    static_findings: dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "checks": self.checks,
            "ok": self.ok,
            "static_findings": self.static_findings,
            "violations": [dataclasses.asdict(v) for v in self.violations],
        }


def _mttkrp_module():
    """The splatt_tpu.ops.mttkrp MODULE — the ops package re-exports
    the ``mttkrp`` function under the same name, so attribute access
    on the package finds the function, not the module."""
    import importlib

    return importlib.import_module("splatt_tpu.ops.mttkrp")


def _apply_mutant(name: str):
    """Wire in the named regression; returns an undo callable.

    The patches are plain module-attribute swaps, so a fresh process
    (the CLI, the subprocess self-tests) is the clean way to run one:
    jitted entry points may cache traces made under the mutant."""
    import jax.numpy as jnp

    from splatt_tpu import config
    from splatt_tpu.ops import linalg

    mttkrp = _mttkrp_module()

    if name == "acc_identity":
        saved, obj, attr = config.acc_dtype, config, "acc_dtype"
        config.acc_dtype = lambda dtype: jnp.dtype(dtype)
    elif name == "gram_unpinned":
        saved, obj, attr = linalg.gram, linalg, "gram"
        linalg.gram = lambda U: jnp.matmul(U.T, U)
    elif name == "stream_narrow_acc":
        saved, obj, attr = mttkrp._acc_dtype, mttkrp, "_acc_dtype"
        mttkrp._acc_dtype = lambda dtype: jnp.dtype(dtype)
    elif name == "lam_narrow_norm":
        def _unpinned(U, which="2"):
            lam = jnp.sqrt(jnp.sum(U * U, axis=0))
            safe = jnp.where(lam > 0, lam, 1.0)
            return U / safe.astype(U.dtype), lam

        saved, obj, attr = (linalg.normalize_columns, linalg,
                            "normalize_columns")
        linalg.normalize_columns = _unpinned
    else:
        raise ValueError(f"unknown mutant {name!r}")
    return lambda: setattr(obj, attr, saved)


def _expect(result: Result, scenario: str, storage: str, got,
            want: str, what: str) -> None:
    import jax.numpy as jnp

    result.checks += 1
    if jnp.dtype(got) != jnp.dtype(want):
        result.violations.append(Violation(
            scenario, storage, "acc-dtype",
            f"{what}: got {jnp.dtype(got).name}, contract says "
            f"{jnp.dtype(want).name}"))


def _check_policy(result: Result, storage: str) -> None:
    """The config policy surface itself: the promotion and the tiling
    table the static plane judges against."""
    import jax.numpy as jnp

    from splatt_tpu import config

    _expect(result, "config.acc_dtype", storage,
            config.acc_dtype(jnp.dtype(storage)), _ACC,
            "accumulation dtype")
    result.checks += 1
    want_pack = (16, 128) if storage == "bfloat16" else (8, 128)
    got_pack = tuple(config.tile_packing(jnp.dtype(storage)))
    if got_pack != want_pack:
        result.violations.append(Violation(
            "config.tile_packing", storage, "tile-packing",
            f"got {got_pack}, the {storage} sublane×lane tile is "
            f"{want_pack}"))


def _check_linalg(result: Result, storage: str) -> None:
    import jax
    import jax.numpy as jnp

    from splatt_tpu.ops import linalg

    U = jax.ShapeDtypeStruct((40, 8), jnp.dtype(storage))
    _expect(result, "gram", storage,
            jax.eval_shape(linalg.gram, U).dtype, _ACC, "Gram matrix")

    norm_U, lam = jax.eval_shape(
        lambda u: linalg.normalize_columns(u, "2"), U)
    _expect(result, "normalize_columns", storage, lam.dtype, _ACC,
            "column norms λ")
    _expect(result, "normalize_columns", storage, norm_U.dtype, storage,
            "normalized factor (storage contract)")

    lhs = jax.ShapeDtypeStruct((8, 8), jnp.dtype(_ACC))
    rhs = jax.ShapeDtypeStruct((40, 8), jnp.dtype(_ACC))
    _expect(result, "solve_normals", storage,
            jax.eval_shape(linalg.solve_normals, lhs, rhs).dtype, _ACC,
            "normal-equations solve")


def _check_mttkrp(result: Result, storage: str) -> None:
    import jax
    import jax.numpy as jnp

    mttkrp = _mttkrp_module()

    dims, R, nnz = (12, 9, 7), 8, 64
    inds = jax.ShapeDtypeStruct((len(dims), nnz), jnp.int32)
    vals = jax.ShapeDtypeStruct((nnz,), jnp.dtype(storage))
    factors = [jax.ShapeDtypeStruct((d, R), jnp.dtype(storage))
               for d in dims]
    for name in ("mttkrp_stream", "mttkrp_ttbox"):
        fn = functools.partial(getattr(mttkrp, name), mode=0, dim=dims[0])
        out = jax.eval_shape(fn, inds, vals, factors)
        _expect(result, name, storage, out.dtype, _ACC, "MTTKRP output")
        result.checks += 1
        if out.shape != (dims[0], R):
            result.violations.append(Violation(
                name, storage, "shape",
                f"got {out.shape}, want {(dims[0], R)}"))


def _check_fit(result: Result, storage: str) -> None:
    """cpd's ⟨Z,Z⟩/⟨X,Z⟩ inner products and the Kruskal norm, with the
    entry dtypes the dispatch layer feeds them: M is the (wide) MTTKRP
    accumulator, U_last the (storage-dtype) resident factor — the same
    contract [tool.splint] hot-stream-param-dtypes declares."""
    import jax
    import jax.numpy as jnp

    from splatt_tpu import cpd, kruskal

    R, d = 8, 40
    lam = jax.ShapeDtypeStruct((R,), jnp.dtype(_ACC))
    grams = [jax.ShapeDtypeStruct((R, R), jnp.dtype(_ACC))
             for _ in range(3)]
    M = jax.ShapeDtypeStruct((d, R), jnp.dtype(_ACC))
    U_last = jax.ShapeDtypeStruct((d, R), jnp.dtype(storage))
    znormsq, inner = jax.eval_shape(cpd._zz_inner, lam, grams, M, U_last)
    _expect(result, "cpd._zz_inner", storage, znormsq.dtype, _ACC,
            "⟨Z,Z⟩")
    _expect(result, "cpd._zz_inner", storage, inner.dtype, _ACC, "⟨X,Z⟩")

    lam_n = jax.ShapeDtypeStruct((R,), jnp.dtype(_ACC))
    fs = [jax.ShapeDtypeStruct((d, R), jnp.dtype(storage))
          for _ in range(3)]

    def normsq(lam_a, f0, f1, f2):
        kt = kruskal.KruskalTensor(factors=[f0, f1, f2], lam=lam_a,
                                   fit=jnp.zeros(()))
        return kt.normsq()

    _expect(result, "kruskal.normsq", storage,
            jax.eval_shape(normsq, lam_n, *fs).dtype, _ACC,
            "Kruskal ⟨Z,Z⟩")


def _check_interpret(result: Result) -> None:
    """One REAL execution: the sorted one-hot Pallas reduction in
    interpret mode over bf16 values and gathered rows — output must be
    wide AND match an exactly-accumulated reference (a cast inserted
    after the accumulate would type correctly and still lose mass)."""
    import jax.numpy as jnp
    import numpy as np

    from splatt_tpu.ops.pallas_kernels import onehot_reduce_sorted

    rng = np.random.default_rng(7)
    nb, B, S, R = 2, 128, 8, 8
    local = rng.integers(-1, S + 2, size=(nb, B)).astype(np.int32)
    vals = jnp.asarray(rng.random((nb, B)), dtype=jnp.bfloat16)
    rows = [jnp.asarray(rng.random((nb, B, R)), dtype=jnp.bfloat16)
            for _ in range(2)]
    got = onehot_reduce_sorted(jnp.asarray(local), vals, rows, S,
                               interpret=True)
    _expect(result, "onehot_reduce_sorted[interpret]", "bfloat16",
            got.dtype, _ACC, "block-partial accumulator")
    # reference: the SAME bf16 inputs and the kernel's bf16 product of
    # the rows, weighted by the values and accumulated exactly
    want = np.zeros((nb, S, R))
    p64 = (np.asarray(rows[0] * rows[1], dtype=np.float64)
           * np.asarray(vals, dtype=np.float64)[..., None])
    for b in range(nb):
        for j in range(B):
            if 0 <= local[b, j] < S:
                want[b, local[b, j]] += p64[b, j]
    result.checks += 1
    if not np.allclose(np.asarray(got, dtype=np.float64), want,
                       atol=1e-2):
        result.violations.append(Violation(
            "onehot_reduce_sorted[interpret]", "bfloat16", "values",
            "interpret-mode reduction does not match the exact "
            "accumulation of its own inputs"))


def _check_static_agreement(result: Result) -> None:
    """The clean run's cross-check: replay the static analyzer over
    the real tree and refuse to certify if the numerics/tiling family
    has findings — the two planes must agree."""
    from tools.splint import load_config, run

    cfg = load_config(Path(__file__).resolve().parents[2])
    report = run(cfg, baseline={})
    for f in report.findings:
        if f.rule in _STATIC_FAMILY:
            result.static_findings[f.rule] = \
                result.static_findings.get(f.rule, 0) + 1
    result.checks += 1
    if result.static_findings:
        result.violations.append(Violation(
            "static-cross-check", "*", "plane-agreement",
            f"the static numerics/tiling rules flag the tree the "
            f"dynamic oracle was asked to certify: "
            f"{result.static_findings}"))


def run_dtype_check(mutant: Optional[str] = None) -> Result:
    result = Result()
    undo = _apply_mutant(mutant) if mutant is not None else None
    try:
        for storage in ("float32", "bfloat16"):
            _check_policy(result, storage)
            _check_linalg(result, storage)
            _check_mttkrp(result, storage)
            _check_fit(result, storage)
        _check_interpret(result)
        if mutant is None:
            # the static plane cannot see an in-process monkeypatch,
            # so the agreement check only means something on the
            # clean tree
            _check_static_agreement(result)
    finally:
        if undo is not None:
            undo()
    return result


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tools.splint.dtypecheck",
        description="dtype-flow oracle over the real factorization "
                    "entry points (the dynamic plane of SPL024/SPL028)")
    p.add_argument("--mutant", choices=MUTANTS, default=None,
                   help="wire in a known dtype regression; exit 0 iff "
                        "the oracle CATCHES it")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the machine-readable report")
    args = p.parse_args(argv)
    result = run_dtype_check(mutant=args.mutant)
    if args.as_json:
        print(json.dumps(result.to_json(), indent=2, sort_keys=True))
    else:
        print(f"dtypecheck: {result.checks} checks over the "
              f"f32/bf16 storage matrix; "
              f"{len(result.violations)} violation(s)")
        for v in result.violations:
            print(f"  {v.scenario} [{v.storage}] "
                  f"({v.invariant}): {v.detail}")
    if args.mutant is not None:
        if result.violations:
            print(f"mutant {args.mutant!r} caught "
                  f"({len(result.violations)} violation(s))")
            return 0
        print(f"mutant {args.mutant!r} NOT caught — the dtype oracle "
              f"has lost its teeth", file=sys.stderr)
        return 1
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
