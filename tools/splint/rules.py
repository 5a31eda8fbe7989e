"""The splint rule catalog — each rule encodes a project invariant.

Every rule here is grounded in a real hazard this codebase has already
paid for (see docs/static-analysis.md for the war stories): these are
code-shape properties — what the code *would* do when infrastructure
misbehaves — which is exactly what behavioral tests cannot catch.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.splint.core import (FileCtx, Finding, FunctionCFG, JitSpec,
                               walk_nodes,
                               Project, _body_stmts, _expr_loads,
                               callable_jit_spec, free_reads,
                               jit_boundary, jit_call_spec, nested_defs,
                               returns_jit_spec, scope_functions)

#: handler-body names accepted as "routing the failure through the
#: taxonomy" — the resilience module's public verbs.  Projects add
#: their own wrappers via [tool.splint] resilience-routers.
RESILIENCE_ROUTERS = {
    "classify_failure", "demote_engine", "retry_transient",
    "run_report", "failure_message",
}

_DTYPE_LITERALS = {"float32", "float64", "bfloat16", "float16"}
_DTYPE_MODULES = {"numpy", "jax.numpy"}
_SYNC_JAX = {"jax.block_until_ready", "jax.device_get"}
_NP_HOST = {"numpy.asarray", "numpy.array"}
_FAULT_FNS = {"maybe_fail", "consume", "active", "inject", "poison"}
_ENV_READ_FNS = {"read_env", "read_env_int", "read_env_float"}


class Rule:
    id = "SPL?"
    title = ""
    hint = ""

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        return []

    def finalize(self, project: Project) -> List[Finding]:
        return []

    def finding(self, ctx_or_path, line: int, message: str) -> Finding:
        path = (ctx_or_path.relpath if isinstance(ctx_or_path, FileCtx)
                else ctx_or_path)
        return Finding(self.id, path, line, message, hint=self.hint)


# -- SPL001 -----------------------------------------------------------------

class RawEnvironAccess(Rule):
    """Raw ``os.environ`` access outside the sanctioned env module.

    Every env read outside ``utils/env.py`` bypasses the ENV_VARS
    registry (so the variable escapes documentation and SPL007), and —
    because env.py feeds the probe cache's ``_kernel_src_hash`` — can
    change dispatch-relevant behavior without invalidating cached
    capability verdicts."""

    id = "SPL001"
    title = "raw os.environ access outside utils/env.py"
    hint = ("read through splatt_tpu.utils.env.read_env/read_env_int/"
            "read_env_float and declare the variable in ENV_VARS")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        if ctx.relpath == project.config.env_module:
            return []
        out = []
        for node in walk_nodes(ctx.tree):
            dotted = None
            if isinstance(node, ast.Attribute):
                dotted = ctx.resolve(node)
            elif isinstance(node, ast.Name):
                dotted = ctx.aliases.get(node.id)
            if dotted in ("os.environ", "os.getenv", "os.putenv"):
                out.append(self.finding(
                    ctx, node.lineno,
                    f"raw {dotted} access bypasses the ENV_VARS "
                    f"registry in {project.config.env_module}"))
        return _dedupe(out)


# -- SPL002 -----------------------------------------------------------------

class BroadExceptSwallows(Rule):
    """``except Exception`` that neither re-raises nor routes the error
    through the failure taxonomy.  The PR 1 bug class: one broad except
    swallowed a transient HTTP 500 and persisted it as a permanent
    engine demotion."""

    id = "SPL002"
    title = "except Exception swallows the failure class"
    hint = ("classify via resilience.classify_failure (or demote_engine/"
            "retry_transient/run_report), re-raise, or add a justified "
            "'# splint: ignore[SPL002] <reason>'")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        routers = RESILIENCE_ROUTERS | set(
            project.config.resilience_routers)
        out = []
        for node in walk_nodes(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node.type):
                continue
            names: Set[str] = set()
            reraises = False
            for sub in node.body:
                for n in ast.walk(sub):
                    if isinstance(n, ast.Raise):
                        reraises = True
                    elif isinstance(n, ast.Name):
                        names.add(n.id)
                    elif isinstance(n, ast.Attribute):
                        names.add(n.attr)
            if reraises or names & routers:
                continue
            out.append(self.finding(
                ctx, node.lineno,
                "broad except swallows the error without classifying "
                "it — a transient infra failure and a real bug become "
                "indistinguishable here"))
        return out

    @staticmethod
    def _is_broad(type_node) -> bool:
        if type_node is None:
            return True  # bare except
        nodes = (type_node.elts if isinstance(type_node, ast.Tuple)
                 else [type_node])
        return any(isinstance(n, ast.Name)
                   and n.id in ("Exception", "BaseException")
                   for n in nodes)


# -- jit helpers (SPL003 / SPL004) ------------------------------------------

def _jit_static_names(ctx: FileCtx,
                      fn: ast.FunctionDef) -> Optional[Set[str]]:
    """The static argnames of a jit-decorated function, or None when
    the function is not jitted.  Handles ``@jax.jit``,
    ``@jax.jit(...)`` and ``@partial(jax.jit, ...)``."""
    for dec in fn.decorator_list:
        call = dec if isinstance(dec, ast.Call) else None
        target = call.func if call else dec
        dotted = ctx.resolve(target) or ""
        kwargs = {k.arg: k.value for k in call.keywords} if call else {}
        if dotted.split(".")[-1] == "partial" and call and call.args:
            inner = ctx.resolve(call.args[0]) or ""
            if inner in ("jax.jit", "jit"):
                return _static_names_from(kwargs, fn)
            continue
        if dotted in ("jax.jit", "jit"):
            return _static_names_from(kwargs, fn)
    return None


def _static_names_from(kwargs: Dict[str, ast.AST],
                       fn: ast.FunctionDef) -> Set[str]:
    static: Set[str] = set()
    names = kwargs.get("static_argnames")
    if names is not None:
        for n in ([names] if isinstance(names, ast.Constant)
                  else getattr(names, "elts", [])):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                static.add(n.value)
    nums = kwargs.get("static_argnums")
    if nums is not None:
        all_args = [a.arg for a in
                    fn.args.posonlyargs + fn.args.args]
        for n in ([nums] if isinstance(nums, ast.Constant)
                  else getattr(nums, "elts", [])):
            if isinstance(n, ast.Constant) and isinstance(n.value, int) \
                    and 0 <= n.value < len(all_args):
                static.add(all_args[n.value])
    return static


def _fn_params(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    return [x.arg for x in a.posonlyargs + a.args + a.kwonlyargs]


# -- SPL003 -----------------------------------------------------------------

class HostSyncInJit(Rule):
    """Host-device synchronization inside a jitted function (where it
    either fails at trace time or silently forces a device round-trip
    per call) or a configured hot-path function."""

    id = "SPL003"
    title = "host sync inside a jitted function / hot path"
    hint = ("keep block_until_ready/np.asarray/.item()/device_get out "
            "of traced code; batch host fetches at the sweep boundary "
            "(cpd.py's fit_check_every pattern)")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        hot = set(project.config.hot_functions)
        out = []
        for fn in walk_nodes(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            jitted = _jit_static_names(ctx, fn) is not None
            if not jitted and f"{ctx.relpath}::{fn.name}" not in hot:
                continue
            where = ("jitted function" if jitted
                     else "configured hot path")
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = ctx.resolve(node.func) or ""
                label = None
                if dotted in _SYNC_JAX or \
                        dotted.split(".")[-1] == "block_until_ready":
                    label = dotted.split(".")[-1]
                elif dotted in _NP_HOST:
                    label = dotted
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"
                        and not node.args and not node.keywords):
                    label = ".item()"
                if label:
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"host sync {label} inside {where} "
                        f"'{fn.name}'"))
        return out


# -- SPL004 -----------------------------------------------------------------

class RecompilationHazard(Rule):
    """A jitted function branching in Python on a non-static argument:
    jax either fails at trace time (tracer in bool context) or — when
    the value is concrete, e.g. a shape-dependent int — specializes
    the compilation to it, recompiling per distinct value."""

    id = "SPL004"
    title = "Python branch on a non-static jit argument"
    hint = ("mark the argument static_argnames (accepting per-value "
            "retraces deliberately) or branch on-device with "
            "jnp.where/lax.cond/lax.while_loop")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        out = []
        for fn in walk_nodes(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            static = _jit_static_names(ctx, fn)
            if static is None:
                continue
            nonstatic = set(_fn_params(fn)) - static - {"self"}
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                for name in self._branching_names(node.test, nonstatic):
                    kind = "while" if isinstance(node, ast.While) else "if"
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"Python {kind} on non-static jit argument "
                        f"'{name}' of '{fn.name}' — recompiles per "
                        f"value (or fails on a traced value)"))
        return out

    @staticmethod
    def _branching_names(test: ast.AST, nonstatic: Set[str]) -> List[str]:
        parents = {child: parent for parent in ast.walk(test)
                   for child in ast.iter_child_nodes(parent)}
        hits = []
        for node in ast.walk(test):
            if not (isinstance(node, ast.Name) and node.id in nonstatic):
                continue
            parent = parents.get(node)
            # attribute access (x.mode) is usually static metadata, and
            # call arguments (len(x), isinstance(x, ...)) resolve to
            # static values at trace time — only a direct value use of
            # the argument is a per-value specialization
            if isinstance(parent, ast.Attribute) and parent.value is node:
                continue
            if isinstance(parent, ast.Call) and node is not parent.func:
                continue
            if isinstance(parent, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in parent.ops):
                continue  # `x is None`: pytree structure, static
            hits.append(node.id)
        return hits


# -- SPL005 -----------------------------------------------------------------

class DtypeLiteral(Rule):
    """A dtype literal outside the config module: per-site dtype
    choices drift from the central Options.val_dtype / resolve_dtype
    policy (the bf16 and f64 paths both exist because dtype is a
    *policy*, not a per-callsite constant)."""

    id = "SPL005"
    title = "dtype literal outside config.py"
    hint = ("resolve dtypes through splatt_tpu.config.resolve_dtype / "
            "Options.val_dtype (or derive from an input's .dtype)")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        if ctx.relpath == project.config.config_module:
            return []
        out = []
        for node in walk_nodes(ctx.tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr in _DTYPE_LITERALS
                    and (ctx.resolve(node.value) or "") in _DTYPE_MODULES):
                out.append(self.finding(
                    ctx, node.lineno,
                    f"dtype literal .{node.attr} outside "
                    f"{project.config.config_module}"))
        return out


# -- SPL006 -----------------------------------------------------------------

def _call_sites(ctx: FileCtx) -> List[Tuple[Optional[str], int]]:
    """(site, lineno) for every fault-hook call in `ctx`; site is the
    literal string, 'prefix.*' for an f-string with a literal prefix,
    or None when not statically resolvable."""
    out = []
    for node in walk_nodes(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        if dotted.split(".")[-1] not in _FAULT_FNS or \
                "faults" not in dotted:
            continue
        arg = node.args[0] if node.args else None
        site: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            site = arg.value
        elif isinstance(arg, ast.Name):
            site = ctx.str_consts.get(arg.id)
        elif isinstance(arg, ast.JoinedStr) and arg.values:
            first = arg.values[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) and first.value:
                site = first.value + "*"
        out.append((site, node.lineno))
    return out


def _declared_sites(ctx: FileCtx) -> Dict[str, int]:
    for node in walk_nodes(ctx.tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "SITES"
                and isinstance(node.value, ast.Dict)):
            return {k.value: k.lineno for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    return {}


def _site_matches(declared: str, used: str) -> bool:
    if declared.endswith(".*"):
        return used == declared or used.startswith(declared[:-1])
    return used == declared


class FaultSiteDrift(Rule):
    """Fault-site drift: every site string the production code passes
    to the fault hooks must be declared in the faults module's SITES
    registry and exercised by at least one test — and every declared
    site must still exist in production.  A renamed hook otherwise
    silently orphans the resilience path it was built to exercise."""

    id = "SPL006"
    title = "fault-site drift against utils/faults.py:SITES"
    hint = ("declare the site (with a doc) in faults.SITES and "
            "exercise it from a test via faults.inject")

    def finalize(self, project: Project) -> List[Finding]:
        cfg = project.config
        faults_ctx = project.ctx_for(cfg.faults_module)
        if faults_ctx is None:
            return []
        declared = _declared_sites(faults_ctx)
        out = []
        prod_sites: List[Tuple[str, FileCtx, int]] = []
        for ctx in project.files:
            if ctx.relpath == cfg.faults_module:
                continue
            for site, line in _call_sites(ctx):
                if site is None:
                    out.append(self.finding(
                        ctx, line,
                        "fault site is not statically resolvable — "
                        "splint cannot check it against SITES"))
                else:
                    prod_sites.append((site, ctx, line))
        test_sites = {site for tctx in project.test_ctxs()
                      for site, _ in _call_sites(tctx) if site}
        for site, ctx, line in prod_sites:
            if not any(_site_matches(d, site) for d in declared):
                out.append(self.finding(
                    ctx, line,
                    f"fault site '{site}' is not declared in "
                    f"{cfg.faults_module}:SITES"))
        used = {s for s, _, _ in prod_sites}
        for d, line in declared.items():
            if not any(_site_matches(d, u) for u in used):
                out.append(self.finding(
                    faults_ctx, line,
                    f"declared fault site '{d}' has no production "
                    f"call — dead declaration or renamed hook"))
            elif not any(_site_matches(d, t) for t in test_sites):
                out.append(self.finding(
                    faults_ctx, line,
                    f"declared fault site '{d}' is not exercised by "
                    f"any test under {cfg.tests_path}/"))
        return out


# -- SPL007 -----------------------------------------------------------------

def _declared_env_vars(ctx: FileCtx) -> Dict[str, int]:
    for node in walk_nodes(ctx.tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "ENV_VARS"
                and isinstance(node.value, ast.Dict)):
            return {k.value: k.lineno for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    return {}


class UndocumentedEnvVar(Rule):
    """Every SPLATT_* environment variable the code reads must be
    declared (with a doc string) in the env module's ENV_VARS registry
    — the single source the docs render from."""

    id = "SPL007"
    title = "undocumented SPLATT_* environment variable"
    hint = ("declare the variable in splatt_tpu/utils/env.py:ENV_VARS "
            "(name -> default -> doc); docs render from that registry")

    def finalize(self, project: Project) -> List[Finding]:
        env_ctx = project.ctx_for(project.config.env_module)
        declared = _declared_env_vars(env_ctx) if env_ctx else {}
        out = []
        for ctx in project.files:
            for name, line in self._env_reads(ctx):
                if name.startswith("SPLATT_") and name not in declared:
                    out.append(self.finding(
                        ctx, line,
                        f"env var {name} is read but not declared in "
                        f"{project.config.env_module}:ENV_VARS"))
        return out

    @staticmethod
    def _env_reads(ctx: FileCtx) -> List[Tuple[str, int]]:
        out = []

        def literal(arg) -> Optional[str]:
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                return arg.value
            if isinstance(arg, ast.Name):
                return ctx.str_consts.get(arg.id)
            return None

        for node in walk_nodes(ctx.tree):
            if isinstance(node, ast.Call):
                dotted = ctx.resolve(node.func) or ""
                if (dotted in ("os.environ.get", "os.getenv")
                        or dotted.split(".")[-1] in _ENV_READ_FNS):
                    name = literal(node.args[0]) if node.args else None
                    if name:
                        out.append((name, node.lineno))
            elif isinstance(node, ast.Subscript) and \
                    (ctx.resolve(node.value) or "") == "os.environ":
                name = literal(node.slice)
                if name:
                    out.append((name, node.lineno))
        return out


# -- SPL008 -----------------------------------------------------------------

def _all_functions(tree) -> List[ast.FunctionDef]:
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _is_deleted_probe(test: ast.AST) -> bool:
    """Whether a branch test probes buffer deletion — the sanctioned
    re-materialization guard (``if any(a.is_deleted() for a in ...)``
    or the ``getattr(a, "is_deleted", ...)`` spelling)."""
    for n in ast.walk(test):
        if isinstance(n, ast.Attribute) and n.attr == "is_deleted":
            return True
        if isinstance(n, ast.Constant) and n.value == "is_deleted":
            return True
    return False


class UseAfterDonate(Rule):
    """A value handed to a jitted call at a donated argnum is read
    again without re-materialization.  ``donate_argnums`` aliases the
    output buffers onto the inputs (what makes the ALS sweep update in
    place), so the caller's array is GONE after the call — jax only
    reports the re-read at runtime, as a RuntimeError naming a deleted
    buffer.  The analysis is flow-sensitive (may-donate union over
    conditional wrappers, exception edges into handlers) and follows
    jit factories across function boundaries via the jit-boundary map.
    Re-binding the name clears the state; so does the sanctioned
    rescue idiom — a branch probing ``is_deleted`` whose body
    re-materializes the name (cpd_als's engine-rescue path).  Known
    imprecision: aliases (``a = factors``) and containers are not
    tracked; nested-function bodies are opaque, but calling a local
    closure counts as reading every name it closes over."""

    id = "SPL008"
    title = "donated buffer read after the jitted call"
    hint = ("re-materialize before the read (re-bind the name, or "
            "guard with the is_deleted + host-snapshot rescue idiom "
            "in cpd.py), or drop the argnum from donate_argnums")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        jb = jit_boundary(ctx)
        out: List[Finding] = []

        def analyze(fn, env: Dict[str, JitSpec],
                    factories: Dict[str, JitSpec]) -> None:
            env = dict(env)
            factories = dict(factories)
            subs = nested_defs(fn)
            # nested factories (build_sweep) against the inherited maps
            for _ in range(4):
                changed = False
                for sub in subs:
                    spec = returns_jit_spec(ctx, sub, env, factories)
                    if spec is not None and spec != factories.get(sub.name):
                        factories[sub.name] = spec
                        changed = True
                if not changed:
                    break
            # flow-insensitive local bindings: sweep = build_sweep()
            for s in _body_stmts(fn):
                if (isinstance(s, ast.Assign) and len(s.targets) == 1
                        and isinstance(s.targets[0], ast.Name)):
                    spec = callable_jit_spec(ctx, s.value, env, factories)
                    if spec is not None:
                        env[s.targets[0].id] = spec
            donating = (any(s.donates for s in env.values())
                        or any(s.donates for s in factories.values()))
            if not donating:
                # a donating wrapper invoked without ever being bound:
                # jax.jit(f, donate_argnums=...)(x), make_step(r)(x, g)
                donating = any(
                    (spec := jit_call_spec(ctx, n)) is not None
                    and spec.donates
                    for n in ast.walk(fn) if isinstance(n, ast.Call))
            if donating:
                out.extend(self._dataflow(ctx, fn, env, factories))
            for sub in subs:
                analyze(sub, env, factories)

        module_env = dict(jb.wrapped)
        for fn in scope_functions(ctx.tree):
            analyze(fn, module_env, dict(jb.factories))
        return _dedupe(out)

    def _dataflow(self, ctx, fn, env, factories) -> List[Finding]:
        cfg = FunctionCFG(fn)
        closures = {sub.name: free_reads(sub) for sub in nested_defs(fn)}
        findings: Dict[Tuple[str, int], Finding] = {}

        def node_effects(node):
            """(exempt_uses, extra_uses, sanitized, donations) of one
            CFG node; donations = [(name, call line)]."""
            stmt = node.stmt
            exprs: List[ast.AST] = []
            if node.kind == "test":
                exprs = [stmt.test]
            elif node.kind == "for":
                exprs = [stmt.iter]
            elif node.kind == "with":
                exprs = [i.context_expr for i in stmt.items]
            elif node.kind == "except":
                exprs = [stmt.type] if stmt.type is not None else []
            elif node.kind == "stmt" and not isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
                exprs = [stmt]
            exempt = (node.kind == "test"
                      and _is_deleted_probe(stmt.test))
            sanitized: Set[str] = set()
            if exempt and isinstance(stmt, ast.If):
                # the guard's body re-materializes these names; the
                # false branch has PROVEN the buffers are not deleted,
                # so both out-edges are clean
                for sub in stmt.body:
                    for n in ast.walk(sub):
                        if isinstance(n, ast.Name) and \
                                isinstance(n.ctx, ast.Store):
                            sanitized.add(n.id)
            extra_uses: List[Tuple[str, int]] = []
            donations: List[Tuple[str, int]] = []
            for root in exprs:
                for call in ast.walk(root):
                    if not isinstance(call, ast.Call):
                        continue
                    if isinstance(call.func, ast.Name) and \
                            call.func.id in closures:
                        extra_uses += [(n, call.lineno)
                                       for n in closures[call.func.id]]
                    spec = callable_jit_spec(ctx, call.func, env,
                                             factories)
                    if spec is None or not spec.donates:
                        continue
                    for i in sorted(spec.donate_argnums):
                        if i < len(call.args) and \
                                isinstance(call.args[i], ast.Name):
                            donations.append(
                                (call.args[i].id, call.lineno))
                    for kw in call.keywords:
                        if kw.arg in spec.donate_argnames and \
                                isinstance(kw.value, ast.Name):
                            donations.append((kw.value.id, call.lineno))
            return exempt, extra_uses, sanitized, donations

        effects = {n.idx: node_effects(n) for n in cfg.nodes}
        preds = cfg.preds()
        # state: name -> line of the donating call; merge = union
        ins: List[Dict[str, int]] = [{} for _ in cfg.nodes]
        outs: List[Dict[str, int]] = [{} for _ in cfg.nodes]
        excs: List[Dict[str, int]] = [{} for _ in cfg.nodes]
        work = [n.idx for n in cfg.nodes]
        while work:
            i = work.pop()
            node = cfg.nodes[i]
            exempt, extra_uses, sanitized, donations = effects[i]
            merged: Dict[str, int] = {}
            for p, via_exc in preds[i]:
                src = excs[p] if via_exc else outs[p]
                for name, line in src.items():
                    merged[name] = min(merged.get(name, line), line)
            state = {k: v for k, v in merged.items()
                     if k not in sanitized}
            if not exempt:
                for name, line in list(node.uses) + extra_uses:
                    if name in state:
                        key = (name, line)
                        if key not in findings:
                            findings[key] = self.finding(
                                ctx, line,
                                f"'{name}' was donated to the jitted "
                                f"call at line {state[name]} "
                                f"(donate_argnums) and is read here "
                                f"without re-materialization")
            after_donate = dict(state)
            for name, line in donations:
                after_donate[name] = line
            new_out = {k: v for k, v in after_donate.items()
                       if k not in node.defs}
            if merged != ins[i] or new_out != outs[i] \
                    or after_donate != excs[i]:
                ins[i], outs[i], excs[i] = merged, new_out, after_donate
                for s in node.succs + node.exc_succs:
                    if s not in work:
                        work.append(s)
        return list(findings.values())


# -- SPL009 -----------------------------------------------------------------

_MUTATORS = {"append", "extend", "add", "insert", "update", "setdefault",
             "appendleft"}


class TracerLeak(Rule):
    """A value derived from a traced argument escapes the trace into
    long-lived state: assigned to ``self.``/a global/nonlocal, or
    pushed into a closed-over container.  The stored object is a
    tracer (or, post-trace, a stale constant from one compilation) —
    it outlives the trace that created it, and jax reports the misuse
    only when the leaked tracer is touched later, far from the leak."""

    id = "SPL009"
    title = "traced value escapes the trace into outer state"
    hint = ("return the value from the jitted function instead of "
            "stashing it on self/globals/closures; host-side logging "
            "belongs outside the traced region")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        out: List[Finding] = []
        seen: Set[int] = set()
        for fn, spec in jit_boundary(ctx).traced:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            out.extend(self._check_traced(ctx, fn, spec))
        return _dedupe(out)

    def _check_traced(self, ctx, fn, spec: JitSpec) -> List[Finding]:
        params = _fn_params(fn)
        static = set(spec.static_argnames) | {
            params[i] for i in spec.static_argnums if i < len(params)}
        tainted: Set[str] = set(params) - static - {"self"}
        if not tainted:
            return []
        body = _body_stmts(fn)
        local: Set[str] = set(params)
        declared_outer: Set[str] = set()
        for s in body:
            for n in ast.walk(s):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                    local.add(n.id)
            if isinstance(s, (ast.Global, ast.Nonlocal)):
                declared_outer.update(s.names)
        local -= declared_outer

        def value_tainted(expr) -> bool:
            return any(name in tainted for name, _ in _expr_loads(expr))

        # taint propagation to a fixpoint (assignments only: the leak
        # verbs below are the sinks, not propagators)
        changed = True
        while changed:
            changed = False
            for s in body:
                targets = []
                if isinstance(s, ast.Assign):
                    targets, value = s.targets, s.value
                elif isinstance(s, (ast.AnnAssign, ast.AugAssign)):
                    targets, value = [s.target], s.value
                elif isinstance(s, (ast.For, ast.AsyncFor)):
                    targets, value = [s.target], s.iter
                else:
                    continue
                if value is None or not value_tainted(value):
                    continue
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and \
                                isinstance(n.ctx, ast.Store) and \
                                n.id not in tainted:
                            tainted.add(n.id)
                            changed = True

        out: List[Finding] = []
        for s in body:
            if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = s.targets if isinstance(s, ast.Assign) \
                    else [s.target]
                value = getattr(s, "value", None)
                if value is None or not value_tainted(value):
                    # a nonlocal/global REBIND leaks even untainted?
                    # no: only traced-derived values are the hazard
                    continue
                for t in targets:
                    base = t.value if isinstance(
                        t, (ast.Attribute, ast.Subscript)) else None
                    if isinstance(base, ast.Name) and (
                            base.id == "self" or base.id not in local):
                        kind = ("self" if base.id == "self"
                                else f"outer object '{base.id}'")
                        out.append(self.finding(
                            ctx, s.lineno,
                            f"traced value stored on {kind} inside "
                            f"jitted '{fn.name}' — the tracer outlives "
                            f"its trace"))
                    elif isinstance(t, ast.Name) and \
                            t.id in declared_outer:
                        out.append(self.finding(
                            ctx, s.lineno,
                            f"traced value assigned to "
                            f"global/nonlocal '{t.id}' inside jitted "
                            f"'{fn.name}' — the tracer outlives its "
                            f"trace"))
            elif isinstance(s, ast.Expr) and isinstance(s.value, ast.Call):
                call = s.value
                f = call.func
                if not (isinstance(f, ast.Attribute)
                        and f.attr in _MUTATORS
                        and isinstance(f.value, ast.Name)):
                    continue
                holder = f.value.id
                if holder in local and holder != "self":
                    continue
                if any(value_tainted(a) for a in call.args) or any(
                        value_tainted(k.value) for k in call.keywords):
                    out.append(self.finding(
                        ctx, s.lineno,
                        f"traced value .{f.attr}()-ed into closed-over "
                        f"container '{holder}' inside jitted "
                        f"'{fn.name}' — the tracer outlives its trace"))
        return out


# -- SPL010 -----------------------------------------------------------------

_ARRAY_MAKERS = {
    "jax.numpy.asarray", "jax.numpy.array", "jax.numpy.zeros",
    "jax.numpy.ones", "jax.numpy.full", "jax.numpy.arange",
    "jax.numpy.empty", "jax.numpy.linspace", "jax.device_put",
    "numpy.asarray", "numpy.array", "numpy.zeros", "numpy.ones",
}

_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.SetComp,
               ast.DictComp, ast.GeneratorExp)


class RecompileTrigger(Rule):
    """Constructs that silently rebuild or re-specialize a compiled
    program: a ``jax.jit`` wrapper created inside a loop (every
    iteration compiles from scratch), a jitted closure capturing a device array from an
    enclosing function (baked into the executable as a constant:
    silent staleness when the array changes, a retrace when the
    closure is rebuilt), and an unhashable literal (list/dict/set)
    passed at a static argnum — a guaranteed ``TypeError`` at call
    time."""

    id = "SPL010"
    title = "recompile/retrace trigger (jit-in-loop, captured array, "\
            "unhashable static)"
    hint = ("hoist the jit wrapper out of the loop (rebuild only on "
            "demotion — the build_sweep factory pattern); pass device "
            "arrays as arguments, not closure captures; static args "
            "must be hashable (tuples, not lists)")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        out: List[Finding] = []
        out += self._jit_in_loop(ctx)
        out += self._captured_arrays(ctx)
        out += self._unhashable_statics(ctx)
        return _dedupe(out)

    # - (a) jit constructed inside a loop -

    def _jit_in_loop(self, ctx) -> List[Finding]:
        out = []

        def walk(node, depth):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                for child in ast.iter_child_nodes(node):
                    walk(child, 0)  # new scope: built when called
                return
            if isinstance(node, (ast.For, ast.AsyncFor)):
                # target/iter evaluate once per loop ENTRY; only the
                # body (and a while test) re-run per iteration
                walk(node.target, depth)
                walk(node.iter, depth)
                for s in node.body:
                    walk(s, depth + 1)
                for s in node.orelse:
                    walk(s, depth)
                return
            if isinstance(node, ast.While):
                walk(node.test, depth + 1)
                for s in node.body:
                    walk(s, depth + 1)
                for s in node.orelse:
                    walk(s, depth)
                return
            if isinstance(node, ast.Call) and depth > 0 \
                    and jit_call_spec(ctx, node) is not None:
                out.append(self.finding(
                    ctx, node.lineno,
                    "jax.jit wrapper constructed inside a loop — "
                    "every iteration pays a fresh trace+compile"))
            for child in ast.iter_child_nodes(node):
                walk(child, depth)

        walk(ctx.tree, 0)
        return out

    # - (b) jitted closure capturing an enclosing-scope device array -

    def _captured_arrays(self, ctx) -> List[Finding]:
        jb = jit_boundary(ctx)
        traced_ids = {id(fn) for fn, _ in jb.traced}
        out = []

        def array_bindings(fn) -> Dict[str, int]:
            binds = {}
            for s in _body_stmts(fn):
                if not (isinstance(s, ast.Assign)
                        and isinstance(s.value, ast.Call)):
                    continue
                if (ctx.resolve(s.value.func) or "") not in _ARRAY_MAKERS:
                    continue
                for t in s.targets:
                    if isinstance(t, ast.Name):
                        binds[t.id] = s.lineno
            return binds

        def visit(fn, outer_binds: Dict[str, int]):
            binds = dict(outer_binds, **array_bindings(fn))
            for sub in nested_defs(fn):
                if id(sub) in traced_ids:
                    for name in sorted(free_reads(sub) & set(binds)):
                        out.append(self.finding(
                            ctx, sub.lineno,
                            f"jitted '{sub.name}' closes over device "
                            f"array '{name}' (materialized at line "
                            f"{binds[name]}) — baked into the trace "
                            f"as a constant"))
                visit(sub, binds)

        for fn in scope_functions(ctx.tree):
            visit(fn, {})
        return out

    # - (c) unhashable literal at a static argnum -

    def _unhashable_statics(self, ctx) -> List[Finding]:
        jb = jit_boundary(ctx)
        out = []

        def analyze(fn, env):
            env = dict(env)
            body = _body_stmts(fn)
            # bindings first (flow-insensitively), then the call scan —
            # statement order must not hide a wrapper from its calls
            for s in body:
                if (isinstance(s, ast.Assign) and len(s.targets) == 1
                        and isinstance(s.targets[0], ast.Name)):
                    spec = callable_jit_spec(ctx, s.value, env,
                                             jb.factories)
                    if spec is not None:
                        env[s.targets[0].id] = spec
            for s in body:
                for call in ast.walk(s):
                    if not (isinstance(call, ast.Call)
                            and isinstance(call.func, ast.Name)):
                        continue
                    spec = env.get(call.func.id)
                    if spec is None:
                        continue
                    for i in sorted(spec.static_argnums):
                        if i < len(call.args) and isinstance(
                                call.args[i], _UNHASHABLE):
                            out.append(self.finding(
                                ctx, call.lineno,
                                f"unhashable literal at static argnum "
                                f"{i} of jitted '{call.func.id}' — "
                                f"TypeError at call time"))
                    for kw in call.keywords:
                        if kw.arg in spec.static_argnames and \
                                isinstance(kw.value, _UNHASHABLE):
                            out.append(self.finding(
                                ctx, call.lineno,
                                f"unhashable literal for static arg "
                                f"'{kw.arg}' of jitted "
                                f"'{call.func.id}' — TypeError at "
                                f"call time"))
            for sub in nested_defs(fn):
                analyze(sub, env)

        for fn in scope_functions(ctx.tree):
            analyze(fn, jb.wrapped)
        return out


# -- SPL011 -----------------------------------------------------------------

_IO_PATH_METHODS = {"open", "read_text", "write_text", "read_bytes",
                    "write_bytes", "unlink", "rename", "replace"}
_IO_OS_FNS = {"os.replace", "os.rename", "os.remove", "os.unlink",
              "shutil.move", "shutil.copy"}


class CacheLockDiscipline(Rule):
    """Raw IO on the shared probe/tune JSON cache files outside the
    locked helpers.  Two processes proving kernels or tuning plans
    share one cache file; only ``_json_cache_update`` (flock +
    atomic-replace read-modify-write) and ``_json_cache_load`` (the
    degrading read side) uphold the concurrency and best-effort
    contracts — an inline ``open(cache_path())``/``json.dump`` can
    drop concurrent writers' entries or crash dispatch on a corrupt
    file.  Detection is dataflow-based: any value derived from a
    configured cache-path function that reaches an IO verb is
    flagged.  Known imprecision: a helper that receives the path as a
    parameter is trusted (that is the sanctioned chokepoint shape)."""

    id = "SPL011"
    title = "cache-file IO bypasses the locked cache helpers"
    hint = ("route writes through pallas_kernels._json_cache_update "
            "and reads through _json_cache_load (tune.py and the "
            "probe cache share them); see docs/autotune.md")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        cfg = project.config
        path_fns = set(cfg.cache_path_functions)
        helpers = set(cfg.cache_io_helpers)
        if not path_fns:
            return []
        out: List[Finding] = []

        def is_path_call(node) -> bool:
            return (isinstance(node, ast.Call)
                    and (ctx.resolve(node.func) or ""
                         ).split(".")[-1] in path_fns)

        def scope(stmts, fname: str) -> None:
            if fname in helpers:
                return
            tainted: Set[str] = set()
            flat: List[ast.stmt] = []
            for s in stmts:
                flat.append(s)
                if not isinstance(s, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.ClassDef)):
                    flat.extend(c for c in ast.walk(s)
                                if isinstance(c, ast.stmt)
                                and c is not s)

            def expr_tainted(expr) -> bool:
                if any(is_path_call(n) for n in ast.walk(expr)):
                    return True
                return any(n in tainted for n, _ in _expr_loads(expr))

            changed = True
            while changed:
                changed = False
                for s in flat:
                    pairs = []
                    if isinstance(s, ast.Assign):
                        pairs = [(t, s.value) for t in s.targets]
                    elif isinstance(s, (ast.With, ast.AsyncWith)):
                        pairs = [(i.optional_vars, i.context_expr)
                                 for i in s.items if i.optional_vars]
                    for t, v in pairs:
                        if not expr_tainted(v):
                            continue
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name) and \
                                    isinstance(n.ctx, ast.Store) and \
                                    n.id not in tainted:
                                tainted.add(n.id)
                                changed = True
            for s in flat:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                for call in ast.walk(s):
                    if not isinstance(call, ast.Call):
                        continue
                    dotted = ctx.resolve(call.func) or ""
                    hit = None
                    if dotted == "open" and call.args and \
                            expr_tainted(call.args[0]):
                        hit = "open()"
                    elif isinstance(call.func, ast.Attribute) and \
                            call.func.attr in _IO_PATH_METHODS and \
                            expr_tainted(call.func.value):
                        hit = f".{call.func.attr}()"
                    elif dotted in _IO_OS_FNS and any(
                            expr_tainted(a) for a in call.args):
                        hit = dotted
                    if hit:
                        out.append(self.finding(
                            ctx, call.lineno,
                            f"direct {hit} on the shared cache file "
                            f"bypasses the locked cache helpers"))
            for s in flat:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    scope(s.body, s.name)
                elif isinstance(s, ast.ClassDef):
                    # class bodies hold methods (their own scopes) and
                    # occasionally class-level statements
                    scope(s.body, f"<class {s.name}>")

        module_stmts = [s for s in ctx.tree.body]
        scope(module_stmts, "<module>")
        return _dedupe(out)


# -- SPL012 -----------------------------------------------------------------

def _declared_registry(ctx: FileCtx, registry: str) -> Dict[str, int]:
    """String keys (-> line) of a module-level ``REGISTRY = {...}``."""
    for node in walk_nodes(ctx.tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == registry
                and isinstance(node.value, ast.Dict)):
            return {k.value: k.lineno for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    return {}


class RunReportEventDrift(Rule):
    """Run-report event drift: every event kind the code emits via
    ``run_report().add("<kind>", ...)`` must be declared (with a doc)
    in the resilience module's RUN_REPORT_EVENTS registry, and every
    declared kind must still be emitted somewhere.  The run report is
    the observability surface for silent degradation — an undocumented
    event is invisible to operators reading the docs, and a declared-
    but-never-emitted one is a dead promise (usually a renamed
    emission site)."""

    id = "SPL012"
    title = "run-report event drift against resilience.py:" \
            "RUN_REPORT_EVENTS"
    hint = ("declare the event kind (with a one-line doc) in "
            "splatt_tpu/resilience.py:RUN_REPORT_EVENTS; docs render "
            "from that registry")

    def finalize(self, project: Project) -> List[Finding]:
        cfg = project.config
        res_ctx = project.ctx_for(cfg.resilience_module)
        if res_ctx is None:
            return []
        declared = _declared_registry(res_ctx, "RUN_REPORT_EVENTS")
        if not declared:
            return []  # registry-less mini-projects: nothing to check
        out: List[Finding] = []
        emitted: Set[str] = set()
        for ctx in project.files + (
                [res_ctx] if res_ctx not in project.files else []):
            for kind, line in self._emissions(ctx):
                if kind is None:
                    out.append(self.finding(
                        ctx, line,
                        "run-report event kind is not statically "
                        "resolvable — splint cannot check it against "
                        "RUN_REPORT_EVENTS"))
                    continue
                emitted.add(kind)
                if kind not in declared and ctx in project.files:
                    out.append(self.finding(
                        ctx, line,
                        f"run-report event '{kind}' is not declared "
                        f"in {cfg.resilience_module}:RUN_REPORT_EVENTS"))
        for kind, line in declared.items():
            if kind not in emitted:
                out.append(self.finding(
                    res_ctx, line,
                    f"declared run-report event '{kind}' is never "
                    f"emitted — dead declaration or renamed emission "
                    f"site"))
        return out

    @staticmethod
    def _emissions(ctx: FileCtx) -> List[Tuple[Optional[str], int]]:
        def is_run_report_call(node) -> bool:
            return (isinstance(node, ast.Call)
                    and (ctx.resolve(node.func) or ""
                         ).split(".")[-1] == "run_report")

        # names bound to the report object: rr = run_report()
        report_names: Set[str] = set()
        for node in walk_nodes(ctx.tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and is_run_report_call(node.value)):
                report_names.add(node.targets[0].id)
        out = []
        for node in walk_nodes(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add"):
                continue
            base = node.func.value
            if not (is_run_report_call(base)
                    or (isinstance(base, ast.Name)
                        and base.id in report_names)):
                continue
            arg = node.args[0] if node.args else None
            kind: Optional[str] = None
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                kind = arg.value
            elif isinstance(arg, ast.Name):
                kind = ctx.str_consts.get(arg.id)
            out.append((kind, node.lineno))
        return out


# -- SPL013 -----------------------------------------------------------------

_SPAN_FNS = {"span", "begin"}


def _span_opens(ctx: FileCtx, is_trace_module: bool
                ) -> List[Tuple[Optional[str], int]]:
    """(name, lineno) for every span-opening call in `ctx`: the literal
    string, 'prefix.*' for an f-string with a literal prefix, or None
    when not statically resolvable.  ``trace.span(...)``/
    ``trace.begin(...)`` everywhere; inside the trace module itself the
    bare ``span(...)``/``begin(...)`` spellings count too (the module
    opens its own ``trace.export`` span)."""
    out = []
    for node in walk_nodes(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        tail = dotted.split(".")[-1]
        if tail not in _SPAN_FNS:
            continue
        if not ("trace" in dotted.split(".")[:-1]
                or (is_trace_module and dotted == tail)):
            continue
        arg = node.args[0] if node.args else None
        name: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        elif isinstance(arg, ast.Name):
            name = ctx.str_consts.get(arg.id)
        elif isinstance(arg, ast.JoinedStr) and arg.values:
            first = arg.values[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) and first.value:
                name = first.value + "*"
        out.append((name, node.lineno))
    return out


class SpanNameDrift(Rule):
    """Span-name drift: every name production code opens a trace span
    under (``trace.span("...")`` / ``trace.begin("...")``) must be
    declared in the trace module's SPANS registry — the catalog
    docs/observability.md renders and ``splatt trace`` summaries are
    read against — and every declared name must still be opened
    somewhere in production.  A renamed span otherwise silently orphans
    the queries and dashboards built on it, exactly like a renamed
    fault site (SPL006) or run-report event (SPL012).  A trailing
    ``.*`` declares an f-string family (``trace.span(f"timer.{n}")``
    matches a declared ``timer.*``)."""

    id = "SPL013"
    title = "span-name drift against trace.py:SPANS"
    hint = ("declare the span name (with a one-line doc) in "
            "splatt_tpu/trace.py:SPANS; docs/observability.md renders "
            "from that registry")

    def finalize(self, project: Project) -> List[Finding]:
        cfg = project.config
        trace_ctx = project.ctx_for(cfg.trace_module)
        if trace_ctx is None:
            return []
        declared = _declared_registry(trace_ctx, "SPANS")
        if not declared:
            return []  # registry-less mini-projects: nothing to check
        out: List[Finding] = []
        used: Set[str] = set()
        ctxs = project.files + ([trace_ctx]
                                if trace_ctx not in project.files else [])
        for ctx in ctxs:
            in_trace = ctx.relpath == cfg.trace_module
            for name, line in _span_opens(ctx, in_trace):
                if name is None:
                    # the trace module's own API helpers forward the
                    # caller's name (begin() -> span(name)); those are
                    # the sanctioned chokepoints, not open sites
                    if not in_trace:
                        out.append(self.finding(
                            ctx, line,
                            "span name is not statically resolvable — "
                            "splint cannot check it against "
                            "trace.SPANS"))
                    continue
                used.add(name)
                if not any(_site_matches(d, name) for d in declared) \
                        and ctx in project.files:
                    out.append(self.finding(
                        ctx, line,
                        f"span name '{name}' is not declared in "
                        f"{cfg.trace_module}:SPANS"))
        for d, line in declared.items():
            if not any(_site_matches(d, u) for u in used):
                out.append(self.finding(
                    trace_ctx, line,
                    f"declared span name '{d}' is never opened — dead "
                    f"declaration or renamed span"))
        return out


# -- SPL029 -----------------------------------------------------------------

#: the metric-recording verbs, each bound to the one sample type it
#: may record (trace.py raises on the mismatch at runtime; SPL029
#: catches it before anything runs)
_METRIC_FNS = {"metric_inc": "counter", "metric_set": "gauge",
               "metric_observe": "histogram"}


def _declared_metric_types(ctx: FileCtx) -> Dict[str, Tuple[Optional[str], int]]:
    """name -> (declared type, line) of the trace module's
    ``METRICS = {"name": ("type", "doc"), ...}`` registry."""
    for node in walk_nodes(ctx.tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "METRICS"
                and isinstance(node.value, ast.Dict)):
            out: Dict[str, Tuple[Optional[str], int]] = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                typ = None
                if isinstance(v, ast.Tuple) and v.elts and \
                        isinstance(v.elts[0], ast.Constant):
                    typ = str(v.elts[0].value)
                out[k.value] = (typ, k.lineno)
            return out
    return {}


def _metric_emissions(ctx: FileCtx, is_trace_module: bool
                      ) -> List[Tuple[Optional[str], str, int]]:
    """(name, verb, lineno) for every ``trace.metric_inc/metric_set/
    metric_observe`` call in `ctx` (bare spellings inside the trace
    module itself count too — _event_metrics records there)."""
    out = []
    for node in walk_nodes(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        tail = dotted.split(".")[-1]
        if tail not in _METRIC_FNS:
            continue
        if not ("trace" in dotted.split(".")[:-1]
                or (is_trace_module and dotted == tail)):
            continue
        arg = node.args[0] if node.args else None
        name: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        elif isinstance(arg, ast.Name):
            name = ctx.str_consts.get(arg.id)
        out.append((name, tail, node.lineno))
    return out


class MetricNameDrift(Rule):
    """Metric-name drift: every name the code records through
    ``trace.metric_inc``/``metric_set``/``metric_observe`` must be
    declared in the trace module's METRICS registry — with the verb
    matching the declared type (incrementing a gauge would raise at
    runtime; here it is a finding before anything runs) — and every
    declared metric must still be recorded somewhere.  The docs
    metrics table ([tool.splint] ``metrics-doc``) is checked in both
    directions too: a declared metric missing from the docs is
    invisible to operators, and a documented-but-undeclared one is a
    dead promise.  The SPL013 span-name discipline, applied to the
    Prometheus surface that dashboards and the fleet aggregator are
    built on (docs/observability.md)."""

    id = "SPL029"
    title = "metric-name drift against trace.py:METRICS / the docs table"
    hint = ("declare the metric (name -> (type, doc)) in "
            "splatt_tpu/trace.py:METRICS and add its row to the docs "
            "metrics table; the registry is the exposition contract")

    def finalize(self, project: Project) -> List[Finding]:
        import re as _re

        cfg = project.config
        trace_ctx = project.ctx_for(cfg.trace_module)
        if trace_ctx is None:
            return []
        declared = _declared_metric_types(trace_ctx)
        if not declared:
            return []  # registry-less mini-projects: nothing to check
        out: List[Finding] = []
        used: Set[str] = set()
        ctxs = project.files + ([trace_ctx]
                                if trace_ctx not in project.files else [])
        for ctx in ctxs:
            in_trace = ctx.relpath == cfg.trace_module
            for name, verb, line in _metric_emissions(ctx, in_trace):
                if name is None:
                    if not in_trace and ctx in project.files:
                        out.append(self.finding(
                            ctx, line,
                            "metric name is not statically resolvable "
                            "— splint cannot check it against "
                            "trace.METRICS"))
                    continue
                used.add(name)
                if name not in declared:
                    if ctx in project.files:
                        out.append(self.finding(
                            ctx, line,
                            f"metric '{name}' is not declared in "
                            f"{cfg.trace_module}:METRICS"))
                    continue
                want = declared[name][0]
                if want and _METRIC_FNS[verb] != want \
                        and ctx in project.files:
                    out.append(self.finding(
                        ctx, line,
                        f"metric '{name}' is declared as a {want} but "
                        f"recorded via {verb} (the "
                        f"{_METRIC_FNS[verb]} verb) — this raises at "
                        f"runtime"))
        for name, (typ, line) in declared.items():
            if name not in used:
                out.append(self.finding(
                    trace_ctx, line,
                    f"declared metric '{name}' is never recorded — "
                    f"dead declaration or renamed emission site"))
        # the docs table, both directions (skipped when the configured
        # doc does not exist — fixture mini-projects)
        doc_path = (cfg.resolve(cfg.metrics_doc)
                    if getattr(cfg, "metrics_doc", "") else None)
        if doc_path is not None and doc_path.exists():
            text = doc_path.read_text()
            table_names = set()
            for line_txt in text.splitlines():
                if line_txt.lstrip().startswith("|"):
                    table_names.update(
                        _re.findall(r"splatt_[a-z0-9_]+", line_txt))
            for name, (typ, line) in declared.items():
                # membership is judged against TABLE rows, not prose:
                # a metric merely name-dropped in body text is still
                # missing its row
                if name not in table_names:
                    out.append(self.finding(
                        trace_ctx, line,
                        f"declared metric '{name}' has no row in "
                        f"{cfg.metrics_doc} — the metrics table "
                        f"renders from the registry"))
            for name in sorted(table_names - set(declared)):
                out.append(self.finding(
                    trace_ctx, 1,
                    f"{cfg.metrics_doc} documents metric '{name}' "
                    f"which {cfg.trace_module}:METRICS never declares "
                    f"— a dead promise to operators"))
        return out


# -- SPL014 -----------------------------------------------------------------

#: method names that mutate a container in place (the write verbs the
#: shared-state rule guards, alongside subscript/attribute stores)
_CONTAINER_MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "popitem", "clear",
    "update", "setdefault", "add", "discard", "appendleft", "popleft",
    "sort", "reverse",
}


def _parse_shared_state(entries) -> Dict[str, List[Tuple[str, str]]]:
    """Config entries ``relpath::target=lock`` → {relpath: [(target,
    lock)]}; malformed entries raise (a typo'd map must fail loudly,
    not silently unguard a structure)."""
    out: Dict[str, List[Tuple[str, str]]] = {}
    for entry in entries:
        try:
            loc, lock = entry.split("=", 1)
            rel, target = loc.split("::", 1)
        except ValueError:
            raise ValueError(
                f"splint: bad shared-state entry {entry!r} (want "
                f"'relpath::target=lock')")
        out.setdefault(rel, []).append((target.strip(), lock.strip()))
    return out


def _struct_root(expr) -> object:
    """The root object being stored into: peel subscripts off an
    assignment target (``self._jobs[jid]["state"]`` → the
    ``self._jobs`` attribute node)."""
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    return expr


def _matches_target(expr, target: str) -> bool:
    """Whether an expression names the configured structure: a bare
    ``NAME`` for module globals, ``self.attr`` for instance state."""
    if target.startswith("self."):
        return (isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and expr.attr == target[5:])
    return isinstance(expr, ast.Name) and expr.id == target


def _required_lock(rel: str, cls: Optional[str], lock: str) -> str:
    """The canonical id the configured guard spelling must resolve to
    at a mutation site inside class `cls`."""
    if lock.startswith("self."):
        return f"{rel}::{cls}.{lock[5:]}"
    return f"{rel}::{lock}"


class SharedStateWithoutLock(Rule):
    """A write to a declared shared structure without its owning lock
    held.  The ``[tool.splint] shared-state`` map records which lock
    guards which structure (the Server job table and queue, the fleet
    lease maps, tune's plan memo, trace's span/metric registries); the
    lock-set analysis (tools/splint/locks.py) proves each mutation
    site holds it.  Functions whose name ends in ``_locked`` are the
    caller-owns-the-lock convention and are exempt, as is ``__init__``
    (the object is not yet shared).  Known imprecision: aliases
    (``j = self._jobs[jid]``) and container elements are not tracked —
    the SPLATT_LOCKCHECK runtime sanitizer is the dynamic
    cross-check."""

    id = "SPL014"
    title = "shared-state write without the owning lock"
    hint = ("take the configured guard lock around the mutation (or "
            "move it into a '*_locked' helper whose callers hold it); "
            "the [tool.splint] shared-state map names the owner")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        from tools.splint.locks import (FileLocks, iter_scope_functions,
                                        lock_walk)

        entries = _parse_shared_state(
            project.config.shared_state).get(ctx.relpath)
        if not entries:
            return []
        fl = FileLocks(ctx)
        out: List[Finding] = []

        def scan(fn, cls):
            if fn.name == "__init__" or fn.name.endswith("_locked"):
                return
            nested: List[Tuple[object, object]] = []
            walk = lock_walk(ctx, fn, cls, fl,
                             on_nested=lambda sub, held:
                             nested.append((sub, cls)))
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.stmt):
                    continue
                held = walk.held_at.get(id(stmt))
                if held is None:
                    continue  # nested-def body: scanned on its own
                for target, lock, line in self._mutations(stmt, entries):
                    need = _required_lock(ctx.relpath, cls, lock)
                    if need not in held:
                        out.append(self.finding(
                            ctx, line,
                            f"write to shared '{target}' without "
                            f"holding its owning lock '{lock}' "
                            f"(declared in [tool.splint] "
                            f"shared-state)"))
            for sub, subcls in nested:
                scan(sub, subcls)

        for fn, cls in iter_scope_functions(ctx.tree):
            scan(fn, cls)
        return _dedupe(out)

    @staticmethod
    def _mutations(stmt, entries) -> List[Tuple[str, str, int]]:
        """(target, lock, line) for each configured-structure write in
        ONE statement.  Simple statements are scanned whole (a mutator
        call anywhere in them — ``jid = self._queue.pop(0)``, a return
        value, a boolean test — is still a mutation); compound
        statements contribute only their HEADER expressions, because
        their bodies are separate statements the caller visits with
        their own (possibly larger) lock sets."""
        out = []

        def hit(expr, line):
            for target, lock in entries:
                if _matches_target(expr, target):
                    out.append((target, lock, line))

        def scan_calls(root, line):
            for call in ast.walk(root):
                if isinstance(call, ast.Call) and \
                        isinstance(call.func, ast.Attribute) and \
                        call.func.attr in _CONTAINER_MUTATORS:
                    hit(_struct_root(call.func.value), line)

        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = stmt.targets if isinstance(stmt, ast.Assign) \
                else [stmt.target]
            for t in targets:
                root = _struct_root(t)
                if isinstance(t, ast.Subscript):
                    hit(root, stmt.lineno)      # X[k] = ... mutates X
                elif isinstance(stmt, ast.AugAssign):
                    hit(root, stmt.lineno)      # X += ... rebinds X
                else:
                    # a direct rebind swaps the shared object under
                    # concurrent readers — same owner, same lock
                    hit(root, stmt.lineno)
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Subscript):
                    hit(_struct_root(t), stmt.lineno)
        if isinstance(stmt, (ast.If, ast.While)):
            scan_calls(stmt.test, stmt.lineno)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            scan_calls(stmt.iter, stmt.lineno)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                scan_calls(item.context_expr, stmt.lineno)
        elif isinstance(stmt, ast.Try):
            pass  # no header expression of its own
        elif not isinstance(stmt, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
            scan_calls(stmt, stmt.lineno)
        return out


# -- SPL015 -----------------------------------------------------------------

class LockOrderCycle(Rule):
    """A cycle in the project-wide lock acquisition graph: somewhere
    lock A is taken while B is held and somewhere else B while A is
    held — two threads walking the two sites deadlock.  Edges come
    from the lock-set analysis: direct nesting (``with a: with b:``,
    including flock sidecars entered via contextmanager wrappers) and
    call sites under a held lock, resolved through the conservative
    call summaries of tools/splint/locks.py.  A self-loop — taking a
    non-reentrant lock while already holding it — is the degenerate
    cycle and deadlocks a single thread.  The in-process-lock-before-
    flock nesting of the cache/journal writers and the flock-before-
    in-process nesting of the fleet lease protocol stay consistent
    exactly because this graph is kept acyclic."""

    id = "SPL015"
    title = "lock-order cycle in the acquisition graph"
    hint = ("pick ONE global order for the locks in the cycle and "
            "re-nest the offending site (usually: move the inner "
            "acquisition out of the outer lock's critical section)")

    def finalize(self, project: Project) -> List[Finding]:
        from tools.splint.locks import project_locks

        pl = project_locks(project)
        edges = pl.order_edges()
        out: List[Finding] = []
        for cycle in pl.cycles():
            pairs = list(zip(cycle, cycle[1:]))
            rel, line = edges[pairs[0]]
            path = " -> ".join(c.split("::", 1)[-1] for c in cycle)
            sites = "; ".join(
                f"{edges[p][0]}:{edges[p][1]} takes "
                f"{p[1].split('::', 1)[-1]} under "
                f"{p[0].split('::', 1)[-1]}" for p in pairs)
            out.append(self.finding(
                rel, line,
                f"lock-order cycle {path} ({sites})"))
        return out


# -- SPL016 -----------------------------------------------------------------

_WRITE_MODES = {"w", "wb", "x", "xb", "w+", "wb+", "w+b"}
_APPEND_MODES = {"a", "ab", "a+", "ab+", "a+b"}
_TMP_WRITERS = {"numpy.savez", "numpy.savez_compressed", "numpy.save"}


class DurabilityProtocolDrift(Rule):
    """A durable-write protocol verb outside the sanctioned helpers
    (splatt_tpu/utils/durable.py; ``[tool.splint]``
    durable-write-helpers): an ``os.fsync``, a tmp-write→``os.replace``
    publish (an ``os.replace`` whose source this function itself wrote
    — claim/.bak renames of existing files are a different verb and
    stay clean), or a written append-mode ``open``.  Every journal
    line, lease, checkpoint, cache file and metrics snapshot must go
    through the one helper so the fsync/heal/atomic-rename discipline
    cannot drift per call site — the hand-rolled copies this rule
    replaced disagreed about fsync."""

    id = "SPL016"
    title = "durable write outside the sanctioned durable-write helpers"
    hint = ("route the write through splatt_tpu.utils.durable "
            "(publish_bytes/publish_json/publish_file for atomic "
            "publishes, append_line for durable appends)")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        helpers = set(project.config.durable_write_helpers)
        if not helpers:
            return []
        out: List[Finding] = []
        for fn in _all_functions(ctx.tree):
            if fn.name in helpers:
                continue
            out.extend(self._scan_fn(ctx, fn))
        return _dedupe(out)

    def _scan_fn(self, ctx, fn) -> List[Finding]:
        out: List[Finding] = []
        written: Set[str] = set()   # names holding a locally-written tmp
        appended: Dict[str, int] = {}  # append-mode file object names
        wrote_to: Set[str] = set()

        def mode_of(call) -> Optional[str]:
            if len(call.args) > 1 and isinstance(call.args[1],
                                                 ast.Constant):
                return str(call.args[1].value)
            for kw in call.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    return str(kw.value.value)
            return None

        body = [s for s in _body_stmts(fn)
                if not isinstance(s, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.ClassDef))]
        for s in body:
            for call in ast.walk(s):
                if not isinstance(call, ast.Call):
                    continue
                dotted = ctx.resolve(call.func) or ""
                # (a) fsync is the durability verb itself
                if dotted == "os.fsync":
                    out.append(self.finding(
                        ctx, call.lineno,
                        "os.fsync outside the sanctioned durable-write "
                        "helpers"))
                # track written-tmp names
                if dotted == "open" and call.args:
                    mode = mode_of(call)
                    argnames = {n.id for n in ast.walk(call.args[0])
                                if isinstance(n, ast.Name)}
                    if mode in _WRITE_MODES:
                        written.update(argnames)
                    elif mode in _APPEND_MODES:
                        for name in argnames:
                            appended[name] = call.lineno
                if dotted in _TMP_WRITERS and call.args:
                    written.update(n.id for n in ast.walk(call.args[0])
                                   if isinstance(n, ast.Name))
                if isinstance(call.func, ast.Attribute) and \
                        call.func.attr in ("write_text", "write_bytes") \
                        and isinstance(call.func.value, ast.Name):
                    written.add(call.func.value.id)
            if isinstance(s, ast.Assign) and isinstance(s.value, ast.Call):
                vdot = (ctx.resolve(s.value.func) or "")
                if vdot.split(".")[-1] == "mkstemp":
                    # fd, tmp = tempfile.mkstemp(...): the tmp path is
                    # a locally-written temp by construction
                    for t in s.targets:
                        written.update(n.id for n in ast.walk(t)
                                       if isinstance(n, ast.Name)
                                       and isinstance(n.ctx, ast.Store))
        # which bound file objects actually got .write()?
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "write" and \
                    isinstance(node.func.value, ast.Name):
                wrote_to.add(node.func.value.id)
        # with open(p, "ab") as f: ... f.write(...) — map the file
        # object back to the opened path name
        for node in ast.walk(fn):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                cexpr = item.context_expr
                if not (isinstance(cexpr, ast.Call)
                        and (ctx.resolve(cexpr.func) or "") == "open"
                        and cexpr.args):
                    continue
                mode = None
                if len(cexpr.args) > 1 and isinstance(cexpr.args[1],
                                                      ast.Constant):
                    mode = str(cexpr.args[1].value)
                if mode in _APPEND_MODES and item.optional_vars is not None:
                    fname = getattr(item.optional_vars, "id", None)
                    if fname in wrote_to:
                        out.append(self.finding(
                            ctx, cexpr.lineno,
                            "hand-rolled durable append (append-mode "
                            "open + write) outside the sanctioned "
                            "helpers"))
        # (b) publishing a locally-written tmp by rename
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dotted = ctx.resolve(node.func) or ""
            src = None
            if dotted in ("os.replace", "os.rename", "shutil.move") \
                    and node.args:
                src = node.args[0]
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("replace", "rename") and \
                    isinstance(node.func.value, ast.Name) and node.args:
                src = node.func.value
            if src is None:
                continue
            names = {n.id for n in ast.walk(src)
                     if isinstance(n, ast.Name)}
            if names & written:
                out.append(self.finding(
                    ctx, node.lineno,
                    "hand-rolled tmp-write -> rename publish outside "
                    "the sanctioned durable-write helpers"))
        return out


# -- SPL017 -----------------------------------------------------------------

class BlockingCallUnderLock(Rule):
    """A blocking call — fsync, flock, sleep, a thread join, an Event
    wait, a subprocess — made while an in-process lock is held, on a
    configured control-plane hot path ([tool.splint] hot-lock-paths).
    Every status poll, submission and worker dequeue serializes on
    these locks: one fsync inside the critical section stalls the
    whole daemon's control plane (the PR 11 submit fix — decide under
    the lock, do the durable IO outside it — made permanent).  Calls
    are checked transitively through the conservative call summaries,
    so ``self.journal.append(...)`` under the server lock is caught
    even though the fsync is two frames down."""

    id = "SPL017"
    title = "blocking call while holding an in-process lock (hot path)"
    hint = ("decide under the lock, perform the blocking IO outside "
            "it (serve.submit's ACCEPTING-reservation pattern), or "
            "drop the path from hot-lock-paths with a justification")

    def finalize(self, project: Project) -> List[Finding]:
        from tools.splint.locks import (_blocking_verb, is_flock_id,
                                        project_locks)

        hot = set(project.config.hot_lock_paths)
        if not hot:
            return []
        pl = project_locks(project)
        out: List[Finding] = []
        for key, (ctx, fn, cls) in pl.functions.items():
            if f"{ctx.relpath}::{fn.name}" not in hot:
                continue
            walk = pl.walk_of(key)
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.stmt):
                    continue
                held = walk.held_at.get(id(stmt))
                if held is None:
                    continue
                held = {h for h in held if not is_flock_id(h)}
                if not held:
                    continue
                for call in ast.walk(stmt):
                    if not isinstance(call, ast.Call):
                        continue
                    verb = _blocking_verb(ctx, call)
                    via = None
                    if verb is None:
                        for callee in pl.call_targets(ctx, cls, call):
                            blocked = pl.blocks(callee)
                            if blocked:
                                verb = sorted(blocked)[0]
                                via = callee.split("::", 1)[-1]
                                break
                    if verb is None:
                        continue
                    lock = sorted(held)[0].split("::", 1)[-1]
                    how = f" (via {via})" if via else ""
                    out.append(self.finding(
                        ctx, call.lineno,
                        f"blocking {verb}{how} while holding "
                        f"'{lock}' on hot path '{fn.name}' — the "
                        f"control plane stalls behind it"))
        return _dedupe(out)


# -- SPL018 -----------------------------------------------------------------

class ContextvarLeak(Rule):
    """A ``ContextVar.set`` whose reset is not crash-safe: the token is
    discarded, or the matching ``reset(token)`` is not inside the
    ``finally`` of the try that immediately guards the scoped region.
    The per-job isolation machinery (``resilience.scope``,
    ``faults.scoped``, trace's ``enabling``) all stack per-tenant
    state in contextvars — a set that an exception can strand leaks
    one tenant's demotions, fault schedule or trace toggle into the
    next job that reuses the context.  The sanctioned idiom::

        token = VAR.set(value)
        try:
            ...
        finally:
            VAR.reset(token)

    ``__enter__``/``__exit__`` method bodies are exempt (the pairing
    spans two functions — trace's span-stack push/pop — which this
    single-function analysis cannot see; documented imprecision)."""

    id = "SPL018"
    title = "ContextVar.set without a try/finally reset"
    hint = ("bind the token and reset it in the finally of the very "
            "next try block (resilience.scope is the exemplar); for "
            "__enter__/__exit__ pairs keep the reset in __exit__")

    def check(self, ctx: FileCtx, project: Project) -> List[Finding]:
        ctxvars: Set[str] = set()
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and (ctx.resolve(node.value.func) or "") \
                    == "contextvars.ContextVar":
                ctxvars.add(node.targets[0].id)
        if not ctxvars:
            return []
        out: List[Finding] = []
        for fn in _all_functions(ctx.tree):
            if fn.name in ("__enter__", "__exit__"):
                continue
            self._scan_body(ctx, fn.body, ctxvars, out)
        return _dedupe(out)

    def _is_set(self, ctx, expr, ctxvars) -> Optional[str]:
        if isinstance(expr, ast.Call) and \
                isinstance(expr.func, ast.Attribute) and \
                expr.func.attr == "set" and \
                isinstance(expr.func.value, ast.Name) and \
                expr.func.value.id in ctxvars:
            return expr.func.value.id
        return None

    def _scan_body(self, ctx, body, ctxvars, out) -> None:
        for i, stmt in enumerate(body):
            # recurse into nested blocks
            for attr in ("body", "orelse", "finalbody"):
                nested = getattr(stmt, attr, None)
                if isinstance(nested, list) and not isinstance(
                        stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                    self._scan_body(ctx, nested, ctxvars, out)
            for h in getattr(stmt, "handlers", []):
                self._scan_body(ctx, h.body, ctxvars, out)
            # a bare set expression discards the token outright
            if isinstance(stmt, ast.Expr):
                var = self._is_set(ctx, stmt.value, ctxvars)
                if var is not None:
                    out.append(self.finding(
                        ctx, stmt.lineno,
                        f"{var}.set(...) discards its reset token — "
                        f"the previous context value is "
                        f"unrestorable"))
                continue
            if not (isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                continue
            var = self._is_set(ctx, stmt.value, ctxvars)
            if var is None:
                continue
            token = stmt.targets[0].id
            nxt = body[i + 1] if i + 1 < len(body) else None
            if not (isinstance(nxt, ast.Try)
                    and self._resets(nxt.finalbody, var, token)):
                out.append(self.finding(
                    ctx, stmt.lineno,
                    f"{var}.set(...) is not guarded by an immediate "
                    f"try/finally {var}.reset({token}) — an exception "
                    f"here leaks the scoped state into the next job "
                    f"on this context"))

    @staticmethod
    def _resets(finalbody, var: str, token: str) -> bool:
        for s in finalbody:
            for call in ast.walk(s):
                if isinstance(call, ast.Call) and \
                        isinstance(call.func, ast.Attribute) and \
                        call.func.attr == "reset" and \
                        isinstance(call.func.value, ast.Name) and \
                        call.func.value.id == var and \
                        any(isinstance(a, ast.Name) and a.id == token
                            for a in call.args):
                    return True
        return False


def _dedupe(findings: List[Finding]) -> List[Finding]:
    seen = set()
    out = []
    for f in findings:
        k = (f.rule, f.path, f.line, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# the crash-consistency protocol rules (SPL019-SPL023) live in their
# own module; it imports only from core, so this import is cycle-free
from tools.splint.durability import (ReplayTotality,  # noqa: E402
                                     FsyncBarrier, StampFactorAtomicity,
                                     TornPublish, UnfencedTerminalCommit)
from tools.splint.numerics import (AccumulationDiscipline,  # noqa: E402
                                   ImplicitHotUpcast)
from tools.splint.tiling import (PlanSchemaDrift,  # noqa: E402
                                 TileAlignment, VmemBudget)

RULES: List[Rule] = [
    RawEnvironAccess(),
    BroadExceptSwallows(),
    HostSyncInJit(),
    RecompilationHazard(),
    DtypeLiteral(),
    FaultSiteDrift(),
    UndocumentedEnvVar(),
    UseAfterDonate(),
    TracerLeak(),
    RecompileTrigger(),
    CacheLockDiscipline(),
    RunReportEventDrift(),
    SpanNameDrift(),
    MetricNameDrift(),
    SharedStateWithoutLock(),
    LockOrderCycle(),
    DurabilityProtocolDrift(),
    BlockingCallUnderLock(),
    ContextvarLeak(),
    TornPublish(),
    UnfencedTerminalCommit(),
    StampFactorAtomicity(),
    ReplayTotality(),
    FsyncBarrier(),
    AccumulationDiscipline(),
    TileAlignment(),
    VmemBudget(),
    PlanSchemaDrift(),
    ImplicitHotUpcast(),
]
