"""splint configuration: the ``[tool.splint]`` table of pyproject.toml.

Python 3.10 has no ``tomllib`` and splint must not grow dependencies,
so a minimal single-table parser lives here: it understands exactly the
value shapes the splint table uses (strings, string arrays — including
multiline arrays) and nothing more.  The same :class:`Config` object is
what tests construct directly to point the analyzer at fixture
mini-projects, so the analyzer runs identically from pytest, the CLI,
and any future CI job.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import List, Optional


@dataclasses.dataclass
class Config:
    """Where splint looks and which project modules anchor its rules."""

    #: project root every relative path below resolves against
    root: Path
    #: files/directories to analyze (relative to root)
    paths: List[str] = dataclasses.field(
        default_factory=lambda: ["splatt_tpu"])
    #: the checked-in baseline of grandfathered findings
    baseline: str = "tools/splint/baseline.json"
    #: the single sanctioned env-access module (SPL001 exemption,
    #: SPL007's ENV_VARS registry)
    env_module: str = "splatt_tpu/utils/env.py"
    #: the fault-injection harness declaring SITES (SPL006)
    faults_module: str = "splatt_tpu/utils/faults.py"
    #: the dtype-policy module (SPL005 exemption)
    config_module: str = "splatt_tpu/config.py"
    #: test tree scanned for exercised fault sites (SPL006)
    tests_path: str = "tests"
    #: non-jitted hot-path functions ("relpath::name") that get the
    #: SPL003 host-sync scan as if they were jitted
    hot_functions: List[str] = dataclasses.field(default_factory=list)
    #: extra handler-body names SPL002 accepts as routing the failure
    #: (project helpers that wrap resilience.classify_failure)
    resilience_routers: List[str] = dataclasses.field(default_factory=list)
    #: the resilience module declaring RUN_REPORT_EVENTS (SPL012)
    resilience_module: str = "splatt_tpu/resilience.py"
    #: the trace module declaring the SPANS name registry (SPL013)
    #: and the METRICS registry (SPL029)
    trace_module: str = "splatt_tpu/trace.py"
    #: the markdown file whose metrics table SPL029 checks against
    #: trace.METRICS in both directions ("" disables the docs legs)
    metrics_doc: str = "docs/observability.md"
    #: functions returning shared-cache file paths; values derived
    #: from them must only reach IO through the locked helpers (SPL011)
    cache_path_functions: List[str] = dataclasses.field(
        default_factory=list)
    #: the sanctioned cache-IO helper functions whose bodies SPL011
    #: exempts (they ARE the locked chokepoints)
    cache_io_helpers: List[str] = dataclasses.field(default_factory=list)
    #: shared-structure → guarding-lock map for SPL014, entries of the
    #: form "relpath::self.attr=self.lock" (instance state, the class
    #: resolved at each mutation site) or "relpath::NAME=LOCK_NAME"
    #: (module globals)
    shared_state: List[str] = dataclasses.field(default_factory=list)
    #: the sanctioned durable-write helper functions whose bodies
    #: SPL016 exempts (they ARE the fsync/tmp-write→replace/append
    #: chokepoints — splatt_tpu/utils/durable.py)
    durable_write_helpers: List[str] = dataclasses.field(
        default_factory=list)
    #: control-plane functions ("relpath::name") where SPL017 flags a
    #: blocking call (fsync/flock/sleep/join/wait/subprocess, directly
    #: or transitively) made while an in-process lock is held
    hot_lock_paths: List[str] = dataclasses.field(default_factory=list)
    #: path fragments naming the durable roots (journal, ckpt, stamp,
    #: lease, result, metrics ...) — a write-mode open whose path
    #: expression carries one of these is a durable write (SPL023)
    durable_roots: List[str] = dataclasses.field(default_factory=list)
    #: the atomic-publish subset of the durable helpers whose bodies
    #: SPL019 audits for the full tmp-write → fsync → os.replace →
    #: parent-dir-fsync protocol, in order
    atomic_publish_helpers: List[str] = dataclasses.field(
        default_factory=list)
    #: every function ("relpath::name") allowed to append to the job
    #: journal — SPL020 flags appends anywhere else
    journal_append_functions: List[str] = dataclasses.field(
        default_factory=list)
    #: the subset of journal-append functions whose terminal appends
    #: must be dominated by a live-lease fence (SPL020)
    lease_fenced_functions: List[str] = dataclasses.field(
        default_factory=list)
    #: call names that constitute a live-lease fence (SPL020)
    lease_fence_calls: List[str] = dataclasses.field(default_factory=list)
    #: call names that advance the generation stamp (SPL021 leg A)
    stamp_advance_calls: List[str] = dataclasses.field(
        default_factory=list)
    #: call names that persist factor content a stamp covers (SPL021
    #: leg A: one must dominate every stamp advance)
    factor_persist_calls: List[str] = dataclasses.field(
        default_factory=list)
    #: commit-scope persists after which the stamp advance is
    #: mandatory on every normal-flow path (SPL021 leg B)
    commit_persist_calls: List[str] = dataclasses.field(
        default_factory=list)
    #: the serve module declaring TERMINAL and KNOWN_KINDS (SPL020,
    #: SPL022)
    serve_module: str = "splatt_tpu/serve.py"
    #: files/dirs whose reductions the SPL024 dtype-flow interpreter
    #: audits for accumulation-dtype discipline
    numerics_modules: List[str] = dataclasses.field(default_factory=list)
    #: the sanctioned accumulation-dtype helper names — a reduce
    #: routed through one carries the discipline (SPL024); each must
    #: exist in config-module (registry leg)
    acc_dtype_helpers: List[str] = dataclasses.field(default_factory=list)
    #: hot stream functions ("relpath::name") audited by SPL028 for
    #: narrow×wide elementwise promotion before the accumulate point
    hot_stream_functions: List[str] = dataclasses.field(
        default_factory=list)
    #: declared entry dtypes for the hot stream functions
    #: ("relpath::fn::param=bf16") — the storage contract the dispatch
    #: layer feeds them (SPL024/SPL028 lattice seeds)
    hot_stream_param_dtypes: List[str] = dataclasses.field(
        default_factory=list)
    #: files/dirs whose BlockSpecs SPL025/SPL026 audit
    pallas_modules: List[str] = dataclasses.field(default_factory=list)
    #: dtype-blind padding helpers (ceil_to/_pad_blocks) — values they
    #: produce need a unit that certifies the block position (SPL025)
    align_helpers: List[str] = dataclasses.field(default_factory=list)
    #: dtype-AWARE padding helpers (tile_packing) whose
    #: results certify any sublane position (SPL025)
    tile_pack_helpers: List[str] = dataclasses.field(default_factory=list)
    #: declared dispatch envelope for SPL026's static accounting:
    #: "dim-text=int" caps a block dim by its unparsed source text,
    #: "*name=int" caps a starred spec list's multiplicity
    vmem_dim_caps: List[str] = dataclasses.field(default_factory=list)
    #: default per-kernel VMEM budget in MiB (SPL026)
    vmem_budget_mib: str = "16"
    #: per-kernel overrides, "fn=MiB" (SPL026)
    vmem_kernel_budgets: List[str] = dataclasses.field(
        default_factory=list)
    #: kernel-wrapper → dispatch-gate registry, "fn=gate" (SPL026):
    #: every pallas_call wrapper needs one, the gate must exist in the
    #: wrapper's module and be consulted somewhere
    vmem_gate_map: List[str] = dataclasses.field(default_factory=list)
    #: functions performing the plan cache's strict-match comparison;
    #: SPL027 checks each compares the schema's match set exactly
    plan_match_functions: List[str] = dataclasses.field(
        default_factory=list)
    #: rules whose finding budget is ZERO — never baselined, never
    #: grandfathered; the pytest gate enforces each at 0 findings
    zero_rules: List[str] = dataclasses.field(default_factory=list)
    #: path fragments to skip entirely
    exclude: List[str] = dataclasses.field(default_factory=list)

    def resolve(self, rel: str) -> Path:
        return (self.root / rel).resolve()


_KEY_RE = re.compile(r"^\s*([A-Za-z0-9_-]+)\s*=\s*(.*)$")


def _parse_table(text: str, table: str) -> dict:
    """Parse one ``[table]`` of a TOML file into a dict.

    Handles the subset splint uses: ``key = "string"`` and
    ``key = ["a", "b", ...]`` (arrays may span lines).  TOML string and
    array literals in this subset are also valid Python literals, so
    ``ast.literal_eval`` does the value parsing.
    """
    lines = text.splitlines()
    out: dict = {}
    in_table = False
    i = 0
    while i < len(lines):
        line = lines[i].strip()
        i += 1
        if line.startswith("["):
            in_table = line == f"[{table}]"
            continue
        if not in_table or not line or line.startswith("#"):
            continue
        m = _KEY_RE.match(line)
        if not m:
            raise ValueError(f"splint: cannot parse pyproject line: {line!r}")
        key, val = m.group(1), m.group(2)
        # accumulate a multiline array until brackets balance
        while val.count("[") > val.count("]"):
            if i >= len(lines):
                raise ValueError(
                    f"splint: unterminated array for {key!r} in [{table}]")
            val += " " + lines[i].strip()
            i += 1
        try:
            out[key.replace("-", "_")] = ast.literal_eval(val)
        except (SyntaxError, ValueError) as e:
            raise ValueError(
                f"splint: unsupported value for {key!r} in [{table}] "
                f"(splint's mini-parser takes strings and string arrays, "
                f"no end-of-line comments): {val!r} ({e})") from e
    return out


def load_config(root: Optional[Path] = None) -> Config:
    """Build a :class:`Config` from ``<root>/pyproject.toml``'s
    ``[tool.splint]`` table (missing file/table → defaults)."""
    root = Path(root) if root is not None else Path.cwd()
    cfg = Config(root=root)
    pp = root / "pyproject.toml"
    if not pp.exists():
        return cfg
    table = _parse_table(pp.read_text(), "tool.splint")
    for key, val in table.items():
        if not hasattr(cfg, key):
            raise ValueError(f"splint: unknown [tool.splint] key {key!r}")
        setattr(cfg, key, val)
    return cfg
