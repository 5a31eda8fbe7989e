"""The benchmark harness (``benchmark/run.py``) at four modes, on the
CPU at a tiny size with the look for a chip skipped, as
``benchmark/tests/test_faults.py`` runs it at three: a sound run is
correct and checks the fourth mode; a window answer with its last
factor's heaviest row 1% off is not.  And the ``hbm_gather_share``
reader over the scan loops of a device trace."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

from splatt_tpu import trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402

SEED = 2**31 + 29
KEYS = [f"it{i}_m{m}" for i in (1, 2) for m in range(4)]
LIMITS = {**{k: 1e-4 for k in KEYS}, "end_m3": 1e-4, "end_fit": 1e-5}
SPEC = {"cell": {"name": "tiny4", "chips": 1},
        "config": {"dims": [40, 30, 2000, 12], "nnz": 20000, "rank": 8,
                   "options": {"block_alloc": "allmode", "tolerance": 0.0,
                               "max_iterations": 100000}},
        "traffic": {"generator": "tensorgen", "exponent": 1.0},
        "limits": LIMITS,
        "end_to_end": [{"name": "als_s_per_iter", "unit": "s/iter"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


@pytest.fixture
def no_chip_needed(monkeypatch):
    monkeypatch.setattr(run, "find_chips", lambda chips: (
        jax.devices()[0], len(jax.devices()),
        {"hbm_bytes_per_s": 819e9, "mxu_flops_per_s": 197e12}))


def test_sound_four_mode_run_is_correct(no_chip_needed):
    r = run.run_cell(SPEC, SEED, 0.02, False)
    assert sorted(r["checks"]) == sorted(LIMITS)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


def test_last_factor_row_altered_is_caught(no_chip_needed, monkeypatch):
    """The timed window's answer with its last factor's heaviest row 1%
    off, as produced, fails ``end_m3``."""
    import dataclasses

    import splatt_tpu.cpd as cpd

    real = cpd.cpd_als

    def cpd_als(*a, stop=None, **k):
        out = real(*a, stop=stop, **k)
        if stop is None:
            return out
        U = out.factors[-1]
        row = int(jnp.argmax(jnp.abs(U).sum(axis=1)))
        return dataclasses.replace(out, factors=[
            *out.factors[:-1], U.at[row].multiply(1.01)])

    monkeypatch.setattr(cpd, "cpd_als", cpd_als)
    r = run.run_cell(SPEC, SEED, 0.02, False)
    assert not r["correct"], r["checks"]
    assert r["checks"]["end_m3"]["value"] > LIMITS["end_m3"]


#: a scan loop's op as the chip's trace names it (Enron, rank 25,
#: mode 3, 26 blocks a step): the compiled HLO's result tuple, the
#: layout chunks and the three gathered tables, two in VMEM (S(1))
ENRON_LOOP = (
    "%while = (s32[]{:T(128)}, f32[509,26,8,25]{3,2,1,0:T(8,128)}, "
    "s32[509,26,4096]{2,1,0:T(8,128)}, s32[509,26,4096]{2,1,0:T(8,128)}, "
    "s32[509,26,4096]{2,1,0:T(8,128)}, "
    "/*index=5*/s32[509,26,4096]{2,0,1:T(8,128)}, "
    "f32[509,26,4096]{2,0,1:T(8,128)}, s32[509,26]{1,0:T(8,128)}, "
    "f32[6066,25]{1,0:T(8,128)S(1)}, f32[5699,25]{1,0:T(8,128)S(1)}, "
    "/*index=10*/f32[244268,25]{1,0:T(8,128)}, s32[]{:T(128)}) "
    "while((s32[]{:T(128)}, f32[509,26,8,25]{3,2,1,0:T(8,128)}) "
    "%tuple.14), condition=%wide.region_1.10, body=%wide.region_0.9.sunk")
#: ops a scan loop is not: the long table's gather inside the loop, and
#: a loop with no layout chunks in its state
NOT_LOOPS = [
    "%fusion.16 = f32[106496,25]{1,0:T(8,128)S(1)} fusion(f32[244268,25]"
    "{1,0:T(8,128)} %get-tuple-element.170, s32[106496]{0:T(1024)S(1)} "
    "%broadcast_clamp_fusion.7), kind=kCustom, calls=%fused_computation.1",
    "%while.2 = (s32[]{:T(128)}, f32[25,25]{1,0:T(8,128)}, "
    "f32[6066,25]{1,0:T(8,128)}) while((s32[]{:T(128)}) %tuple.3), "
    "condition=%region_1.2, body=%region_0.1"]


def _loop(tables, rank=25, blocks=26):
    """A scan loop's op name holding `tables`: (rows, in VMEM) each."""
    state = ", ".join(f"f32[{d},{rank}]{{1,0:T(8,128)"
                      f"{'S(1)' if vmem else ''}}}" for d, vmem in tables)
    return (f"%while = (s32[]{{:T(128)}}, f32[509,{blocks},8,{rank}]"
            f"{{3,2,1,0:T(8,128)}}, s32[509,{blocks},4096]{{2,1,0:T(8,128)}}, "
            f"{state}, s32[]{{:T(128)}}) while((s32[]{{:T(128)}}) %tuple.14)"
            ", condition=%wide.region_1.10, body=%wide.region_0.9.sunk")


def _enron(hbm):
    """The four Enron modes' scan loops, the tables of `hbm` in HBM."""
    dims = (6066, 5699, 244268, 1176)
    return [_loop([(d, k not in hbm) for k, d in enumerate(dims) if k != m])
            for m in range(4)]


def _summary(ops):
    import types

    return types.SimpleNamespace(
        summary=types.SimpleNamespace(device_ops=[[n, 1.0] for n in ops]))


@pytest.mark.parametrize("ops,want", [
    ([ENRON_LOOP], 100 / 3),
    (_enron({2}) + NOT_LOOPS, 25.0),
    (_enron({0, 1, 2, 3}), 100.0),
    ([_loop([(12092, True), (28818, True)], 50, 40),
      _loop([(9184, True), (28818, True)], 50, 40),
      _loop([(12092, True), (9184, True)], 50, 40)], 0.0),
    (NOT_LOOPS, None),
    (None, None)],
    ids=["chip-loop", "long-table", "every-table", "nell2-r50", "no-loop",
         "no-trace"])
def test_hbm_gather_share_reads_the_device_placement(ops, want):
    """The share of the tables the traced scan loops carry outside VMEM
    (no S(1) in their layout), so of the rows they gather; ops that are
    no scan loop, or no trace, give nothing."""
    metric = run.load_metric("hbm_gather_share")
    ctx = _summary(ops or [])
    if ops is None:
        ctx.summary = None
    got = metric.read(ctx)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("placement,want", [
    (None, None),
    ({0: [2], 1: [2], 2: [], 3: [2]}, {"0": [2], "1": [2], "2": [],
                                        "3": [2]}),
    ({0: [1, 2, 3], 1: [0, 2, 3], 2: [0, 1, 3], 3: [0, 1, 2]},
     {"0": [1, 2, 3], "1": [0, 2, 3], "2": [0, 1, 3], "3": [0, 1, 2]})],
    ids=["no-attribute", "long-table", "every-table"])
def test_hbm_gather_share_reads_the_last_dispatch(placement, want, capsys):
    """The log line beside the reading gives each mode's last dispatch
    span's own reckoning (``tables_hbm``), where the spans carry it; the
    reading is the device's placement whatever the program reckons."""
    import json

    metric = run.load_metric("hbm_gather_share")
    if placement is None:
        sweeps = [{m: {} for m in range(4)}]
    else:
        # an earlier sweep reckoned every table in HBM; the last counts
        sweeps = [{m: {"tables_hbm": [k for k in range(4) if k != m]}
                   for m in range(4)},
                  {m: {"tables_hbm": placement[m], "step_blocks": 26}
                   for m in range(4)}]
    trace.reset()
    trace.set_enabled(True)
    try:
        for sweep in sweeps:
            for m, attrs in sweep.items():
                with trace.span("mttkrp.dispatch", mode=m, **attrs):
                    pass
        assert metric.read(_summary(_enron({2}))) == 25.0
    finally:
        trace.set_enabled(None)
        trace.reset()
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "as the program reckons it" in ln]
    if want is None:
        assert lines == []
    else:
        (line,) = lines
        got = json.loads(line.split("reckons it ", 1)[1])
        assert {m: a["tables_hbm"] for m, a in got.items()} == want
