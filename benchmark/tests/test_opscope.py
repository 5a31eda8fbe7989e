"""The MTTKRP phase reader (``benchmark/opscope.py``) on hand-made
intervals and on two small traces recorded on a v5e: one from before
the program named its phases (``tiny.xplane.pb``) and one after
(``tiny_scoped.xplane.pb``); and the per-layer readers on the first,
which must read what they always read."""

import os

import pytest

import opscope
import run
import tracereduce

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
UNSCOPED = os.path.join(DATA, "tiny.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_scoped.xplane.pb")


def _device(planes):
    (dev,) = [p for p in planes if opscope.DEVICE_PLANE.match(p.name)]
    return dev


def _line(plane, name):
    (line,) = [ln for ln in plane.lines if ln.name == name]
    return opscope._intervals(line)


def test_wire_reader_agrees_with_profile_data():
    ops = _line(_device(opscope.read_space(UNSCOPED)), "XLA Ops")
    (dev,) = tracereduce.load(UNSCOPED).devices.values()
    assert len(ops) == len(dev.ops) > 1000
    for (a, b, _), (_, a2, b2) in zip(ops, dev.ops):
        assert a == pytest.approx(a2, abs=1e-8)
        assert b == pytest.approx(b2, abs=1e-8)


def test_containers_are_left_out():
    # a while op [0, 10] around three body ops, then a lone op
    ops = [(0.0, 10.0, 1), (1.0, 3.0, 2), (3.0, 5.0, 3), (5.0, 10.0, 4),
           (10.0, 12.0, 5)]
    leaves, containers = opscope.leaf_ops(ops)
    assert containers == 1
    assert sorted(mid for _, _, mid in leaves) == [2, 3, 4, 5]
    assert sum(b - a for a, b, _ in leaves) == 11.0


def test_phase_of_reads_the_innermost_scopes():
    assert opscope.phase_of(
        "jit(_mttkrp_blocked_jit)/mttkrp.mode2/while/body/closed_call/"
        "mttkrp.reduce/jit(onehot_reduce_sorted)/pad:") == (2, "reduce")
    assert opscope.phase_of(
        "jit(_mttkrp_blocked_jit)/mttkrp.mode0/mttkrp.merge/"
        "scatter-add:") == (0, "merge")
    assert opscope.phase_of("jit(update)/jit(eigh)/eigh:") == (None, None)
    assert opscope.phase_of("") == (None, None)


def test_unscoped_trace_counts_leaf_ops_only():
    planes = opscope.read_space(UNSCOPED)
    dev = _device(planes)
    leaves, containers = opscope.leaf_ops(_line(dev, "XLA Ops"))
    assert containers > 0
    names = [dev.event_names[mid] for _, _, mid in leaves]
    assert not any(n.startswith("%while ") for n in names)
    ph = opscope.summarize(planes)
    s = tracereduce.summarize(tracereduce.load(UNSCOPED))
    assert ph.iterations == s.iterations == 3
    assert ph.window_s == pytest.approx(s.window_s, abs=1e-8)
    # leaf ops fill the MTTKRP program's time, and no more
    module = s.seconds_matching("mttkrp")
    assert 0.99 * module <= ph.program_s("mttkrp") <= module
    assert all(ph.phase_s(p) == 0.0 for p in opscope.PHASES)


def test_scoped_trace_names_every_phase():
    """A --trace 1 window at a tiny size (rank 50, three modes, the
    unfused Pallas engine), recorded on a v5e by record_trace.py after
    the program named its phases."""
    ph = opscope.load(SCOPED)
    assert ph.iterations >= 1
    for mode in range(3):
        for phase in opscope.PHASES:
            assert ph.phase_s(phase, mode) > 0.0, (mode, phase)
    scoped = sum(ph.phase_s(p) for p in opscope.PHASES)
    assert scoped >= 0.9 * ph.program_s("mttkrp")
    rows = dict(ph.table())
    assert set(rows) == {"mode0", "mode1", "mode2", "unscoped"}
    assert sum(sum(r.values()) for r in rows.values()) == pytest.approx(
        1e3 * ph.program_s("mttkrp") / ph.iterations)


# what each reader read on tiny.xplane.pb when it was recorded
BEFORE = {"mttkrp_ms_per_iter": 5.944179333333325,
          "mttkrp_roofline": 16.82318018893331,
          "als_update_ms_per_iter": 0.39881033333331867,
          "launches_per_iter": 33.0,
          "device_idle": 66.62292784266943,
          "layout_build_s": 7.5}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_existing_metrics_read_as_before(name):
    s = tracereduce.summarize(tracereduce.load(UNSCOPED))
    ctx = run.Context(summary=s, setup={"layout_build_s": 7.5},
                      work={"seconds": 1e-3}, peak={})
    assert run.load_metric(name).read(ctx) == pytest.approx(
        BEFORE[name], rel=1e-12)


def test_window_jit_s_reads_the_last_traced_run():
    import numpy as np

    from splatt_tpu import trace
    from splatt_tpu.blocked import BlockedSparse
    from splatt_tpu.config import Options, Verbosity
    from splatt_tpu.coo import SparseTensor
    from splatt_tpu.cpd import cpd_als

    ctx = run.Context(summary=None, setup={}, work={}, peak={})
    reader = run.load_metric("window_jit_s")
    trace.reset()
    assert reader.read(ctx) is None
    rng = np.random.default_rng(0)
    inds = np.stack([rng.integers(0, d, 400) for d in (9, 8, 7)])
    X = BlockedSparse.from_coo(SparseTensor(inds, rng.random(400),
                                            (9, 8, 7)))
    cpd_als(X, 2, Options(max_iterations=2, trace=True,
                          verbosity=Verbosity.NONE))
    root = trace.spans("cpd.als")[-1]["args"]
    assert reader.read(ctx) == root["jit_s"] > 0.0
    trace.reset()
