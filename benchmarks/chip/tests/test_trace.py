"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (``tests/data/``) read end to end."""
from pathlib import Path

import pytest

from harness import BENCH, load_module

trace = load_module(BENCH / "trace.py")
DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (39, 45), (60, 70)]
    assert trace.union_ns(iv, 0, 100) == 20 + 15 + 10
    assert trace.union_ns(iv, 8, 35) == 12 + 5
    assert trace.gaps_ns(iv, 0, 100) == [(20, 30), (45, 60), (70, 100)]
    assert trace.gaps_ns(iv, 25, 42) == [(25, 30)]


def test_busy_kernel_and_breakdown_by_hand():
    tr = trace.Trace(
        window=(0, 100),
        ops=[[(0, 10, "fusion.1"), (20, 30, "aircomp_kernel"),
              (25, 40, "fusion.2"), (90, 120, "fusion.1")]],
        host=[(0, 100, "run_sweep"), (40, 85, "lower_sharding_computation"),
              (86, 100, "h2d_batch")])
    assert tr.window_s == 100e-9
    assert tr.busy_s == pytest.approx((10 + 20 + 10) * 1e-9)
    assert tr.kernel_s("aircomp") == pytest.approx(10e-9)
    assert tr.kernel_count("aircomp") == 1
    bd = tr.breakdown()
    ops = dict(bd["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert ops["fusion.2"] == pytest.approx(15e-9)
    gaps = dict(bd["idle_gaps"])
    # gaps: 10-20 and 40-90 (midpoints 15 and 65)
    assert gaps == {"run_sweep": pytest.approx(10e-9),
                    "lower_sharding_computation": pytest.approx(50e-9)}


def test_kernels_are_found_by_instruction_name():
    tr = trace.Trace(
        window=(0, 100),
        ops=[[(0, 10, "%aircomp_pallas.1 = f32[8]{0} custom-call(%x)"),
              (10, 30, "%fusion.2 = f32[8]{0} fusion(%aircomp_pallas.1)")]])
    assert tr.kernel_count("aircomp") == 1
    assert tr.kernel_s("aircomp") == pytest.approx(10e-9)


def test_lost_events_by_hand():
    ops = [(0, 40, "%a = f32[]"), (50, 95, "%k.1 = f32[]")]
    whole = trace.Trace(window=(0, 100), ops=[ops],
                        modules=[[(0, 45, "jit_a"), (50, 98, "jit_b")]])
    assert whole.lost_events({"k": 1}) == []
    # fewer launches than the driver made
    assert whole.lost_events({"k": 2}) == ["1 of 2 launches of k"]
    # the operations of a program that ran were dropped
    sparse = trace.Trace(window=(0, 100), ops=[ops[:1]],
                         modules=[[(0, 45, "jit_a"), (50, 98, "jit_b")]])
    assert len(sparse.lost_events({})) == 2
    # the events stop long before the window does
    early = trace.Trace(window=(0, 200), ops=[ops],
                        modules=[[(0, 45, "jit_a"), (50, 98, "jit_b")]])
    assert "before the window" in early.lost_events({})[0]


def test_two_devices_average():
    tr = trace.Trace(window=(0, 100), ops=[[(0, 50, "a")], [(0, 10, "a")]])
    assert tr.busy_s == pytest.approx(30e-9)
    assert tr.kernel_s("a") == pytest.approx(30e-9)


def test_recorded_chip_trace():
    # three steps of a small jitted program on one TPU v5e, each with a
    # host-to-device copy, inside a host span named "window"
    tr = trace.load(DATA, 1)
    assert 0 < tr.busy_s < tr.window_s
    bd = tr.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
    assert tr.modules[0] and tr.lost_events({}) == []
