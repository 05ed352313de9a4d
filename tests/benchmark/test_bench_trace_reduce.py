"""``benchmark/trace/reduce.py`` on hand-made traces, where every number is
known, and on a small trace recorded on a TPU v5e."""
import os

import numpy as np
import pytest

from benchmark.trace import reduce as tr

from bench_testlib import xspace_text

HERE = os.path.dirname(os.path.abspath(__file__))


def planes_of(spec):
    from jax.profiler import ProfileData
    return tr.read_planes(ProfileData.from_text_proto(xspace_text(spec)))


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert tr.length(tr.merge([(0, 2), (1, 3)])) == 3
    assert tr.clip([(0, 4), (6, 9), (10, 12)], (3, 7)) == [(3, 4), (6, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.gaps([(1, 2), (4, 6)], (0, 8)) == [(0, 1), (2, 4), (6, 8)]


# One chip, window 0..1000 ns marked on the host. Two runs of the step
# program: 100..400 (ops 100..200, 250..400: 50 idle inside) and 600..900
# (ops 600..900, two of them overlapping), one small other program.
ONE_CHIP = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_step_body(111)", 100, 300),
                        ("jit_step_body(222)", 600, 300),
                        ("jit_copy(5)", 950, 20)],
        "XLA Ops": [
            ("%fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%p), kind=kLoop", 100,
             100),
            ("%gather.2 = f32[4]{0} gather(%q)", 250, 150),
            ("%fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%p), kind=kLoop", 600,
             200),
            ("%gather.2 = f32[4]{0} gather(%q)", 700, 200),
            ("%copy.9 = f32[2]{0} copy(%r)", 950, 20),
            ("%fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%p), kind=kLoop",
             1200, 100),
        ]},
    "/host:CPU": {"python": [(tr.WINDOW_EVENT, 0, 1000),
                             ("other", 10, 5)]},
    "/device:TPU:0 SparseCore": {"XLA Ops": [("x", 0, 1000)]},
}


def test_busy_idle_steps_and_gaps_of_a_known_trace():
    red = tr.reduce_planes(planes_of(ONE_CHIP))
    assert red.window_ns == (0, 1000)
    assert red.window_s == pytest.approx(1000e-9)
    # 100 + 150 + 300 (union of the overlapping pair) + 20; the op at 1200
    # is outside the window, the SparseCore plane is not a chip
    assert red.busy_s == pytest.approx(570e-9)
    assert red.busy_s_per_device == [pytest.approx(570e-9)]
    assert red.idle_share == pytest.approx(0.43)
    runs = red.program_runs
    assert set(runs) == {"jit_step_body", "jit_copy"}
    assert runs["jit_step_body"] == [pytest.approx(250e-9),
                                     pytest.approx(300e-9)]
    assert tr.main_program(red) == "jit_step_body"
    assert red.idle_in_programs_s == pytest.approx(50e-9)
    assert red.idle_gaps == [(0, 100), (400, 600), (900, 950), (970, 1000)]
    # by result name and shape, clipped to the window; summed durations,
    # not the union
    assert red.op_seconds["%fusion.1 = f32[8,4]"] == pytest.approx(300e-9)
    assert red.op_seconds["%gather.2 = f32[4]"] == pytest.approx(350e-9)
    assert tr.top(red.op_seconds, 2) == [
        ["%gather.2 = f32[4]", pytest.approx(350e-9)],
        ["%fusion.1 = f32[8,4]", pytest.approx(300e-9)]]
    assert red.collective_s == 0 and red.collective_exposed_s == 0


def test_idle_gaps_go_to_the_innermost_host_span():
    red = tr.reduce_planes(planes_of(ONE_CHIP))
    spans = [("train_epoch", 0, 1000), ("dataload_wait", 390, 610),
             ("device_wait", 0, 120)]
    got = dict(map(tuple, tr.attribute_gaps(red.idle_gaps, spans)))
    assert got == {"device_wait": pytest.approx(100e-9),
                   "dataload_wait": pytest.approx(200e-9),
                   "train_epoch": pytest.approx(80e-9)}
    assert dict(map(tuple, tr.attribute_gaps(red.idle_gaps, []))) == {
        "no_span": pytest.approx(380e-9)}
    assert len(tr.attribute_gaps(red.idle_gaps, spans, count=1)) == 1


# Two chips, no host mark: the window is first to last device event
# (0..100). Chip 0: compute 0..60, all-reduce 40..80 (20 exposed);
# chip 1: compute 0..40, all-reduce-start 50..90 (all 40 exposed).
TWO_CHIPS = {
    "/device:TPU:1": {"XLA Ops": [
        ("fusion.7", 0, 40), ("all-reduce-start.1", 50, 40),
        ("fusion.8", 95, 5)]},
    "/device:TPU:0": {"XLA Ops": [
        ("fusion.7", 0, 60), ("%all-reduce.3 = f32[9]{0} all-reduce(%g)",
                                  40, 40), ("fusion.8", 95, 5)]},
}


def test_collectives_hidden_and_exposed_mean_over_the_chips():
    red = tr.reduce_planes(planes_of(TWO_CHIPS))
    assert red.window_ns == (0, 100)
    assert red.busy_s_per_device == [pytest.approx(85e-9),
                                     pytest.approx(85e-9)]
    assert red.collective_s == pytest.approx(40e-9)
    assert red.collective_exposed_s == pytest.approx(30e-9)  # (20 + 40) / 2
    assert red.program_runs == {} and tr.main_program(red) is None
    # per-chip totals are averaged over the chips
    assert red.op_seconds["fusion.7"] == pytest.approx(50e-9)
    assert red.op_seconds["%all-reduce.3 = f32[9]"] == pytest.approx(20e-9)


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce_planes(planes_of({"/host:CPU": {"python": [
            (tr.WINDOW_EVENT, 0, 10)]}}))


def test_program_name_drops_the_run_id():
    assert tr.program_name("jit_step_body(123456)") == "jit_step_body"
    assert tr.program_name("jit_f") == "jit_f"


RECORDED = os.path.join(HERE, "data", "v5e_recorded.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the checkout")
def test_the_recorded_v5e_trace_reduces_to_the_numbers_read_by_hand():
    """Recorded in PR 22 on one TPU v5 lite chip by
    ``python -m benchmark.calibrate record`` (three steps of a small jitted
    matmul chain inside a `bench.window` mark); the expected numbers were
    read from the events with ``calibrate xplane`` and a brute-force
    raster, not with the code under test."""
    import json
    with open(os.path.join(HERE, "data", "v5e_recorded.expected.json")) as f:
        want = json.load(f)
    planes = tr.load_xplane(RECORDED)
    red = tr.reduce_planes(planes)
    assert len(tr.device_planes(planes)) == want["chips"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    main = tr.main_program(red)
    assert main == want["main_program"]
    assert len(red.program_runs[main]) == want["runs"]
    assert float(np.median(red.program_runs[main])) == pytest.approx(
        want["median_run_busy_s"], rel=1e-6)
    assert len(red.idle_gaps) == want["gaps_between_programs"]
    # the brute-force raster: every nanosecond of the window that some op
    # of chip 0 covers
    ops = tr.device_planes(planes)[0].lines[tr.OPS_LINE]
    lo, hi = (int(round(v)) for v in red.window_ns)
    covered = np.zeros(hi - lo, bool)
    for ev in ops:
        a = max(int(round(ev.start_ns)) - lo, 0)
        b = min(int(round(ev.end_ns)) - lo, hi - lo)
        if b > a:
            covered[a:b] = True
    assert red.busy_s_per_device[0] == pytest.approx(
        covered.sum() * 1e-9, rel=1e-3)
