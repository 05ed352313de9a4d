"""The roofline arithmetic against hand counts for one shape, the table of
peaks, and the readers on hand-made readings."""
import json
import os

import pytest

from benchmark.readers import Readings, counters, spans, xplane
from benchmark.roofline import cfconv, common, pnaconv
from benchmark.trace import reduce as tr

from bench_testlib import REPO

HEAD = {"node": {"dim_headlayers": [4, 4]}}


def test_pnaconv_hand_count():
    arch = {"hidden_dim": 8, "num_conv_layers": 2, "num_radial": 6,
            "input_dim": 1, "output_heads": HEAD}
    flops, hbm = pnaconv.forward(arch, atoms=10, edges=100)
    head = 2 * (8 * 4 + 4 * 4 + 4 * 1)
    first = (10 * (4 * 1 + 32 * 8 + 2 * 64 + 12 + 80)
             + 100 * (2 * 6 + 2 + 8))
    second = (10 * (4 * 64 + 32 * 64 + 2 * 64 + 96 + 80)
              + 100 * (2 * 6 * 8 + 2 * 64 + 64))
    assert flops == 10 * head + first + second == 62920
    assert hbm == 4 * ((100 * 1 + 10 * 9) + (100 * 8 + 10 * 16)) == 4600


def test_cfconv_hand_count():
    arch = {"hidden_dim": 8, "num_filters": 4, "num_gaussians": 5,
            "num_conv_layers": 2, "input_dim": 1, "output_heads": HEAD}
    flops, hbm = cfconv.forward(arch, atoms=10, edges=100)
    head = 2 * (8 * 4 + 4 * 4 + 4 * 1)
    per_edge = 4 * 5 + 2 * 5 * 4 + 2 * 4 * 4 + 4 * 4
    first = 100 * per_edge + 10 * (2 * 1 * 4 + 2 * 16 + 2 * 4 * 8 + 80)
    second = 100 * per_edge + 10 * (2 * 8 * 4 + 2 * 16 + 2 * 4 * 8 + 80)
    assert flops == 10 * head + first + second == 26880
    assert hbm == 4 * ((100 * 4 + 10 * 9) + (100 * 4 + 10 * 16)) == 4200


def test_least_seconds_says_which_roof_binds():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert common.least_seconds(1000.0, 50.0, peak) == (10.0, "compute")
    assert common.least_seconds(100.0, 50.0, peak) == (5.0, "hbm")
    assert common.PASSES == {"forward_ef": 2.0, "train_ef": 6.0}


def test_peaks_of_the_v5e_as_published():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9 and "cloud.google.com" in peaks["source"]


def readings(**kw):
    red = tr.Reduced(
        window_s=2.0, busy_s=1.0, busy_s_per_device=[1.0],
        op_seconds={"fusion.1": 0.75, "gather.2": 0.25},
        collective_s=0.2, collective_exposed_s=0.05,
        program_runs={"jit_step": [0.10, 0.30, 0.20], "jit_eval": [0.01]},
        idle_in_programs_s=0.1, idle_gaps=[(0.0, 0.8e9)],
        window_ns=(0.0, 2e9))
    base = dict(
        arch={"hidden_dim": 8, "num_conv_layers": 2, "num_radial": 6,
              "input_dim": 1, "output_heads": HEAD},
        chips=1, reduced=red, window=(10.0, 12.0),
        spans=[("serve.forward", 10.0, 10.1), ("serve.forward", 10.5, 10.8),
               ("dataload_wait", 10.0, 10.5), ("dataload_wait", 11.0, 11.1)],
        counters={"batch_occupancy": 0.5, "absent": None},
        work={"atoms": 10, "edges": 100}, device_kind="TPU v5 lite")
    base.update(kw)
    return Readings(**base)


def test_readers_on_known_readings():
    r = readings()
    assert xplane.program_device_ms(r) == pytest.approx(200.0)
    assert xplane.program_device_ms(r, program="eval") == pytest.approx(10.0)
    assert xplane.program_device_ms(r, program="nothing") is None
    assert xplane.collective_exposed_share(r) is None, "one chip"
    assert xplane.collective_exposed_share(readings(chips=4)) == \
        pytest.approx(5.0)
    flops, hbm = pnaconv.forward(r.arch, 10, 100)
    want = 100.0 * max(6 * flops / 197e12, 6 * hbm / 819e9) / 1.0
    assert xplane.roofline_share(r, "pnaconv", "train_ef") == \
        pytest.approx(want)
    assert xplane.roofline_share(readings(chips=4), "pnaconv", "train_ef") \
        == pytest.approx(want / 4)
    assert spans.percentile_ms(r, "serve.forward", 50) == pytest.approx(200.0)
    assert spans.share_of_window(r, "dataload_wait") == pytest.approx(30.0)
    assert counters.value(r, "batch_occupancy", scale=100.0) == 50.0


def test_a_reader_with_nothing_to_read_returns_nothing():
    r = readings()
    assert spans.percentile_ms(r, "serve.queue_wait") is None
    assert spans.share_of_window(r, "h2d") is None
    assert counters.value(r, "absent") is None
    assert counters.value(r, "missing") is None
    none = readings(reduced=None)
    assert xplane.program_device_ms(none) is None
    assert xplane.roofline_share(none, "cfconv", "forward_ef") is None
    assert none.breakdown() == {"device_ops": [], "idle_gaps": []}
    with pytest.raises(KeyError, match="no peaks on record"):
        readings(device_kind="TPU v9").peak()


def test_breakdown_lists_device_ops_and_attributed_gaps():
    r = readings()
    got = r.breakdown()
    assert got["device_ops"][0] == ["fusion.1", 0.75]
    # the window opened at host second 10 and profiler ns 0: the midpoint of
    # the gap 0..0.8 s is host second 10.4, inside the first dataload_wait
    assert ["dataload_wait", pytest.approx(0.8)] in got["idle_gaps"]
    assert ["inside_programs", 0.1] in got["idle_gaps"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
