"""BENCHMARK.json against the contract, and that every name in it resolves
to the files it stands for — without JAX."""
import json
import os
import re

import pytest

from benchmark import cells

from bench_testlib import (DEVICE_KEYS, LINE_KEYS, REPO,
                           check_configuration_file, config_doc, depth_cuts,
                           run_cell, throwaway_root as throwaway)

BENCH = cells.load_benchmark()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x 24 cells of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert all(isinstance(c, str) for c in BENCH["command"])
    assert not any(c.startswith("/") or ".." in c for c in BENCH["command"])


def test_every_name_resolves_and_is_well_formed():
    assert cells.problems() == []


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    cell = cells.load_cell(entry["name"])
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "jobs", cell.traffic["job"] + ".py"))
    assert cell.traffic["who"], "a mix says who sends it"
    assert "tiny" in cell.traffic and "tiny" in cell.config_doc


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(config):
    check_configuration_file(BENCH, REPO, config)


ARCH = {"num_conv_layers": 2, "hidden_dim": 192}


@pytest.mark.parametrize("reduced,keys", [
    ([], []), (["num_conv_layers: 2 of 3"], ["num_conv_layers"]),
    (["hidden_dim: 192 of 256"], None), (["num_conv_layers"], None),
    (["num_conv_layers: 3 of 3"], None), (["num_conv_layers: 1 of 3"], None),
    (["num_conv_layers: 2 of 3", "num_conv_layers: 2 of 3"], None)],
    ids=["none", "depth", "width", "bare-key", "not-a-cut", "not-as-held",
         "twice"])
def test_reduced_holds_a_cut_in_depth_and_never_a_width(reduced, keys):
    """`reduced` in a configuration file: `"<Architecture key>: <held> of
    <published>"`, a key of depth as held, under the published count."""
    if keys is None:
        with pytest.raises(AssertionError):
            depth_cuts(reduced, ARCH)
    else:
        assert depth_cuts(reduced, ARCH) == keys


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert metric["source"] in SOURCES
    assert metric["better"] in ("higher", "lower") and metric["unit"]
    if "bound" in metric:  # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%"
        spec_path = os.path.join(REPO, "benchmark", "metrics",
                                 metric["name"] + ".json")
        with open(spec_path) as f:
            assert callable(cells.resolve_reader(json.load(f)["reader"]))


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and "workloads" not in setup


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_./-]+$")
    for path in BENCH["paths"]:
        assert plain.match(path) and len(path) <= 200
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert plain.match(rel), rel


@pytest.fixture
def throwaway_root(tmp_path):
    """A copy of BENCHMARK.json and the data files with one more
    configuration, traffic mix, per-layer metric and cell — new files and
    new entries only, nothing that was there is edited."""
    doc = config_doc("schnet-s2ef")
    doc["tiny"]["hidden_dim"] = 8
    root, bench, _ = throwaway(tmp_path, "schnet-wide", doc, "predict-few")
    with open(root / "benchmark" / "traffic" / "predict.json") as f:
        mix = json.load(f)
    mix["tiny"]["structures"] = 12
    (root / "benchmark" / "traffic" / "predict-few.json").write_text(
        json.dumps(mix))
    (root / "benchmark" / "metrics" / "predict_batches.json").write_text(
        json.dumps({"reader": "counters.value", "args": {"key": "batches"}}))
    bench["per_layer"].append({
        "name": "predict_batches", "unit": "batches", "better": "lower",
        "source": "program_counter", "layer": "serving",
        "moves": "infer_graphs_per_s",
        "workloads": ["schnet-wide.predict-few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_a_cell_a_configuration_a_mix_and_a_metric_are_added_as_files(
        throwaway_root):
    assert cells.problems(throwaway_root) == []
    cell = cells.load_cell("schnet-wide.predict-few", throwaway_root)
    assert cell.config_doc["tiny"]["hidden_dim"] == 8
    assert cell.traffic["tiny"]["structures"] == 12
    assert "predict_batches" in [m["name"] for m in cell.per_layer]
    rc, out, err = run_cell("schnet-wide.predict-few", trace=1,
                            root=throwaway_root)
    assert rc == 0, err[-2000:]
    line = json.loads(out[-1])
    assert LINE_KEYS <= set(line) and DEVICE_KEYS <= set(line["device"])
    assert line["correct"] is True and line["metrics"] == {}
    # 12 structures a round: the throw-away mix, not the one it was copied
    # from, drove the run
    assert line["attempted"] % 12 == 0 and line["attempted"] > 0
