"""What PR 28 added to the benchmark: the DimeNet++ configuration, its
plain reference, its roofline count, the two train cells
(`dimenetpp-s2ef.train`, `schnet-s2ef.train`) and four per-layer metrics.
The harness's own parametrised tests pick the new configuration and cells
up by themselves (`test_bench_reference.py`, `test_bench_rehearsal.py`,
`test_bench_scopes.py`); here is what they do not hold: the entries, the
hand count, the kept sweeps of EVERY cell against the tolerances as they
stand, and the 12 GiB rule. Nothing runs on a chip.

Two tests of the harness pinned the benchmark to its extent at PR 26 and
failed on any appended cell or per-layer metric from PR 28 to PR 33, which
repaired them: `test_bench_checks.py::
test_the_kept_sweeps_hold_the_tolerances_as_they_stand` and
`test_bench_scopes.py::test_problems_empty_and_new_entries_resolve`.
`test_every_cells_sweeps_hold_the_tolerances_as_they_stand` and
`test_entries_are_appended_and_resolve` below assert what those two
assert, and since PR 33 find PR 28's own entries by name as well: no test
here counts entries from the end of a list that later PRs append to.
"""
import json
import os

import pytest

from benchmark import cells, system
from benchmark.data import s2ef_like
from benchmark.jobs import checks
from benchmark.roofline import common, dimenetconv

from bench_testlib import REPO, kept_sweeps

GIB = 2 ** 30
STEP_LIMIT_GIB = 12.0
FLOOR_BYTES = 0.25 * 16e9
CELL = "dimenetpp-s2ef.train"
NEW_CELLS = [CELL, "schnet-s2ef.train"]
NEW_METRICS = ["step_pair_basis_share", "step_pair_message_share",
               "step_dimenet_roofline_share", "step_cfconv_roofline_share"]


# ------------------------------------------------------ the entries -----

def test_entries_are_appended_and_resolve():
    from test_bench_scopes import NEW_METRICS as PR26
    bench = cells.load_benchmark()
    assert cells.problems() == []
    # appended after what PR 26 left and kept together, wherever later
    # PRs' entries leave them (PR 33: by name, not counted from the end)
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("dimenetpp-s2ef") == 2
    workloads = [w["name"] for w in bench["workloads"]]
    at = workloads.index(NEW_CELLS[0])
    assert at == 4 and workloads[at:at + 2] == NEW_CELLS
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 4] == NEW_METRICS
    # PR 26's fifteen stay together, right before them
    assert set(names[first - 15:first]) == PR26
    for entry in bench["configs"][2:3] + bench["workloads"][at:at + 2]:
        assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["job"] == "train"
    reported = {m["name"] for m in cell.per_layer}
    assert {"step_pair_basis_share", "step_pair_message_share",
            "step_dimenet_roofline_share", "step_device_ms",
            "pad_node_share"} <= reported
    assert "step_roofline_share" not in reported, "its conv is pnaconv"
    assert "step_cfconv_roofline_share" in {
        m["name"] for m in cells.load_cell("schnet-s2ef.train").per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"train_graphs_per_s",
                                                   "setup_s"}


def test_dimenetconv_hand_count():
    arch = {"hidden_dim": 8, "int_emb_size": 4, "basis_emb_size": 2,
            "out_emb_size": 6, "num_radial": 3, "num_spherical": 2,
            "num_before_skip": 1, "num_after_skip": 2, "num_conv_layers": 2,
            "input_dim": 1,
            "output_heads": {"node": {"type": "mlp",
                                      "dim_headlayers": [4, 4]}}}
    flops, hbm = dimenetconv.forward(arch, atoms=10, edges=100)
    pairs = 100 * 100 / 10 - 100
    assert pairs == 900
    once = 4 * (pairs * 6 + 100 * 3)
    per_pair = 2 * 6 * 2 + 2 * 2 * 4 + 2 * 4
    per_edge = (2 * 3 * 8 + 6 * 64 + 4 * 64 + 2 * 3 * 2 + 2 * 2 * 8 + 8
                + 4 * 8 * 4 + 2 * 64 * 4 + 2 * 3 * 8 + 2 * 8)
    assert (per_pair, per_edge) == (48, 1444)
    first = 10 * (2 * 1 * 8 + 2 * 8 * 6 + 2 * 36 + 2 * 6 * 8)
    later = 10 * (2 * 8 * 8 + 2 * 8 * 6 + 2 * 36 + 2 * 6 * 8)
    head = 10 * (2 * 8 * 4 + 2 * 4 * 4 + 2 * 4 * 1)
    want = (once + 2 * (pairs * per_pair + 100 * per_edge) + first + later
            + head)
    assert flops == want == 405760
    assert hbm == 4 * ((pairs * 4 + 100 * 8 + 10 * 9)
                       + (pairs * 4 + 100 * 8 + 10 * 16)) == 36200
    # a conv-type head's layers are blocks of the same kind, with BatchNorm
    arch["output_heads"]["node"] = {"type": "conv", "dim_headlayers": [8]}
    assert dimenetconv.forward(arch, 10, 100) == (597200.0, 54440.0)
    # fewer edges than atoms: no pair, never a negative count
    assert dimenetconv.forward(arch, 10, 5)[0] > 0


def test_the_published_widths_and_which_roof_binds():
    cell = cells.load_cell(CELL)
    arch = cell.config_doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["out_emb_size"], arch["int_emb_size"],
            arch["basis_emb_size"], arch["num_radial"],
            arch["num_spherical"]) == (192, 192, 64, 8, 6, 7)
    assert (arch["radius"], arch["max_neighbours"]) == (6.0, 50)
    # the published depth, three blocks over the pair space: two encoder
    # layers and the one layer of the conv-type head, whose BatchNorm is
    # what the harness's initialiser calibrates
    head = arch["output_heads"]["node"]
    assert head == {"num_headlayers": 1, "dim_headlayers": [192],
                    "type": "conv"}
    assert arch["num_conv_layers"] + len(head["dim_headlayers"]) == 3 \
        == cell.config_doc["assumed"]["num_blocks"]
    assert cell.config_doc["reduced"] == []
    # a mean s2ef_like structure: 73.8 atoms, degree 33
    flops, hbm = dimenetconv.forward({**arch, "input_dim": 1}, 73.8,
                                     73.8 * 33)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert common.least_seconds(flops, hbm, peak)[1] == "hbm"
    # same pools and K as the other configurations
    for other in ("pnaplus-s2ef.train", "schnet-s2ef.predict"):
        assert cells.load_cell(other).config_doc["data"] \
            == cell.config_doc["data"]


def test_every_cells_sweeps_hold_the_tolerances_as_they_stand(capsys):
    """Every `benchmark/calibration*.json` (TPU v5 lite; PR 25's sweeps of
    the four first cells, PR 28's of its two) judged by the present
    HIGHEST_TOL: every limit 3 x above the widest sound reading of every
    sweep kept and below every control that separates, every control 3 x
    over the limit of some number; every cell of the benchmark has a sweep
    and every tolerance judges some number. A PR that moves a tolerance, or
    adds a cell, without a sweep beside it fails here."""
    from benchmark import calibrate
    verdicts, judged_by = {}, set()
    for path, kept in kept_sweeps("tolerance").items():
        assert calibrate.main(["verdict", path]) == 0
        verdicts.update(json.loads(capsys.readouterr().out))
        judged_by |= {dist["judged_by"] for sweep in kept.values()
                      for dist in sweep["at_highest"].values()
                      if dist["judged_by"]}
    assert set(verdicts) == {w["name"] for w in
                             cells.load_benchmark()["workloads"]}
    assert judged_by == set(checks.HIGHEST_TOL)
    for cell, verdict in verdicts.items():
        assert verdict["holds"] is True, (cell, verdict)
        assert set(verdict["controls"]) == set(checks.CONTROLS)
        for name, held in verdict["limits"].items():
            assert held["room_above_sound"] >= 3, (cell, name)
            # no limit sits above a control that separates
            assert all(reading > held["limit"]
                       for reading in held["held_against"].values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference",
                           "dimenetpp_s2ef.py")) as f:
        source = f.read()
    assert "hydragnn_tpu" not in source.split('"""', 2)[2]
    assert "build_neighbor_tables" not in source.split('"""', 2)[2]


# ------------------------------ the 12 GiB rule, for a described v5e ----

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def pools():
    params = cells.load_cell(CELL).config_doc["data"]["params"]
    train = s2ef_like.generate(160, 11, params)
    assert max(s.num_nodes for s in train) == params["max_atoms"]
    return train, train[:8], train[8:16]


def temp_gib(topo, pools, cell, graphs):
    """GiB of temporaries of the cell's step at `graphs` a chip; more
    than the chip has when its compiler refuses the program."""
    import jax
    try:
        lowered, shape = system.lower_train_step(
            cell.config_doc, pools, graphs, 1, topo.devices[:1])
        assert shape["n_node"] >= 225 * graphs and shape["neighbor_k"] == 56
        return lowered.compile().memory_analysis().temp_size_in_bytes / GIB
    except jax.errors.JaxRuntimeError as refused:
        assert "RESOURCE_EXHAUSTED" in str(refused)
        return float("inf")


@pytest.mark.parametrize("workload,graphs", [(CELL, 4),
                                             ("schnet-s2ef.train", 16)])
def test_train_step_compiles_and_fits_at_its_batch_only(
        topo, pools, workload, graphs):
    cell = cells.load_cell(workload)
    assert int(cell.traffic["graphs_per_chip"]) == graphs <= 32
    assert cell.traffic["job"] == "train" and cell.chips == 1
    assert not cell.traffic.get("training"), "batch_packing off, as written"
    at = temp_gib(topo, pools, cell, graphs)
    assert at <= STEP_LIMIT_GIB and at * GIB >= FLOOR_BYTES
    # twice the batch no longer meets the rule: the cell's batch is the
    # largest power of two that does
    assert temp_gib(topo, pools, cell, 2 * graphs) > STEP_LIMIT_GIB
