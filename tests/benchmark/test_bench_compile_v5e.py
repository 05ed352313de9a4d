"""The four cells' main programs at FULL width, compiled for a described
TPU v5e (`v5e:2x2`) by the TPU compiler that is installed here: what the
chip's compiler would refuse, and how much memory each program needs, at
no chip time (on-chip-measurement guide, section 2). Nothing runs, so this
says nothing about results or times.

The topology is described inside a fixture and only here: one process at a
time may load the TPU's library. Shapes come from a reduced pool of the
real generator (160 structures, its largest the law's 225 atoms), so the
padded shapes are the cells' up to the rounding of the edge budget.
"""
import json
import os

import pytest

from benchmark import cells, system
from benchmark.data import s2ef_like

from bench_testlib import REPO

GIB = 2 ** 30
# the rule of the train cells' batch (PERF.md section 4): a step may take
# 12 GiB of a chip's 15.75; a cell must fill a quarter of the chip
STEP_LIMIT_GIB = 12.0
FLOOR_BYTES = 0.25 * 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def doc_of(config):
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pools():
    params = doc_of("pnaplus-s2ef")["data"]["params"]
    train = s2ef_like.generate(160, 11, params)
    assert max(s.num_nodes for s in train) == params["max_atoms"]
    return train, train[:8], train[8:16]


@pytest.mark.parametrize("workload", ["pnaplus-s2ef.train",
                                      "pnaplus-s2ef.train-dp4"])
def test_train_step_compiles_and_fits(topo, pools, workload):
    cell = cells.load_cell(workload)
    graphs = int(cell.traffic["graphs_per_chip"])
    lowered, shape = system.lower_train_step(
        cell.config_doc, pools, graphs, cell.chips,
        topo.devices[:cell.chips])
    arch = cell.config_doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["num_conv_layers"]) == (200, 6)
    assert shape["n_node"] >= 225 * graphs and shape["neighbor_k"] == 56
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes / GIB <= STEP_LIMIT_GIB
    assert mem.temp_size_in_bytes >= FLOOR_BYTES
    text = compiled.as_text()
    assert ("all-reduce" in text) == (cell.chips > 1), \
        "the gradient mean is the step's collective, and only across chips"
    # twice the batch no longer meets the rule: the cell's batch is the
    # largest power of two that does (one chip is enough to show it)
    if cell.chips == 1:
        twice, _ = system.lower_train_step(cell.config_doc, pools,
                                           2 * graphs, 1, topo.devices[:1])
        assert twice.compile().memory_analysis().temp_size_in_bytes / GIB \
            > STEP_LIMIT_GIB


@pytest.mark.parametrize("workload", ["schnet-s2ef.predict",
                                      "schnet-s2ef.serve-open"])
def test_engine_buckets_compile_and_the_largest_fills_the_chip(topo, pools,
                                                               workload):
    import jax
    from jax.sharding import SingleDeviceSharding
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.models.create import create_model
    cell = cells.load_cell(workload)
    one = SingleDeviceSharding(topo.devices[0])
    doc = cell.config_doc
    arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["num_filters"], arch["num_gaussians"],
            arch["num_conv_layers"]) == (1024, 256, 200, 5)
    config = system.complete_config(doc, pools, 64,
                                    serving=cell.traffic["serving"])
    mcfg = build_model_config(config)
    model = create_model(mcfg)
    variables = jax.eval_shape(
        lambda: system.init_variables(model, pools[2], 0))
    engine = system.make_engine(config, model, mcfg, variables, pools[0],
                                pools)
    try:
        assert engine.ef_forward and engine.neighbor_k == 56
        assert engine.max_batch_size == 128
        assert bool(cell.traffic["serving"].get("structure")) == (
            cell.traffic["job"] == "serve_open")
        # the bucket a lone request runs on, and the fullest one
        for bucket, fills in ((engine.buckets[0], False),
                              (engine.buckets[-1], True)):
            lowered, _ = system.lower_bucket(engine, variables, bucket,
                                             pools[0][0], one)
            mem = lowered.compile().memory_analysis()
            assert mem.temp_size_in_bytes / GIB <= STEP_LIMIT_GIB
            assert (mem.temp_size_in_bytes >= FLOOR_BYTES) == fills
    finally:
        engine.shutdown()
