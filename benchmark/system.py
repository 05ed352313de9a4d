"""The one place where the benchmark touches the program.

From the program the benchmark takes the system under test and its spans,
counters and program names; everything that decides a number (traffic,
timing, trace reduction, peaks, roofline arithmetic, the references and the
comparison behind ``correct``) lives beside this file. The composition below
follows ``hydragnn_tpu/run_training.py`` and ``run_prediction.py`` on their
default path, entry by entry, because neither entry point takes a seed for
the weights or a hook round the step (PERF.md section 7 lists both).
"""
from __future__ import annotations

import copy
import functools
import importlib
import json
import os
import types
from typing import Dict, List, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_CACHE_DIR = os.path.join(ROOT, ".bench_cache")
CHECK_STRUCTURES = 8


def apply_tiny(config_doc: Dict) -> Dict:
    """The CPU-rehearsal preset of a configuration file: its `tiny` block
    overrides widths, depth and data scale. Never used on the chip."""
    doc = copy.deepcopy(config_doc)
    tiny = doc["tiny"]
    arch = doc["hydragnn"]["NeuralNetwork"]["Architecture"]
    for key, value in tiny.items():
        if key in arch:
            arch[key] = value
    arch["output_heads"]["node"]["dim_headlayers"] = tiny["dim_headlayers"]
    arch["output_heads"]["node"]["num_headlayers"] = len(
        tiny["dim_headlayers"])
    data = doc["data"]
    for key in ("train_structures", "val_structures", "test_structures"):
        data[key] = tiny[key]
    data["params"]["max_atoms"] = tiny["max_atoms"]
    data["params"]["size_median"] = tiny["max_atoms"] // 2
    return doc


def load_pools(config_doc: Dict, cache_dir: str = DATA_CACHE_DIR
               ) -> Tuple[List, List, List]:
    """(train, validate, test) structure pools of a configuration. The pools
    come from the configuration's own `pool_seed`, not from ``--seed``: the
    padded shapes, the neighbour K, the PNA degree histogram and the serving
    buckets all follow from the data, so a pool that moved with the seed
    would compile new programs in every run. ``--seed`` orders and draws
    from the pools (`seeded_order`)."""
    from .data import s2ef_like
    data = config_doc["data"]
    return tuple(
        s2ef_like.load_or_generate(int(data[f"{split}_structures"]),
                                   int(data["pool_seed"]) + 7919 * offset,
                                   data["params"], cache_dir)
        for offset, split in enumerate(("train", "val", "test")))


def seeded_order(count: int, seed: int) -> np.ndarray:
    return np.random.RandomState(seed).permutation(count)


def check_structures(testset: Sequence, count: int = CHECK_STRUCTURES
                     ) -> List:
    """The structures every cell is checked on: evenly spaced through the
    test pool sorted by size, so small and large cells are both there and
    the reference compiles one shape per configuration."""
    by_size = sorted(range(len(testset)), key=lambda i: (
        testset[i].num_nodes, i))
    picks = np.linspace(0, len(by_size) - 1, count).round().astype(int)
    return [testset[by_size[i]] for i in picks]


DROPPED_EDGE = 100     # `drop_edges` leaves out one edge in so many


def drop_edges(sample):
    """`sample` as the plain reference reads it, with one edge in
    `DROPPED_EDGE` left out: the wrong-mask negative control of
    `jobs/checks.CONTROLS`."""
    keep = np.arange(len(sample.senders)) % DROPPED_EDGE != DROPPED_EDGE - 1
    return types.SimpleNamespace(
        x=sample.x, pos=sample.pos, num_nodes=sample.num_nodes,
        energy=sample.energy, forces=sample.forces,
        senders=sample.senders[keep], receivers=sample.receivers[keep],
        edge_shifts=sample.edge_shifts[keep])


def complete_config(config_doc: Dict, pools, batch_size: int,
                    training: Dict = None, serving: Dict = None) -> Dict:
    """The HydraGNN config as `run_training` would hold it after
    `update_config`, at the batch size the traffic mix states. A mix may
    also carry `training` keys (say, batch_packing) and a `serving` block
    (the top-level `Serving` of the config)."""
    from hydragnn_tpu.config import update_config
    config = copy.deepcopy(config_doc["hydragnn"])
    config["NeuralNetwork"]["Training"].update(training or {})
    config["NeuralNetwork"]["Training"]["batch_size"] = int(batch_size)
    if serving:
        config["Serving"] = dict(serving)
    return update_config(config, *pools)


def neighbor_format(config: Dict) -> bool:
    """The batch layout the entry points resolve (run_training.py:302-303,
    run_prediction.py:62-63): dense neighbour tables unless the config or
    HYDRAGNN_NEIGHBOR_FORMAT says otherwise."""
    from hydragnn_tpu.utils.envflags import env_flag
    arch = config["NeuralNetwork"]["Architecture"]
    return env_flag("HYDRAGNN_NEIGHBOR_FORMAT",
                    bool(arch.get("neighbor_format", True)))


BATCHNORM_PASSES = 64


def initialiser(model, calibration: Sequence):
    """key -> weights, made on the device in one jitted call. Where the
    model has BatchNorm layers, with the running statistics a trained
    model would carry: 64 train-mode passes over the `calibration`
    structures move them (momentum 0.9) onto those structures' own
    statistics. At flax's initial values (mean 0, variance 1) nothing is
    normalised in eval mode, SchNet's activations shrink to 1e-6 layer by
    layer, and ``softplus(x) - log 2`` there cancels to a few digits: two
    float32 evaluations of the same mathematics then differ by percents
    (PERF.md, PR 22). Where `model.init` makes no `batch_stats` collection
    there is nothing to calibrate: the collection comes back empty and no
    pass runs. Whoever keeps the function (a sweep over seeds) compiles it
    once."""
    import jax
    from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
    n = 64 * (sum(s.num_nodes for s in calibration) // 64 + 1)
    e = 64 * (sum(s.num_edges for s in calibration) // 64 + 1)
    batch = with_neighbor_format(collate(
        list(calibration), n_node=n, n_edge=e, n_graph=len(calibration) + 1,
        np_out=True))

    @jax.jit
    def init(key):
        variables = model.init(key, batch, train=False)
        if "batch_stats" not in variables:
            return {"params": variables["params"], "batch_stats": {}}

        def one_pass(_, stats):
            _, mutated = model.apply(
                {"params": variables["params"], "batch_stats": stats},
                batch, train=True, mutable=["batch_stats"])
            return mutated["batch_stats"]
        stats = jax.lax.fori_loop(0, BATCHNORM_PASSES, one_pass,
                                  variables["batch_stats"])
        return {"params": variables["params"], "batch_stats": stats}
    return init


def init_variables(model, calibration: Sequence, seed: int):
    """Weights from `seed` (`initialiser`)."""
    import jax
    return initialiser(model, calibration)(jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=None)
def _reference_fn(reference: str, arch_json: str, num_graphs: int,
                  train: bool):
    """The jitted reference of one configuration, one per (graphs, mode):
    a sweep over seeds calls it with new weights and compiles nothing."""
    import jax
    from .reference import common
    node_fn = importlib.import_module(
        f"benchmark.reference.{reference}").node_energies(
            json.loads(arch_json))
    return jax.jit(functools.partial(
        common.energies_and_forces, node_fn, num_graphs=num_graphs,
        train=train))


def reference_energy_forces(config_doc: Dict, config: Dict, variables,
                            samples: Sequence, train: bool,
                            float64: bool = False):
    """(E [G], F [N, 3], structure dict) of the plain reference on
    `samples`, as numpy, with the system's own weights. `float64`: the same
    computation in double precision on the CPU backend beside the chip
    (`calibrate tolerance --diagnose`: which side rounding moved)."""
    import jax
    from .reference import common
    arch = config["NeuralNetwork"]["Architecture"]
    struct = common.concat_structures(samples)
    name = os.path.splitext(os.path.basename(config_doc["reference"]))[0]
    fn = _reference_fn(name, json.dumps(arch, sort_keys=True,
                                  default=lambda a: a.tolist()),
                       len(samples), bool(train))
    plain = {"params": variables["params"],
             "batch_stats": variables.get("batch_stats", {})}
    arrays = {k: v for k, v in struct.items() if k not in ("energy",
                                                           "forces")}
    if float64:
        def wide(a):
            a = np.asarray(a)
            return a.astype(np.float64) if a.dtype.kind == "f" else a
        with jax.enable_x64(True), jax.default_device(
                jax.devices("cpu")[0]):
            energy, forces = jax.device_get(fn(
                jax.tree_util.tree_map(wide, plain),
                {k: wide(v) for k, v in arrays.items()}))
    else:
        energy, forces = fn(plain, arrays)
    return np.asarray(energy), np.asarray(forces), struct


def relative_error(got, want) -> float:
    """||got - want||_2 / ||want||_2 over all elements."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def device_report(memory_peak: int) -> Dict:
    """The `device` object of the result line, as JAX reports it."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak)}


def memory_peak_bytes(device) -> int:
    """Peak of a device's memory so far. The TPU runtime counts arrays
    under `peak_bytes_in_use` and the scratch memory of loaded programs
    under `peak_bytes_reserved` (read on a v5e, PR 22: a program whose
    `memory_analysis()` says 512 MiB of temporaries reserves 536,870,912
    bytes and leaves `peak_bytes_in_use` at its arguments). The two peaks
    need not coincide, so their sum is an upper bound, by at most the
    arrays' share, which is a few percent in every cell."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def enable_compile_cache() -> None:
    """The program's one cache rule (`utils/devices.enable_compile_cache`:
    $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache on a TPU), with
    JAX's two thresholds lowered in this process so that the many programs
    under one second are kept as well: PR 21 saw 157 of 166 programs
    rebuilt on every warm run because they fell under them."""
    import jax
    from hydragnn_tpu.utils.devices import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Training:
    """What `run_training` builds before its epoch loop, on the default
    path (fixed-shape batching, dense neighbour layout, async collation,
    no checkpoint): loaders, model, optimizer, jitted steps, placement."""

    def __init__(self, config: Dict, pools, num_shards: int, devices=None):
        import jax
        from hydragnn_tpu.config import build_model_config
        from hydragnn_tpu.models.create import create_model
        from hydragnn_tpu.preprocess.load_data import create_dataloaders
        from hydragnn_tpu.train.optimizer import select_optimizer
        from hydragnn_tpu.train.train_step import (make_eval_step,
                                                   make_train_step)
        from hydragnn_tpu.utils.envflags import resolve_packing
        self.config = config
        self.train_cfg = tcfg = config["NeuralNetwork"]["Training"]
        self.num_shards = num_shards
        self.mcfg = build_model_config(config)
        self.neighbor_format = neighbor_format(config)
        trainset, valset, testset = pools
        self.loaders = create_dataloaders(
            trainset, valset, testset, int(tcfg["batch_size"]),
            num_shards=num_shards, neighbor_format=self.neighbor_format,
            async_workers=tcfg.get("async_loader_workers"),
            cache_mb=tcfg.get("batch_cache_mb"),
            packing=resolve_packing(tcfg))
        self.model = create_model(self.mcfg)
        self.tx = select_optimizer(tcfg)
        f_w = tcfg.get("force_loss_weight", 1.0)
        kw = dict(loss_name=tcfg.get("loss_function_type", "mse"),
                  compute_grad_energy=bool(
                      tcfg.get("compute_grad_energy", False)),
                  energy_weight=float(tcfg.get("energy_loss_weight", 1.0)),
                  force_weight=f_w if f_w == "auto" else float(f_w))
        self.mesh = None
        if num_shards > 1:
            from hydragnn_tpu.parallel.mesh import make_mesh, shard_batch
            from hydragnn_tpu.parallel.spmd import (make_spmd_eval_step,
                                                    make_spmd_train_step)
            self.mesh = make_mesh((("data", num_shards),), devices=devices)
            self.train_step = make_spmd_train_step(
                self.model, self.mcfg, self.tx, self.mesh, **kw)
            self.eval_step = make_spmd_eval_step(
                self.model, self.mcfg, self.mesh, **kw)
            self.place = lambda b: shard_batch(b, self.mesh)
        else:
            self.train_step = make_train_step(self.model, self.mcfg,
                                              self.tx, **kw)
            self.eval_step = make_eval_step(self.model, self.mcfg, **kw)
            self.place = lambda b: jax.tree_util.tree_map(
                lambda a: None if a is None else jax.device_put(a), b)

    @functools.cached_property
    def _initialiser(self):
        return initialiser(self.model,
                           check_structures(self.loaders[2].dataset))

    def initial_state(self, seed: int):
        import jax
        from hydragnn_tpu.train.train_step import TrainState
        return TrainState.create(
            self._initialiser(jax.random.PRNGKey(seed)), self.tx)

    def collate(self, samples: Sequence):
        """`samples` as one host batch of the train loader's padded shape;
        shard i of a data-parallel step holds samples[i::num_shards]."""
        import jax
        from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
        loader = self.loaders[0]
        parts = [collate(list(samples[i::self.num_shards]),
                         n_node=loader.n_node, n_edge=loader.n_edge,
                         n_graph=loader.n_graph, np_out=True)
                 for i in range(self.num_shards)]
        if loader.neighbor_k is not None:
            parts = [with_neighbor_format(b, k=loader.neighbor_k)
                     for b in parts]
        if self.num_shards == 1:
            return parts[0]
        return jax.tree_util.tree_map(lambda *a: np.stack(a), *parts)


def make_engine(config: Dict, model, mcfg, variables, reference: Sequence,
                pools, buckets=None):
    """An energy+force `InferenceEngine` built as
    `run_prediction._predict_with_engine` builds its engine (knobs from
    `resolve_serving`; bucket shapes from `reference`; the neighbour K from
    all three `pools`, as the loaders pin it), with `ef_forward` on as
    `chip_smoke.py` has it. `buckets` replaces the ladder."""
    from hydragnn_tpu.datasets.async_loader import neighbor_budget
    from hydragnn_tpu.serving.config import resolve_serving
    from hydragnn_tpu.serving.engine import InferenceEngine
    serving = resolve_serving(config)
    dense = neighbor_format(config)
    everything = [s for pool in pools for s in pool]
    return InferenceEngine(
        model, variables, mcfg, reference_samples=reference, buckets=buckets,
        max_batch_size=serving.max_batch_size,
        max_wait_ms=serving.max_wait_ms, num_buckets=serving.num_buckets,
        bucket_multiple=serving.bucket_multiple, neighbor_format=dense,
        neighbor_k=neighbor_budget(everything) if dense else None,
        compute_dtype=serving.precision, max_queue=serving.max_queue,
        default_deadline_ms=serving.deadline_ms or None,
        breaker_threshold=serving.breaker_threshold,
        breaker_reset_s=serving.breaker_reset_s,
        structure_config=config if serving.structure else None,
        md_skin=serving.md_skin, ef_forward=True)


# ------------------------------------------- lowering for a described chip

def _abstract(tree, sharding):
    """The shapes of `tree`'s arrays, placed by `sharding`."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: None if a is None else jax.ShapeDtypeStruct(
            np.shape(a), a.dtype, sharding=sharding), tree)


def lower_train_step(config_doc: Dict, pools, graphs_per_chip: int,
                     chips: int, devices):
    """The train step of a configuration, lowered for `devices` (described
    or attached: shapes only, nothing runs) at `graphs_per_chip`; with it
    the padded shape. Compile what comes back to hear what the chip's
    compiler would say, and how much memory the step needs."""
    import jax
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    config = complete_config(config_doc, pools, graphs_per_chip * chips)
    comp = Training(config, pools, chips, devices=devices)
    loader = comp.loaders[0]
    batch = comp.collate(pools[0][:chips])
    state = jax.eval_shape(lambda: comp.initial_state(0))
    if chips == 1:
        one = SingleDeviceSharding(devices[0])
        args = _abstract(state, one), _abstract(batch, one)
    else:
        mesh = comp.mesh
        args = (_abstract(state, NamedSharding(mesh, PartitionSpec())),
                _abstract(batch, NamedSharding(mesh, PartitionSpec("data"))))
    shape = {"n_node": loader.n_node, "n_edge": loader.n_edge,
             "neighbor_k": loader.neighbor_k}
    return comp.train_step.lower(*args), shape


def lower_largest_bucket(config_doc: Dict, pools, traffic: Dict,
                         max_batch_size: int, sharding):
    """The energy+force forward of a serving mix's LARGEST bucket at
    `max_batch_size`, lowered for one (described) chip."""
    import jax
    from hydragnn_tpu.config import build_model_config
    from hydragnn_tpu.models.create import create_model
    serving = dict(traffic["serving"], max_batch_size=int(max_batch_size))
    config = complete_config(
        config_doc, pools,
        config_doc["hydragnn"]["NeuralNetwork"]["Training"]["batch_size"],
        serving=serving)
    mcfg = build_model_config(config)
    model = create_model(mcfg)
    variables = jax.eval_shape(
        lambda: init_variables(model, check_structures(pools[2]), 0))
    engine = make_engine(config, model, mcfg, variables,
                         pools[0][:int(traffic["structures"])], pools)
    try:
        lowered, shape = lower_bucket(engine, variables, engine.buckets[-1],
                                      pools[0][0], sharding)
        shape["buckets"] = len(engine.buckets)
    finally:
        engine.shutdown()
    return lowered, shape


def lower_bucket(engine, variables, bucket, proto_sample, sharding):
    """One bucket's forward of an engine, lowered for `sharding`, from a
    proto batch as `InferenceEngine.warmup` builds it. The engine has no
    public way to lower a bucket, so this reads its jitted forward
    (`_jit_forward`); nothing of a measured run depends on it."""
    from hydragnn_tpu.graphs.batch import collate, with_neighbor_format
    proto = collate([proto_sample], n_node=bucket.n_node,
                    n_edge=bucket.n_edge, n_graph=bucket.n_graph,
                    np_out=True).replace(y_graph=None, y_node=None,
                                         energy=None, forces=None)
    if engine.neighbor_k is not None:
        proto = with_neighbor_format(proto, k=engine.neighbor_k)
    lowered = engine._jit_forward.lower(_abstract(variables, sharding),
                                        _abstract(proto, sharding))
    return lowered, {"n_node": bucket.n_node, "n_edge": bucket.n_edge,
                     "n_graph": bucket.n_graph}
