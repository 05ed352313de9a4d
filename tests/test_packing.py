"""Budget-packed graph batching (graphs/packing.py + loader wiring):
determinism across runs/ranks, no-drop/no-dup invariants, overflow
fallback, waste targets, async bitwise equality, the collate
field-homogeneity guard, and loss-trajectory equivalence vs unpacked
batching on a tiny fixture (docs/packing.md)."""
import dataclasses

import numpy as np
import pytest

from hydragnn_tpu.graphs.batch import GraphSample, collate
from hydragnn_tpu.graphs.packing import (PackBudget, check_fits,
                                         choose_budget, pack_order,
                                         plan_padding_stats, plan_steps,
                                         sample_sizes)
from hydragnn_tpu.datasets.loader import GraphDataLoader


def skewed_samples(num=192, lo=8, hi=80, deg=8, seed=0, heads=("graph",)):
    """Size-skewed random graphs (uniform lo..hi nodes, fixed degree) —
    the workload where fixed-shape batching pays ~1 - mean/max of its
    node slots as padding."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(num):
        n = int(rng.randint(lo, hi + 1))
        send = np.repeat(np.arange(n), deg).astype(np.int32)
        recv = rng.randint(0, n, n * deg).astype(np.int32)
        kw = {}
        if "graph" in heads:
            kw["y_graph"] = np.asarray([rng.randn()], np.float32)
        if "node" in heads:
            kw["y_node"] = rng.rand(n, 1).astype(np.float32)
        out.append(GraphSample(
            x=rng.rand(n, 1).astype(np.float32),
            pos=rng.rand(n, 3).astype(np.float32) * 10,
            senders=send, receivers=recv, **kw))
    return out


@pytest.fixture(scope="module")
def pool():
    return skewed_samples()


def _flat(selections):
    return [i for sel in selections for shard in sel for i in shard]


def _assert_batches_identical(a, b, ctx=""):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if va is None:
            assert vb is None, f"{ctx}: {f.name} None mismatch"
            continue
        va, vb = np.asarray(va), np.asarray(vb)
        assert va.dtype == vb.dtype, f"{ctx}: {f.name} dtype"
        assert np.array_equal(va, vb), f"{ctx}: {f.name} values"


# ------------------------------------------------------------- planner

def test_pack_plan_deterministic(pool):
    """Same (seed, epoch, budget) -> bitwise-identical plan, across
    independent loader instances and repeated epochs."""
    mk = lambda: GraphDataLoader(pool, batch_size=32, shuffle=True,
                                 seed=7, packing=True)
    a, b = mk(), mk()
    for epoch in (0, 1, 5):
        a.set_epoch(epoch)
        b.set_epoch(epoch)
        assert a._selections() == b._selections()
        assert len(a._selections()) > 0
    # and pack_order itself is a pure function of its inputs
    nodes, edges = sample_sizes(pool)
    budget = choose_budget(nodes, edges, 32)
    order = np.random.RandomState(3).permutation(len(pool))
    assert pack_order(order, nodes, edges, budget) == \
        pack_order(order, nodes, edges, budget)


def test_no_sample_dropped_or_duplicated(pool):
    """Every dataset index appears in exactly one bin of the plan."""
    ld = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=1,
                         packing=True)
    for epoch in (0, 2):
        ld.set_epoch(epoch)
        flat = _flat(ld._selections())
        assert sorted(flat) == list(range(len(pool)))


def test_rank_sliced_plans_agree(pool):
    """Multi-process contract: every rank slices the SAME global plan —
    identical step counts, disjoint samples, and the union matches the
    single-rank grouping of the same global bins."""
    mk = lambda r, n: GraphDataLoader(pool, batch_size=32, shuffle=True,
                                      seed=7, packing=True,
                                      pack_rank=r, pack_nproc=n)
    r0, r1 = mk(0, 2), mk(1, 2)
    assert len(r0) == len(r1) > 0
    i0, i1 = set(_flat(r0._selections())), set(_flat(r1._selections()))
    assert not (i0 & i1), "ranks overlap"
    # interleaved rank selections == the global plan's leading groups
    nodes, edges = sample_sizes(pool)
    bins = pack_order(r0._order(), nodes, edges, r0.pack_budget)
    merged = []
    for s0, s1 in zip(r0._selections(), r1._selections()):
        merged.extend(list(s0) + list(s1))
    assert merged == list(bins[:len(merged)])


def test_equal_step_counts_across_epochs_and_ranks(pool):
    """Ranks must execute the same step count on EVERY epoch (collective
    lockstep), even as the realized plan length varies with the shuffle."""
    mk = lambda r: GraphDataLoader(pool, batch_size=32, shuffle=True,
                                   seed=11, packing=True,
                                   pack_rank=r, pack_nproc=3)
    lds = [mk(r) for r in range(3)]
    for epoch in range(4):
        lens = []
        for ld in lds:
            ld.set_epoch(epoch)
            lens.append(len(ld))
        assert len(set(lens)) == 1 and lens[0] > 0


def test_budget_overflow_raises_clearly(pool):
    big = skewed_samples(num=4, lo=8, hi=16, seed=2)
    big.append(skewed_samples(num=1, lo=500, hi=500, seed=3)[0])
    nodes, edges = sample_sizes(big)
    budget = PackBudget(n_node=64, n_edge=1024, n_graph=9)
    with pytest.raises(ValueError, match="does not fit the pack budget"):
        check_fits(nodes, edges, budget)
    with pytest.raises(ValueError, match="does not fit the pack budget"):
        pack_order(list(range(len(big))), nodes, edges, budget)
    # the loader surfaces the same error at plan time
    ld = GraphDataLoader(big, batch_size=4, shuffle=False, packing=True,
                         pack_budget=budget)
    with pytest.raises(ValueError, match="does not fit the pack budget"):
        len(ld)


def test_padding_waste_targets(pool):
    """The acceptance numbers, host-side: packed <= 0.15 padding on the
    8-80 skewed pool vs >= 0.4 for fixed-shape batching."""
    packed = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=0,
                             packing=True)
    fixed = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=0)
    ps, fs = packed.padding_stats(), fixed.padding_stats()
    assert ps["packing"] == "packed" and fs["packing"] == "fixed"
    assert ps["padding_frac_nodes"] <= 0.15, ps
    assert ps["padding_frac_edges"] <= 0.15, ps
    assert fs["padding_frac_nodes"] >= 0.4, fs
    # same samples processed either way
    assert ps["real_graphs"] == fs["real_graphs"] == len(pool)


def test_packed_shapes_static_single_program(pool):
    """Every packed batch shares ONE padded shape (the one-compiled-
    program contract) while the real graph count varies per batch."""
    ld = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=4,
                         packing=True)
    shapes, counts = set(), []
    for b in ld:
        shapes.add(tuple(
            None if getattr(b, f.name) is None
            else np.asarray(getattr(b, f.name)).shape
            for f in dataclasses.fields(b)))
        counts.append(int(np.asarray(b.graph_mask).sum()))
    assert len(shapes) == 1
    assert len(set(counts)) > 1, "skewed pool should pack variable counts"
    assert sum(counts) == len(pool)


def test_packed_multishard_pads_tail_with_empty_shards(pool):
    """num_shards > 1 without drop_last: the tail group is padded with
    all-padding shards (proto-sample branch) — no sample dropped, shapes
    static."""
    ld = GraphDataLoader(pool[:37], batch_size=8, num_shards=2,
                         shuffle=False, drop_last=False, packing=True)
    total, shapes = 0, set()
    for b in ld:
        shapes.add(np.asarray(b.x).shape)
        total += int(np.asarray(b.graph_mask).sum())
    assert total == 37
    assert len(shapes) == 1


def test_packed_async_bitwise_identical_to_sync(pool):
    """The async loader path must deliver the exact synchronous packed
    stream (nested selections ride the same worker pool + cache keys)."""
    mk = lambda workers, cache: GraphDataLoader(
        pool, batch_size=24, shuffle=True, seed=11, packing=True,
        neighbor_format=True, async_workers=workers, cache_mb=cache)
    def stream(ld, epochs=2):
        out = []
        for e in range(epochs):
            ld.set_epoch(e)
            out.extend(ld)
        return out
    sync, asyn = stream(mk(0, 0)), stream(mk(3, 64))
    assert len(sync) == len(asyn) > 0
    for i, (a, b) in enumerate(zip(sync, asyn)):
        _assert_batches_identical(a, b, ctx=f"packed batch {i}")


def test_packed_nonthreadsafe_dataset_flat_fetch(pool):
    """Non-list datasets are fetched on the consumer thread via the
    flattened nested selection (async_loader's _flat_indices path)."""
    import threading

    class RecordingDataset:
        def __init__(self, s):
            self._s = list(s)
            self.threads = set()

        def __len__(self):
            return len(self._s)

        def __getitem__(self, i):
            self.threads.add(threading.current_thread().name)
            return self._s[i]

    ds = RecordingDataset(pool[:40])
    ld = GraphDataLoader(ds, batch_size=8, shuffle=True, seed=0,
                         packing=True, async_workers=2, cache_mb=0)
    got = sum(int(np.asarray(b.graph_mask).sum()) for b in ld)
    assert got == 40
    assert ds.threads == {"MainThread"}


def test_resolve_packing_precedence_and_strictness(monkeypatch):
    """HYDRAGNN_PACKING overrides Training.batch_packing, but only with
    explicit boolean spellings — a typo falls back to the config default
    (packing flips batch composition; it must not switch on silently)."""
    from hydragnn_tpu.utils.envflags import resolve_packing
    monkeypatch.delenv("HYDRAGNN_PACKING", raising=False)
    assert resolve_packing({}) is False
    assert resolve_packing({"batch_packing": True}) is True
    monkeypatch.setenv("HYDRAGNN_PACKING", "1")
    assert resolve_packing({}) is True
    monkeypatch.setenv("HYDRAGNN_PACKING", "0")
    assert resolve_packing({"batch_packing": True}) is False
    monkeypatch.setenv("HYDRAGNN_PACKING", "ture")  # typo: not truthy
    assert resolve_packing({}) is False
    assert resolve_packing({"batch_packing": True}) is True


def test_overflow_error_names_dataset_index_not_stream_position():
    """check_fits must report the DATASET index of the offending sample
    even when the epoch order is shuffled (the error tells users which
    sample to filter)."""
    from hydragnn_tpu.graphs.packing import PackBudget, pack_order
    nodes = np.asarray([4, 4, 500, 4])
    edges = np.asarray([8, 8, 8, 8])
    budget = PackBudget(n_node=64, n_edge=64, n_graph=8)
    with pytest.raises(ValueError, match="sample 2 "):
        pack_order([3, 2, 1, 0], nodes, edges, budget)


def test_multidataset_loader_packs_shared_budget(pool):
    """Heterogeneous multi-dataset mode: all shard streams pack against
    ONE budget (union of member datasets) — one compiled program — and
    padding_stats aggregates across shards."""
    from hydragnn_tpu.parallel.multidataset import MultiDatasetLoader
    small = skewed_samples(num=24, lo=8, hi=24, seed=8)
    ld = MultiDatasetLoader([list(pool[:48]), small], batch_size=16,
                            num_shards=2, seed=3, packing=True)
    assert all(l.pack_budget == ld.loaders[0].pack_budget
               for l in ld.loaders)
    shapes = set()
    for i, b in enumerate(ld):
        shapes.add(np.asarray(b.x).shape)
        if i >= 4:
            break
    assert len(shapes) == 1
    st = ld.padding_stats()
    assert st["packing"] == "packed"
    assert 0.0 <= st["padding_frac_nodes"] < 1.0


# --------------------------------------------------- collate homogeneity

def test_collate_mixed_fields_raise_clearly(pool):
    ok = skewed_samples(num=3, seed=5, heads=("graph",))
    bad = skewed_samples(num=1, seed=6, heads=())[0]  # no y_graph
    with pytest.raises(ValueError, match="field 'y_graph'"):
        collate(ok + [bad])
    # missing-on-0 / present-later direction
    with pytest.raises(ValueError, match="field 'y_graph'"):
        collate([bad] + ok)
    # width mismatch
    wide = skewed_samples(num=1, seed=7, heads=("graph",))[0]
    wide.y_graph = np.zeros(3, np.float32)
    with pytest.raises(ValueError, match="width"):
        collate(ok + [wide])
    with pytest.raises(ValueError, match="at least one sample"):
        collate([])


# ------------------------------------------- training-level equivalence

def test_loss_trajectory_equivalence_packed_vs_fixed():
    """Packed batching must train equivalently to fixed-shape batching on
    a tiny fixture: both see every sample once per epoch (num_shards=1
    packs drop nothing), so the loss trajectories should land in the
    same place (different batch compositions => not bitwise, but close).

    Re-conditioned in PR 21, when the jax 0.9.0 compiler moved this test
    out of its band. Two causes, both the TEST's:
    * it scored each run on its OWN loader's batches with an unweighted
      mean of per-batch means. Packed batches hold unequal graph counts,
      so that number differs for identical weights — after one epoch 0.166
      (packed) vs 0.097 (fixed) where a common graph-weighted evaluation
      reads 0.0969 vs 0.0973. Both runs are now scored on ONE fixed-shape,
      unshuffled loader (six full batches of eight).
    * at the fixture's lr 5e-3 with six steps per epoch a single run's
      loss swings 2-5x from epoch to epoch, wider than any band between
      two runs; which side of it epoch 6 lands on is decided by last-bit
      rounding. At lr 1e-3 both trajectories are smooth and fall 2x in
      six epochs; measured packed-vs-fixed difference there: 7-13% over
      three shuffle seeds. The band is 25%."""
    import jax
    from hydragnn_tpu.config import build_model_config, update_config
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.train_step import (TrainState, make_eval_step,
                                               make_train_step)
    from tests.deterministic_data import deterministic_graph_dataset
    from tests.utils import make_config

    samples = deterministic_graph_dataset(num_configs=48, heads=("graph",))
    cfg = make_config("PNA", heads=("graph",), hidden_dim=8,
                      num_conv_layers=1, radius=1.0)
    cfg["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"] = 1e-3
    cfg = update_config(cfg, samples)
    mcfg = build_model_config(cfg)
    model = create_model(mcfg)
    tx = select_optimizer(cfg["NeuralNetwork"]["Training"])
    score_on = GraphDataLoader(samples, batch_size=8, shuffle=False,
                               async_workers=0)

    def train(packing, epochs=6):
        ld = GraphDataLoader(samples, batch_size=8, shuffle=True, seed=0,
                             packing=packing, async_workers=0)
        variables = init_params(model, next(iter(ld)))
        state = TrainState.create(variables, tx)
        step = make_train_step(model, mcfg, tx, loss_name="mse",
                               donate=False)
        evl = make_eval_step(model, mcfg, loss_name="mse")
        losses = []
        for e in range(epochs):
            ld.set_epoch(e)
            for b in ld:
                state, _ = step(state, b)
            per_batch = [float(np.asarray(evl(state, b)[0]["loss"]))
                         for b in score_on]
            losses.append(sum(per_batch) / len(per_batch))
        return losses

    fixed = train(False)
    packed = train(True)
    assert packed[-1] < 0.6 * packed[0], f"packed did not learn: {packed}"
    assert fixed[-1] < 0.6 * fixed[0], f"fixed did not learn: {fixed}"
    assert abs(packed[0] - fixed[0]) / fixed[0] < 0.02, (fixed, packed)
    assert abs(packed[-1] - fixed[-1]) / fixed[-1] < 0.25, (fixed, packed)


# ------------------------------------------------- CI smoke perf guard

def test_packed_smoke_perf_guard(pool):
    """Deterministic FLOP-proxy guard (no wall-clock flakiness): on the
    skewed pool the packed plan must execute >= 1.3x fewer node slots
    than fixed-shape batching for the same samples — the padding FLOPs
    the tentpole removes. Prints the numbers so CI logs carry them."""
    packed = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=2,
                             packing=True)
    fixed = GraphDataLoader(pool, batch_size=32, shuffle=True, seed=2)
    slots_packed = len(packed) * packed.num_shards * packed.n_node
    slots_fixed = len(fixed) * fixed.num_shards * fixed.n_node
    print(f"node slots packed={slots_packed} fixed={slots_fixed} "
          f"ratio={slots_fixed / slots_packed:.2f} "
          f"pad_packed={packed.padding_stats()['padding_frac_nodes']:.3f} "
          f"pad_fixed={fixed.padding_stats()['padding_frac_nodes']:.3f}")
    assert slots_fixed >= 1.3 * slots_packed


@pytest.mark.slow
def test_packing_sweep_budget_and_seeds():
    """Heavy sweep (slow lane): waste target holds across pool skews,
    batch sizes, and seeds; invariants hold throughout."""
    for lo, hi in ((8, 80), (4, 120), (30, 40)):
        for bs in (16, 32, 64):
            for seed in (0, 1):
                sam = skewed_samples(num=256, lo=lo, hi=hi, seed=seed)
                ld = GraphDataLoader(sam, batch_size=bs, shuffle=True,
                                     seed=seed, packing=True)
                for epoch in range(3):
                    ld.set_epoch(epoch)
                    flat = _flat(ld._selections())
                    assert sorted(flat) == list(range(len(sam)))
                    st = ld.padding_stats()
                    # steady-state waste target plus the final partial
                    # bin's share (a short epoch of B bins can leave up
                    # to ~1/B of its slots in the tail bin)
                    bound = 0.15 + 1.0 / max(len(ld), 1)
                    assert st["padding_frac_nodes"] <= bound, (
                        lo, hi, bs, seed, st)
