"""Preprocessing fast path (docs/preprocessing.md): content-addressed
preprocessed cache (shard roundtrip, invalidation on config/data/code
change, corruption detection + rebuild) and process-parallel sample builds
(bitwise determinism across worker counts, failure naming the file)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hydragnn_tpu.datasets import AbstractRawDataset, RawSample
from hydragnn_tpu.graphs.batch import GraphSample
from hydragnn_tpu.preprocess import cache as pcache
from hydragnn_tpu.preprocess.workers import PreprocessError, parallel_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_npz_dir(tmp_path, n_files=8, seed=0):
    rng = np.random.RandomState(seed)
    rawdir = tmp_path / "raw"
    rawdir.mkdir(exist_ok=True)
    for i in range(n_files):
        n = 5 + int(rng.randint(0, 4))
        np.savez(rawdir / f"s{i:03d}.npz", pos=rng.rand(n, 3) * 2,
                 feat=rng.rand(n, 1) * 10 + 5,
                 y=[float(rng.rand())])
    return rawdir


def _npz_config(rawdir, cache_dir="", workers=0, radius=1.5):
    return {
        "Dataset": {
            "path": {"total": str(rawdir)},
            "normalize_features": True,
            "node_features": {"dim": [1], "column_index": [0]},
            "graph_features": {"dim": [1], "column_index": [0]},
            "preprocessed_cache_dir": str(cache_dir),
        },
        "NeuralNetwork": {
            "Architecture": {"radius": radius, "max_neighbours": 10,
                             "edge_features": True},
            "Variables_of_interest": {"input_node_features": [0],
                                      "type": ["graph"],
                                      "output_index": [0]},
            "Training": {"preprocess_workers": workers},
        },
    }


class NpzDataset(AbstractRawDataset):
    """Module-level (picklable) raw dataset for the worker-pool tests."""

    def transform_input_to_data_object_base(self, filepath):
        if not filepath.endswith(".npz"):
            return None
        d = np.load(filepath)
        return RawSample(node_features=d["feat"].astype(np.float32),
                         pos=d["pos"].astype(np.float32),
                         graph_features=np.asarray(d["y"], np.float32))


class FailingDataset(NpzDataset):
    """Raises while parsing one specific file — the error must name it."""

    def transform_input_to_data_object_base(self, filepath):
        if filepath.endswith("s003.npz"):
            raise RuntimeError("synthetic parse failure")
        return super().transform_input_to_data_object_base(filepath)


def _assert_samples_equal(a, b):
    assert len(a) == len(b)
    for sa, sb in zip(a, b):
        for f in ("x", "pos", "senders", "receivers", "edge_attr",
                  "edge_shifts", "y_graph", "y_node", "cell", "energy",
                  "forces"):
            va, vb = getattr(sa, f), getattr(sb, f)
            assert (va is None) == (vb is None), f
            if va is not None:
                np.testing.assert_array_equal(np.asarray(va),
                                              np.asarray(vb), err_msg=f)


class TestShardRoundtrip:
    def test_bitwise_roundtrip_with_optional_fields(self, tmp_path):
        rng = np.random.RandomState(0)
        samples = [
            GraphSample(x=rng.rand(4, 2), pos=rng.rand(4, 3),
                        senders=[0, 1], receivers=[1, 0],
                        edge_attr=rng.rand(2, 1),
                        y_graph=rng.rand(3), cell=np.eye(3),
                        energy=1.5, forces=rng.rand(4, 3)),
            # no optional fields, empty edge set
            GraphSample(x=rng.rand(1, 2), pos=rng.rand(1, 3),
                        senders=np.zeros(0, np.int32),
                        receivers=np.zeros(0, np.int32)),
        ]
        meta = {"minmax": np.asarray([[0.0], [2.5]], np.float32),
                "note": "hello"}
        pcache.save_shard(str(tmp_path), "k1", samples, meta)
        loaded, lmeta = pcache.load_shard(str(tmp_path), "k1")
        _assert_samples_equal(samples, loaded)
        np.testing.assert_array_equal(lmeta["minmax"], meta["minmax"])
        assert lmeta["minmax"].dtype == np.float32
        assert lmeta["note"] == "hello"

    def test_wrong_key_and_schema_rejected(self, tmp_path):
        s = [GraphSample(x=np.zeros((2, 1)), pos=np.zeros((2, 3)),
                         senders=[0], receivers=[1])]
        path = pcache.save_shard(str(tmp_path), "k1", s)
        with pytest.raises(FileNotFoundError):
            pcache.load_shard(str(tmp_path), "other")
        # a shard renamed onto another key must not be served
        os.rename(path, pcache._shard_dir(str(tmp_path), "other"))
        with pytest.raises(pcache.CacheInvalid, match="built for key"):
            pcache.load_shard(str(tmp_path), "other")

    def test_corruption_detected(self, tmp_path):
        rng = np.random.RandomState(1)
        s = [GraphSample(x=rng.rand(6, 2), pos=rng.rand(6, 3),
                         senders=[0, 1], receivers=[1, 0])]
        path = pcache.save_shard(str(tmp_path), "k1", s)
        data = os.path.join(path, "data.bin")
        with open(data, "r+b") as f:
            f.seek(4)
            b = f.read(1)
            f.seek(4)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(pcache.CacheInvalid, match="checksum"):
            pcache.load_shard(str(tmp_path), "k1")
        # truncation is caught by the size check even with verify off
        with open(data, "r+b") as f:
            f.truncate(8)
        with pytest.raises(pcache.CacheInvalid, match="bytes"):
            pcache.load_shard(str(tmp_path), "k1", verify=False)


class TestCacheInvalidation:
    def test_hit_then_invalidation_on_config_data_code(self, tmp_path,
                                                       monkeypatch):
        rawdir = _write_npz_dir(tmp_path)
        cache_dir = tmp_path / "cache"
        cfg = _npz_config(rawdir, cache_dir)
        ds_cold = NpzDataset(cfg)
        assert ds_cold.cache_stats == {"enabled": 1, "hits": 0,
                                       "misses": 1, "invalid": 0}
        ds_warm = NpzDataset(cfg)
        assert ds_warm.cache_stats["hits"] == 1
        _assert_samples_equal(list(ds_cold), list(ds_warm))
        # minmax metadata restored from the shard on a warm hit
        np.testing.assert_array_equal(ds_cold.minmax_node_feature,
                                      ds_warm.minmax_node_feature)
        np.testing.assert_array_equal(ds_cold.minmax_graph_feature,
                                      ds_warm.minmax_graph_feature)

        # config change -> new key -> rebuild
        cfg2 = _npz_config(rawdir, cache_dir, radius=2.0)
        assert NpzDataset(cfg2).cache_stats["misses"] == 1
        # data change (touch one raw file) -> rebuild
        os.utime(rawdir / "s000.npz")
        assert NpzDataset(cfg).cache_stats["misses"] == 1
        # code change -> rebuild
        monkeypatch.setattr(pcache, "code_fingerprint", lambda: "v2")
        assert NpzDataset(cfg).cache_stats["misses"] == 1

    def test_corrupted_shard_rebuilt_not_served(self, tmp_path):
        rawdir = _write_npz_dir(tmp_path)
        cache_dir = tmp_path / "cache"
        cfg = _npz_config(rawdir, cache_dir)
        ds_cold = NpzDataset(cfg)
        shard = [d for d in os.listdir(cache_dir)
                 if d.startswith("preproc-")][0]
        with open(cache_dir / shard / "data.bin", "r+b") as f:
            f.seek(10)
            b = f.read(1)
            f.seek(10)
            f.write(bytes([b[0] ^ 0xFF]))
        ds = NpzDataset(cfg)
        assert ds.cache_stats["invalid"] == 1
        assert ds.cache_stats["misses"] == 1
        _assert_samples_equal(list(ds_cold), list(ds))  # rebuilt, not served
        # and the rebuilt shard serves cleanly again
        assert NpzDataset(cfg).cache_stats["hits"] == 1

    def test_warm_hit_skips_building_entirely(self, tmp_path, monkeypatch):
        rawdir = _write_npz_dir(tmp_path)
        cfg = _npz_config(rawdir, tmp_path / "cache")
        ds_cold = NpzDataset(cfg)

        def boom(*a, **k):
            raise AssertionError("build ran on a warm hit")

        import hydragnn_tpu.preprocess.transforms as transforms
        monkeypatch.setattr(transforms, "build_graph_sample", boom)
        monkeypatch.setattr(NpzDataset,
                            "transform_input_to_data_object_base", boom)
        ds_warm = NpzDataset(cfg)
        assert ds_warm.cache_stats["hits"] == 1
        _assert_samples_equal(list(ds_cold), list(ds_warm))


class TestParallelBuilds:
    def test_bitwise_identical_across_worker_counts(self, tmp_path):
        rawdir = _write_npz_dir(tmp_path)
        ref = NpzDataset(_npz_config(rawdir, workers=0))
        for workers in (1, 4):
            ds = NpzDataset(_npz_config(rawdir, workers=workers))
            _assert_samples_equal(list(ref), list(ds))
            np.testing.assert_array_equal(ref.minmax_node_feature,
                                          ds.minmax_node_feature)
            np.testing.assert_array_equal(ref.minmax_graph_feature,
                                          ds.minmax_graph_feature)

    def test_xyz_loader_parallel_matches_serial(self, tmp_path):
        from hydragnn_tpu.datasets.xyzdataset import XYZDataset
        rng = np.random.RandomState(4)
        rawdir = tmp_path / "xyz"
        rawdir.mkdir()
        for i in range(6):
            n = 6 + int(rng.randint(0, 3))
            p = rng.rand(n, 3) * 3
            with open(rawdir / f"s{i}.xyz", "w") as f:
                f.write(f"{n}\nc\n")
                for j in range(n):
                    f.write(f"6 {p[j, 0]} {p[j, 1]} {p[j, 2]}\n")
        cfg = _npz_config(rawdir)
        cfg["Dataset"] = {"format": "XYZ", "path": {"total": str(rawdir)},
                          "node_features": {"dim": [1], "column_index": [0]},
                          "preprocessed_cache_dir": ""}
        cfg["NeuralNetwork"]["Variables_of_interest"]["type"] = ["node"]
        serial = XYZDataset(cfg, str(rawdir))
        cfg["NeuralNetwork"]["Training"]["preprocess_workers"] = 4
        par = XYZDataset(cfg, str(rawdir))
        _assert_samples_equal(serial.samples, par.samples)

    def test_parallel_failure_names_file(self, tmp_path):
        rawdir = _write_npz_dir(tmp_path)
        with pytest.raises(PreprocessError, match="s003.npz"):
            FailingDataset(_npz_config(rawdir, workers=4))
        # serial fail-fast path names the file too, original chained
        with pytest.raises(PreprocessError,
                           match="s003.npz.*RuntimeError") as ei:
            FailingDataset(_npz_config(rawdir, workers=0))
        assert isinstance(ei.value.__cause__, RuntimeError)

    def test_parallel_map_error_names_label(self):
        def f(x):
            if x == 3:
                raise KeyError("boom")
            return x * 2

        with pytest.raises(PreprocessError, match="item-3.*KeyError"):
            parallel_map(f, list(range(6)), workers=4,
                         labels=[f"item-{i}" for i in range(6)])
        with pytest.raises(PreprocessError, match="item-3.*KeyError"):
            parallel_map(f, list(range(6)), workers=0,
                         labels=[f"item-{i}" for i in range(6)])

    def test_unpicklable_fn_falls_back_to_serial(self, caplog):
        import logging
        with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
            out = parallel_map(lambda x: x + 1, [1, 2, 3], workers=4)
        assert out == [2, 3, 4]
        assert any("not picklable" in r.message for r in caplog.records)


@pytest.mark.slow
def test_bench_preproc_smoke(tmp_path):
    """Slow-lane BENCH_PREPROC subprocess smoke (the nightly runs the
    full-size bench): the acceptance floors — >=5x neighbor construction
    vs the seed implementation on >=512-atom systems, >=10x warm-cache
    samples/s, parallel builds bitwise-equal — hold at smoke scale."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PREPROC="1",
               BENCH_PREPROC_ATOMS="1024", BENCH_PREPROC_FILES="48",
               BENCH_PREPROC_FILE_ATOMS="256",
               BENCH_PREPROC_OUT=str(tmp_path / "BENCH_PREPROC.json"))
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["neighbor_open"]["speedup_vs_seed"] >= 5.0, out
    assert out["neighbor_pbc"]["speedup_vs_seed"] >= 5.0, out
    assert out["cache"]["warm_speedup"] >= 10.0, out
    assert out["cache"]["cold"]["misses"] == 1
    assert out["cache"]["warm"]["hits"] == 1
    assert out["parallel"]["bitwise_equal"] is True
    assert os.path.exists(tmp_path / "BENCH_PREPROC.json")
