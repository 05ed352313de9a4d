"""Device plumbing (PR 21): nothing hides the device.

* the ONE compile-cache placement rule (utils/devices.enable_compile_cache),
  driven with a faked backend name — never a chip;
* `resolve_num_shards` says so when the implicit all-devices default falls
  back to one device;
* `CompileWatch` counts process-wide compiles off jax's own events (what
  chip_smoke.py's "zero compiles after warm-up" rests on);
* `chip_smoke.py` and bench.py's rate-printing modes refuse to run off the
  TPU.
"""
import importlib.util
import json
import logging
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.utils import devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Snapshot/restore the jax config value the rule may set."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_rule_env_set_sets_nothing(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    # even on a TPU backend the code must leave jax's own setting alone
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert devices.enable_compile_cache() == str(tmp_path / "x")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "x").exists()  # nothing created either


def test_cache_rule_unset_on_cpu_is_off(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert jax.default_backend() == "cpu"
    assert devices.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_rule_tpu_default_is_the_checkout_whatever_the_cwd(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # HPO/elastic children run with their own cwd: the path must not move
    want = os.path.join(REPO, ".jax_cache")
    paths = []
    for cwd in (REPO, str(tmp_path)):
        monkeypatch.chdir(cwd)
        paths.append(devices.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == want
    assert paths == [want, want]
    assert os.path.isdir(want)


def test_cache_rule_bad_directory_fails_loudly(monkeypatch, cache_config,
                                               tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(devices, "_CHECKOUT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    with pytest.raises(OSError):
        devices.enable_compile_cache()


def test_resolve_num_shards_logs_implicit_fallback(caplog):
    from hydragnn_tpu.parallel.mesh import resolve_num_shards
    assert jax.device_count() == 8
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        # default (num_shards unset) on 8 devices, batch 12: 8 does not
        # divide it -> one device, and the log must say so
        assert resolve_num_shards(None, 12) == 1
    assert "8 devices seen, 1 shard used" in caplog.text
    assert "does not divide batch_size 12" in caplog.text
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="hydragnn_tpu"):
        assert resolve_num_shards(None, 16) == 8   # no fallback, no noise
        assert resolve_num_shards(1, 12) == 1      # explicit single device
    assert caplog.text == ""
    with pytest.warns(UserWarning, match="does not divide"):
        assert resolve_num_shards(8, 12) == 1      # explicit request warns


def test_compile_watch_counts_every_program():
    from hydragnn_tpu.utils.profiling import CompileWatch
    # inputs made up front: creating an array is itself a compiled program
    x, y = jnp.ones((3, 5)), jnp.ones((4, 5))
    with CompileWatch() as watch:
        f = jax.jit(lambda a: a * 2 + 1)
        f(x)
        first = watch.count
        assert first >= 1 and watch.seconds > 0
        f(x)                      # warm: the same program
        assert watch.count == first
        f(y)                      # a new shape compiles
        assert watch.count == first + 1
        # AOT programs (the serving engine's buckets) are seen too
        jax.jit(lambda a: a - 1).lower(x).compile()
        assert watch.count == first + 2
    after = watch.count
    jax.jit(lambda a: a * 3)(x)   # closed watch: no longer listening
    assert watch.count == after


def test_chip_smoke_refuses_to_run_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line of any kind


def test_bench_rate_modes_require_the_chip(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench._require_chip("cpu")   # CPU it merely fell back to
    assert exc.value.code != 0
    assert "not 'tpu'" in capsys.readouterr().err
    bench._require_chip("tpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench._require_chip("cpu")       # asked for by name: a contract check


@pytest.mark.slow
def test_chip_smoke_phases_run_at_tiny_size_on_the_cpu_mesh():
    """The smoke's phases — train, predict (loop vs engine), structure
    serving, sync, multi-device facts incl. the
    compile-store-warmed fleet — at a tiny size on 4 virtual CPU devices,
    so the script cannot rot between chip runs. Only `main()` holds the
    TPU gate; `run()` is the same code the chip executes."""
    code = (
        "import json, chip_smoke\n"
        "sz = dict(chip_smoke.FULL, atoms_per_dim=3, cutoff=2.0, n_train=32,"
        " n_val=8, n_test=8, batch_size=8, hidden_dim=16, num_conv_layers=2,"
        " num_epoch=2, structure_requests=2, timed_steps=3,"
        " min_force_corr=-1.0)\n"
        "rep = chip_smoke.run(sz, 4)\n"
        "print('TINY ' + json.dumps(rep, default=str))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    with tempfile.TemporaryDirectory() as cwd:   # ./logs lands here
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                           capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rep = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("TINY ")][-1][5:])
    assert rep["compile_cache_dir"] is None      # CPU: no cache
    assert rep["predict"]["engine_equals_loop_bitwise"] is True
    assert rep["train"]["jit_recompiles_per_epoch"][1:] == [0]
    assert rep["devices"]["fleet_warmup"][1]["fresh"] == 0
