#!/bin/bash
# CI test shards — one definition shared by .github/workflows/ci.yml and
# local runs (`tools/ci_shards.sh <shard>`). Each shard targets <10 min on
# a CI-class CPU box with the 8-device virtual mesh (tests/conftest.py).
set -euo pipefail
cd "$(dirname "$0")/.."

shard="${1:?usage: ci_shards.sh core|data|train|parallel|robust|zoo|sweep}"

# fail-fast contract lint before any shard spends minutes on tests:
# hydralint is stdlib-only AST analysis (sub-second), so a traced env
# read / bare assert / lock-discipline violation stops CI here with a
# file:line instead of surfacing as a flaky behavioral failure later
# (docs/static_analysis.md)
python -m tools.hydralint

case "$shard" in
  core)
    # ops, model zoo construction, symmetry, neighbor
    # construction (vectorized radius/PBC oracle suite)
    python -m pytest -q tests/test_graph_core.py tests/test_models.py \
      tests/test_registries.py tests/test_irreps.py \
      tests/test_layout_parity.py \
      tests/test_equivariance.py tests/test_radius_fast.py
    ;;
  data)
    # datasets, configs, loaders, postprocess, acquisition tooling,
    # preprocessing cache + parallel builds (the PR 4 lesson: every new
    # test file must land in a shard or it never runs)
    python -m pytest -q tests/test_datasets.py tests/test_example_configs.py \
      tests/test_reference_configs.py tests/test_multidataset.py \
      tests/test_sampling.py tests/test_visualizer.py \
      tests/test_model_loadpred.py tests/test_dataset_tooling.py \
      tests/test_preprocess_cache.py
    ;;
  train)
    # end-to-end training paths: single-device + examples + HPO
    # (the former train shard ran 34 min vs the 25-min CI timeout; the
    # SPMD/mesh half now lives in the `parallel` shard)
    python -m pytest -q tests/test_training.py tests/test_examples.py \
      tests/test_hpo.py tests/test_pod_launch.py
    ;;
  parallel)
    # SPMD, composed mesh, pipeline (1f1b/gpipe schedule equivalence,
    # remat, pipe x data + ZeRO, knob resolution — docs/pipeline.md),
    # multi-process rendezvous. Slow lane deselected here: the pipeline
    # slow tests (BENCH_MFU subprocess smoke, 32-layer deep-stack train,
    # SchNet/EF config trains) run in the nightly mfu-bench job — left
    # in this per-push shard they blow its <10-min budget
    python -m pytest -q -m "not slow" tests/test_multiprocess.py \
      tests/test_composite.py tests/test_pipeline_config.py \
      tests/test_graph_parallel.py tests/test_pipeline.py
    ;;
  robust)
    # infrastructure robustness: input pipeline, packing, serving engine,
    # fault tolerance (kill/resume + serving failure semantics), the HPO
    # trial supervisor (in-process fault-site fakes), the hydralint
    # suite + env-read shim, telemetry (registry/spans//metrics
    # endpoint), reference shims — files that grew after the
    # original shard split and were previously in no shard
    python -m pytest -q tests/test_async_loader.py tests/test_packing.py \
      tests/test_serving.py tests/test_serving_faults.py \
      tests/test_serving_fleet.py \
      tests/test_faults.py tests/test_env_lint.py tests/test_lint.py \
      tests/test_ref_shims.py tests/test_telemetry.py tests/test_devices.py
    # the HPO supervisor suite runs its fast lane here; its slow lane is
    # a multi-minute subprocess chaos e2e (real child training
    # processes) covered by the nightly hpo-chaos job
    python -m pytest -q -m "not slow" tests/test_hpo_supervisor.py
    # same split for the elastic job supervisor: in-process fakes here;
    # the multi-rank subprocess chaos e2e runs in the nightly
    # elastic-chaos job
    python -m pytest -q -m "not slow" tests/test_elastic.py
    ;;
  zoo)
    # the 13-model accuracy battery (per-model thresholds)
    python -m pytest -q tests/test_graphs_full.py
    ;;
  sweep)
    # nightly: full variant sweep (multihead/lengths/vector/conv-head/
    # equivariant thresholds) + the energy-force accuracy harness
    python -m pytest -q -m sweep tests/test_graphs_sweep.py
    python accuracy.py --cpu
    ;;
  *)
    echo "unknown shard: $shard" >&2; exit 2
    ;;
esac
