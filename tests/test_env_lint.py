"""tools/check_traced_env_reads.py — structural guard against env reads
inside traced model/step/ops modules (the trace-time-read bug class)."""
import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint():
    path = os.path.join(REPO, "tools", "check_traced_env_reads.py")
    spec = importlib.util.spec_from_file_location("check_traced_env_reads",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_modules_have_no_direct_env_reads():
    lint = _lint()
    violations = lint.check(REPO)
    assert violations == [], (
        "direct os.environ/os.getenv reads in traced modules — resolve "
        f"via utils/envflags.py at construction time: {violations}")


def test_lint_detects_violations():
    lint = _lint()
    src = (
        "import os\n"
        "def f():\n"
        "    a = os.environ.get('HYDRAGNN_X')\n"
        "    b = os.environ['HYDRAGNN_Y']\n"
        "    c = os.getenv('HYDRAGNN_Z')\n"
    )
    hits = lint.find_env_reads(src, "fake.py")
    assert len(hits) == 3
    assert {h[1] for h in hits} == {3, 4, 5}


def test_lint_detects_from_import():
    lint = _lint()
    hits = lint.find_env_reads("from os import getenv, environ\n", "f.py")
    assert len(hits) == 2


def test_lint_ignores_comments_and_strings():
    lint = _lint()
    src = (
        "# the traced body must not read os.environ (see envflags)\n"
        "DOC = 'os.getenv is forbidden here'\n"
    )
    assert lint.find_env_reads(src, "f.py") == []


def test_lint_covers_the_known_offender_modules():
    """The two modules this bug class actually shipped in must be inside
    the linted surface."""
    lint = _lint()
    paths = [os.path.relpath(p, REPO) for p in lint.traced_module_paths(REPO)]
    assert os.path.join("hydragnn_tpu", "ops", "segment.py") in paths
    assert os.path.join("hydragnn_tpu", "models", "convs.py") in paths
    assert os.path.join("hydragnn_tpu", "models", "schnet.py") in paths
    assert os.path.join("hydragnn_tpu", "train", "train_step.py") in paths
    # the mixed-precision policy module resolves its knob at construction
    # (HYDRAGNN_PRECISION) — keep it linted
    assert os.path.join("hydragnn_tpu", "train", "precision.py") in paths
    # PR 7: the telemetry subsystem resolves every knob via
    # utils/envflags.resolve_telemetry — no direct env reads inside
    # telemetry/ (registry/spans/session/http/mfu all covered)
    for mod in ("registry.py", "spans.py", "session.py", "http.py",
                "mfu.py", "__init__.py"):
        assert os.path.join("hydragnn_tpu", "telemetry", mod) in paths
    # PR 8: the parallel step/forward factories are traced surface —
    # the pipeline schedule/remat knobs resolve via
    # utils/envflags.resolve_pipeline at construction time. mesh.py is
    # the ONE documented exclusion (host-side rendezvous/SLURM reads).
    for mod in ("pipeline.py", "pipeline_trainer.py", "spmd.py",
                "composite.py", "graph_parallel.py"):
        assert os.path.join("hydragnn_tpu", "parallel", mod) in paths
    assert os.path.join("hydragnn_tpu", "parallel", "mesh.py") not in paths


def test_lint_cli_exit_code():
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_traced_env_reads.py"), REPO],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok:" in r.stdout
