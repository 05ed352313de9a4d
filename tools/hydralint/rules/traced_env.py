"""traced-env-read: no os.environ/os.getenv inside the traced surface.

An env read inside code that jax traces (model forward, loss/step bodies,
ops) is resolved once at trace time and frozen into the compiled program —
toggling the variable afterwards silently does nothing, and a
loosely-parsed value can flip an experimental path on from a typo. So the
rule is structural: env reads belong in utils/envflags.py helpers,
resolved at construction time and passed in as plain values.

Checked (AST, so comments/strings never trip it):
* any `os.environ` attribute use (covers .get, [], `in`),
* any `os.getenv(...)` call,
* `from os import environ` / `from os import getenv`.

This module carries the scope tables and the `find_env_reads` /
`traced_module_paths` / `check` unit API; tools/check_traced_env_reads.py
is a delegating shim over it so the historical entry point (and
tests/test_env_lint.py) keep working unchanged.
"""
from __future__ import annotations

import ast
import os
from typing import List, Tuple

from ..engine import Finding, Rule

# the traced surface: modules whose function bodies run under jax.jit /
# grad tracing. Host-side drivers (trainer, loaders, run_*) legitimately
# read env at startup and are NOT covered (the loose-env-read rule still
# requires them to parse via envflags helpers).
TRACED_DIRS = (
    os.path.join("hydragnn_tpu", "models"),
    os.path.join("hydragnn_tpu", "ops"),
    # the telemetry layer is host-side, but its knobs gate producer call
    # sites that run adjacent to (and inside wrappers around) traced
    # code — every telemetry knob must resolve through
    # utils/envflags.resolve_telemetry at construction time, never via a
    # direct env read inside the subsystem (PR 7; same rule that keeps
    # the precision module honest)
    os.path.join("hydragnn_tpu", "telemetry"),
    # the parallel step/forward factories (pipeline, spmd, composite,
    # graph_parallel) build traced bodies — the schedule/remat/shard
    # knobs resolve via utils/envflags.resolve_pipeline at construction
    # (PR 8); mesh.py is excluded below: its env reads are the multi-host
    # rendezvous + SLURM walltime probes, host-side startup code that
    # never runs under trace
    os.path.join("hydragnn_tpu", "parallel"),
    # the MD farm's scan body + batched re-filter are compiled programs
    # whose knobs (steps-per-dispatch, candidate headroom) must resolve
    # via serving/config.resolve_md_farm at construction — an env read
    # here would be trace-time-frozen (PR 11)
    os.path.join("hydragnn_tpu", "md"),
    # the HPO supervision layer is host-side, but its knobs (retry/
    # heartbeat/backoff/concurrency) must resolve through
    # utils/envflags.resolve_hpo_supervisor at construction, never via
    # direct reads inside the subsystem (PR 14; the telemetry rule).
    # process.py is excluded below: its one read constructs a child env.
    os.path.join("hydragnn_tpu", "hpo"),
    # the elastic job-supervision layer is host-side, but its knobs
    # (restarts/heartbeat/backoff, rendezvous timeout) must resolve
    # through utils/envflags.resolve_elastic /
    # resolve_rendezvous_timeout at construction, never via direct
    # reads inside the subsystem (the PR 14 rule, applied to the rank
    # supervisor). process.py is excluded below: child-rank env
    # construction.
    os.path.join("hydragnn_tpu", "elastic"),
    # the int8 PTQ layer builds TRACED programs (quant/ptq.py's
    # interceptor runs under the engine's jit) and trace-time constants
    # (activation scales): every knob — calibration-set size, serve
    # precision — resolves through serving/config.py at construction,
    # never via env reads that would silently fork compiled programs
    os.path.join("hydragnn_tpu", "quant"),
)

# host-side files inside an otherwise-traced directory; every entry must
# carry a reason above/next to it
EXCLUDED_FILES = (
    os.path.join("hydragnn_tpu", "parallel", "mesh.py"),  # rendezvous/
    # SLURM env parsing at process startup (init_distributed,
    # walltime_deadline) — never traced
    os.path.join("hydragnn_tpu", "hpo", "process.py"),  # child-trial
    # env construction (dict(os.environ, ...)) — loose-env-read still
    # covers the file via its function-scoped allowlist entry
    os.path.join("hydragnn_tpu", "elastic", "process.py"),  # child-rank
    # env construction (rendezvous coordinates, per-rank device counts)
    # — loose-env-read still covers the file via its function-scoped
    # allowlist entry
)
TRACED_FILES = (
    os.path.join("hydragnn_tpu", "train", "train_step.py"),
    os.path.join("hydragnn_tpu", "train", "loss.py"),
    # the mixed-precision policy module: resolve_precision is called by
    # step/engine factories whose results are baked into compiled
    # programs — an env read here would be the same trace-time-frozen
    # bug class, so it must go through utils/envflags
    os.path.join("hydragnn_tpu", "train", "precision.py"),
    # the sampled-training pipeline: its knobs (fanouts, staleness_k,
    # partitions) determine every compiled shape of the run and the
    # training mathematics — they resolve ONCE through
    # utils/envflags.resolve_sampling at loader construction; an env
    # read here would fork the one-compile contract from a typo
    # (docs/sampling.md)
    os.path.join("hydragnn_tpu", "preprocess", "sampling.py"),
    # the GFM step-factory layer: head combine weights and the mixture
    # spec are baked into the compiled program's config (task_weights
    # substitution) — they resolve ONCE through utils/envflags
    # .resolve_gfm at the call site; an env read here would fork the
    # one-compile mixture contract from a typo (docs/gfm.md)
    os.path.join("hydragnn_tpu", "train", "gfm.py"),
    # the continuous-learning loop (PR 19) is host-side, but its knobs
    # (shadow-window sizing, drift bound, autoscale watermarks) must
    # resolve through serving/config.resolve_publish /
    # resolve_autoscale at construction, never via direct env reads
    # inside the subsystem — the PR 7/14 rule, applied to the publisher
    # and autoscaler
    os.path.join("hydragnn_tpu", "serving", "publish.py"),
    os.path.join("hydragnn_tpu", "serving", "autoscale.py"),
)

MESSAGE = ("read inside a traced module — resolve it via utils/envflags.py "
           "at construction time")


def find_env_reads(source: str, filename: str = "<str>", tree=None
                   ) -> List[Tuple[str, int, str]]:
    """(file, lineno, what) for every direct env read in `source`.
    An already-parsed `tree` (the engine's single parse) skips the
    re-parse; the string-only form is the unit/shim API."""
    out: List[Tuple[str, int, str]] = []
    if tree is None:
        tree = ast.parse(source, filename=filename)
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "os"
                and node.attr in ("environ", "getenv")):
            out.append((filename, node.lineno, f"os.{node.attr}"))
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            for alias in node.names:
                if alias.name in ("environ", "getenv"):
                    out.append((filename, node.lineno,
                                f"from os import {alias.name}"))
    return out


def traced_module_paths(root: str) -> List[str]:
    paths: List[str] = []
    for d in TRACED_DIRS:
        full = os.path.join(root, d)
        for dirpath, _, names in os.walk(full):
            paths.extend(os.path.join(dirpath, n) for n in sorted(names)
                         if n.endswith(".py"))
    paths.extend(os.path.join(root, f) for f in TRACED_FILES)
    excluded = {os.path.join(root, f) for f in EXCLUDED_FILES}
    return [p for p in paths if os.path.exists(p) and p not in excluded]


def check(root: str) -> List[Tuple[str, int, str]]:
    violations: List[Tuple[str, int, str]] = []
    for path in traced_module_paths(root):
        with open(path) as f:
            rel = os.path.relpath(path, root)
            violations.extend(find_env_reads(f.read(), rel))
    return violations


# posix-normalized scope tables for the engine's relpaths
_TRACED_DIRS_P = tuple(d.replace(os.sep, "/") for d in TRACED_DIRS)
_EXCLUDED_P = frozenset(f.replace(os.sep, "/") for f in EXCLUDED_FILES)
_TRACED_FILES_P = frozenset(f.replace(os.sep, "/") for f in TRACED_FILES)


class TracedEnvReadRule(Rule):
    name = "traced-env-read"

    def applies(self, relpath: str) -> bool:
        if relpath in _TRACED_FILES_P:
            return True
        if relpath in _EXCLUDED_P:
            return False
        return any(relpath.startswith(d + "/") for d in _TRACED_DIRS_P)

    def check(self, tree: ast.AST, source: str,
              relpath: str) -> List[Finding]:
        return [Finding(relpath, line, self.name, f"{what} {MESSAGE}")
                for _, line, what in find_env_reads(source, relpath,
                                                    tree=tree)]
