"""loose-env-read: every env read goes through utils/envflags helpers.

The strict-parsing rule ("a typo warns and keeps the default"),
generalized from the traced surface to the whole library: a raw ``os.environ``/``os.getenv`` read means ad-hoc
parsing, and ad-hoc parsing is how a typo value silently enables an
experimental path (`bool(int(env))` crashing on "true", any-non-empty
truthiness enabling a kernel). utils/envflags.py is the one place that
knows the strict grammar (env_strict_flag / env_strict_choice /
env_strict_int / env_str), warns on unrecognized values, and falls back
to the default instead of letting the typo take effect.

Scope: all of ``hydragnn_tpu/`` except envflags itself and a short,
reason-documented host-side allowlist — modules whose env access is
process-bootstrap plumbing (rendezvous addresses, SLURM probes, XLA_FLAGS
read-modify-write), not flag parsing. Files whose only legitimate raw
access is building a CHILD process environment carry a function-scoped
entry instead (``SCOPED_ALLOWLIST``): raw reads are exempt only inside
the named env-construction functions, and everything else in the file
stays covered.
"""
from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, Rule
from .traced_env import find_env_reads

# relpath -> why raw env access is legitimate there. Additions need the
# same kind of reason — "it was easier" is not one.
ALLOWLIST = {
    # the strict-parsing layer itself: the helpers this rule points at
    "hydragnn_tpu/utils/envflags.py":
        "the envflags helpers are the one sanctioned env-read site",
    # multi-host rendezvous (HYDRAGNN_MASTER_ADDR/PORT, SLURM_NPROCS/
    # PROCID) + walltime probes at process startup — addresses and
    # scheduler facts, not feature flags
    "hydragnn_tpu/parallel/mesh.py":
        "host-side rendezvous/SLURM bootstrap reads",
    # XLA_FLAGS read-modify-write + device env probes BEFORE jax
    # initializes — must happen at import/startup, and the writes are the
    # point
    "hydragnn_tpu/utils/devices.py":
        "XLA_FLAGS read-modify-write before jax init",
}

# relpath -> (reason, function names whose BODIES may read env raw) —
# the surgical form of the allowlist for files that are mostly ordinary
# flag-parsing territory with one legitimate env-construction site.
# Anything outside the named functions is still a finding (PR 14: the
# former whole-file hpo.py entry hid its SLURM reads, which belonged on
# envflags.env_str).
SCOPED_ALLOWLIST = {
    # `dict(os.environ, **env_over)` when building a child trial's
    # environment — constructing an env, not parsing flags
    "hydragnn_tpu/utils/hpo.py":
        ("child-process env construction in orchestrate", ("_launch",)),
    # same contract for the trial supervisor's subprocess launcher
    "hydragnn_tpu/hpo/process.py":
        ("child-trial env construction", ("_child_env",)),
    # and for the elastic rank launcher: rendezvous coordinates,
    # per-rank virtual device counts, fault-plan masking
    "hydragnn_tpu/elastic/process.py":
        ("child-rank env construction", ("_child_env",)),
}

MESSAGE = ("env read outside utils/envflags.py — parse via an envflags "
           "strict helper (env_str / env_strict_flag / env_strict_choice "
           "/ env_strict_int) so a typo value warns instead of taking "
           "effect")


def _allowed_ranges(tree: ast.AST, func_names) -> List[tuple]:
    """(lineno, end_lineno) spans of the named (possibly nested)
    functions — the lines a scoped allowlist entry exempts."""
    names = set(func_names)
    return [(node.lineno, node.end_lineno or node.lineno)
            for node in ast.walk(tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in names]


class LooseEnvReadRule(Rule):
    name = "loose-env-read"

    def applies(self, relpath: str) -> bool:
        return (relpath.startswith("hydragnn_tpu/")
                and relpath not in ALLOWLIST)

    def check(self, tree: ast.AST, source: str,
              relpath: str) -> List[Finding]:
        scoped = SCOPED_ALLOWLIST.get(relpath)
        ranges = (_allowed_ranges(tree, scoped[1]) if scoped else ())
        return [Finding(relpath, line, self.name, f"{what}: {MESSAGE}")
                for _, line, what in find_env_reads(source, relpath,
                                                    tree=tree)
                if not any(lo <= line <= hi for lo, hi in ranges)]
