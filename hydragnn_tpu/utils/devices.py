"""Process-level device plumbing: where the persistent XLA compilation
cache lives, the CPU cross-process collectives switch, and the
persistent AOT compile store the serving fleet warms from.

Nothing here chooses a platform. A process takes the backend JAX gives
it (`JAX_PLATFORMS` is honoured); entry points that report device
numbers check `jax.default_backend()` themselves and fail when it is
not the one they need. One process owns a chip at a time, so nothing
here starts a child that touches JAX.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
from typing import Optional, Sequence

# <checkout>/.jax_cache, resolved from the package's own location so that
# every process of a run (trainer, HPO trial, elastic rank, bench child)
# shares one directory whatever its cwd — the path is part of the cache
# key's lookup, so a directory that moves never hits. Listed in .gitignore.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_cpu_gloo_collectives() -> None:
    """Select gloo as the CPU backend's cross-process collectives
    implementation (docs/fault_tolerance.md "Elastic multi-process
    training"). XLA CPU refuses multiprocess computations outright
    unless a collectives layer is chosen, and the knob has no effect
    once the backend client exists — so multi-rank CPU jobs (the
    elastic chaos runs, the 2-process CI pass) must call this BEFORE
    any device op, after jax.distributed.initialize's config is known."""
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def enable_compile_cache() -> Optional[str]:
    """Place the persistent XLA compilation cache; returns the directory
    in use, or None when there is no cache. THE one rule, shared by
    run_training, run_prediction, bench.py, chip_smoke.py and the
    HPO/elastic child runners:

    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — nothing
      is set in code.
    * unset and the backend is ``tpu``: ``<checkout>/.jax_cache``.
    * unset on any other backend: no cache (XLA's CPU AOT loader warns
      about machine-feature mismatches — potential SIGILL — when
      reloading CPU entries).

    Reads ``jax.default_backend()``, so call it AFTER
    ``jax.distributed.initialize`` in a multi-process run. A cache
    directory that cannot be created raises."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    if jax.default_backend() != "tpu":
        return None
    os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


class CompileStore:
    """Persistent AOT executable store: serialized compiled programs on
    disk, keyed by a caller-supplied fingerprint (docs/serving.md
    "Fleet").

    The jax in-process compile cache dies with the process and the
    XLA compilation cache (``enable_compile_cache``) still pays tracing
    plus a cache probe per program; this store pickles the COMPILED
    executable (``jax.experimental.serialize_executable``) so a
    replacement serving replica can load its whole bucket ladder from
    disk in seconds — ``InferenceEngine.warmup()`` on a warm store
    reports 0 fresh compiles (BENCH_SERVE_FLEET adjudicates it).

    Contract: same machine class, same backend, same jax version — the
    serialized artifact embeds compiled code, exactly like XLA's own CPU
    AOT cache entries. ``fingerprint()`` folds the jax version and the
    live backend platform into every key, and any load failure (corrupt
    file, foreign artifact, incompatible runtime) degrades to a miss —
    the caller compiles fresh and overwrites. Writes are atomic
    (tmp + ``os.replace``); a lost rename race means a peer replica won,
    which is fine because keyed contents are identical by construction.
    Thread-safe; one store may back every replica in a process."""

    SUFFIX = ".jaxexec"

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.saves = 0  # guarded-by: _lock
        self.errors = 0  # guarded-by: _lock

    @staticmethod
    def fingerprint(*parts, precision=None) -> str:
        """Stable key from repr()s of the parts + jax version + backend
        platform (an artifact compiled for another runtime must never be
        a hit).

        `precision` is the LABELED precision-mode field: the engine
        passes its (compute_dtype, quantization-scale digest) pair here
        so an int8 and an fp32 executable for the same (mcfg, bucket,
        schema) can never collide on a warm restart — and two int8
        programs baked from different calibration scales cannot either
        (the scales are trace-time constants inside the artifact). The
        field is folded for every key, including the default None, so
        precision-less and precision-labeled keys share one keyspace
        with no ambiguity."""
        import jax
        h = hashlib.sha256()
        h.update(f"jax={jax.__version__}".encode())
        h.update(f";backend={jax.devices()[0].platform}".encode())
        h.update(f";precision={precision!r}".encode())
        for p in parts:
            h.update(b";")
            h.update(repr(p).encode())
        return h.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + self.SUFFIX)

    def load(self, key: str, execution_devices: Sequence):
        """The deserialized executable for `key`, loaded onto
        `execution_devices` (the devices the caller's program was
        compiled for — left to its default, ``deserialize_and_load``
        spreads a one-device artifact over EVERY device of the backend
        and the first call fails with a shard-count mismatch), or None
        on a miss — including ANY failure to read/deserialize (corrupt
        entry, runtime mismatch, an artifact built for other devices):
        the store must degrade to a fresh compile, never take a warmup
        down."""
        path = self._path(key)
        if not os.path.exists(path):
            with self._lock:
                self.misses += 1
            return None
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            loaded = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(execution_devices))
        except Exception as exc:  # noqa: BLE001 — degrade to a miss
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "compile store entry %s is unloadable (%s: %s); "
                "compiling fresh", path, type(exc).__name__, exc)
            with self._lock:
                self.errors += 1
                self.misses += 1
            return None
        with self._lock:
            self.hits += 1
        return loaded

    def save(self, key: str, compiled) -> bool:
        """Serialize `compiled` under `key`; atomic, best-effort (a full
        or read-only disk warns and returns False — the run already has
        its executable in memory)."""
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(compiled)
            tmp = self._path(key) + f".tmp-{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump((payload, in_tree, out_tree), f)
            os.replace(tmp, self._path(key))
        except Exception as exc:  # noqa: BLE001 — best-effort persistence
            import logging
            logging.getLogger("hydragnn_tpu").warning(
                "compile store save for %s failed (%s: %s); continuing "
                "without persisting", key[:12], type(exc).__name__, exc)
            with self._lock:
                self.errors += 1
            return False
        with self._lock:
            self.saves += 1
        return True

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "saves": self.saves, "errors": self.errors,
                    "root": self.root}
