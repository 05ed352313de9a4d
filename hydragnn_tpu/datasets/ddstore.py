"""Python binding for the C++ DDStore equivalent + DistDataset wrapper.

reference: hydragnn/utils/datasets/distdataset.py:22-183 (DistDataset wraps
any dataset in DDStore: each rank holds a shard; `get(idx)` does a remote
fetch) and the pyddstore C++ library's add/get/epoch_begin/epoch_end API
(SURVEY.md §2.5).

The native library (native/ddstore.cpp) is compiled on first use with g++
(no pip deps). Peer discovery: the caller provides (host, port) per rank —
on a TPU pod these come from jax.distributed; the single-host test path
uses 127.0.0.1 ports.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..graphs.batch import GraphSample

_LIB: Optional[ctypes.CDLL] = None


def _build_lib() -> str:
    """Path of the compiled library for THIS source, built on first use.
    The file name carries a content hash of ddstore.cpp, so an existing
    file is by construction built from the current source — a checkout,
    a copy or an export cannot make a stale or missing library look
    fresh the way an mtime comparison does. (*.so is in .gitignore.)"""
    import hashlib
    d = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                     "native"))
    src = os.path.join(d, "ddstore.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(d, f"libddstore-{digest}.so")
    if not os.path.exists(so):
        tmp = f"{so[:-3]}.{os.getpid()}.tmp.so"  # still matches *.so
        subprocess.check_call(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, src,
             "-lpthread"])
        os.replace(tmp, so)  # atomic: a concurrent builder wrote the same
    return so


def _lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        lib = ctypes.CDLL(_build_lib())
        lib.dds_init.restype = ctypes.c_void_p
        lib.dds_init.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.dds_listen.restype = ctypes.c_int
        lib.dds_listen.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dds_connect.restype = ctypes.c_int
        lib.dds_connect.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                    ctypes.c_char_p, ctypes.c_int]
        lib.dds_add.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
        lib.dds_get.restype = ctypes.c_int64
        lib.dds_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int64, ctypes.c_int,
                                ctypes.c_char_p, ctypes.c_int64]
        lib.dds_epoch_begin.argtypes = [ctypes.c_void_p]
        lib.dds_epoch_end.argtypes = [ctypes.c_void_p]
        lib.dds_free.argtypes = [ctypes.c_void_p]
        _LIB = lib
    return _LIB


class DDStore:
    """Thin OO wrapper over the C ABI, mirroring pyddstore's API."""

    def __init__(self, rank: int = 0, world: int = 1):
        self.rank = rank
        self.world = world
        self._h = _lib().dds_init(rank, world)
        self._meta: Dict[str, Tuple[np.dtype, tuple, np.ndarray, np.ndarray]] = {}
        self.port: Optional[int] = None

    def listen(self, port: int = 0) -> int:
        self.port = int(_lib().dds_listen(self._h, port))
        return self.port

    def connect(self, peer: int, host: str, port: int):
        r = _lib().dds_connect(self._h, peer, host.encode(), port)
        if r != 0:
            raise ConnectionError(f"ddstore connect to rank {peer} "
                                  f"{host}:{port} failed")

    def add(self, name: str, arrays: Sequence[np.ndarray],
            global_base: int, global_total: int):
        """Register the local shard: a list of per-sample arrays sharing
        dtype and trailing shape."""
        a0 = np.ascontiguousarray(arrays[0])
        tail = a0.shape[1:]
        itemsize = int(np.prod(tail, dtype=np.int64)) * a0.dtype.itemsize
        counts = np.asarray([a.shape[0] for a in arrays], np.int64)
        blob = b"".join(np.ascontiguousarray(a).tobytes() for a in arrays)
        _lib().dds_add(self._h, name.encode(), blob, len(blob),
                       counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                       len(counts), itemsize, global_base, global_total)
        self._meta[name] = (a0.dtype, tail, counts, None)

    def get(self, name: str, index: int, owner: int,
            max_bytes: int = 1 << 22) -> np.ndarray:
        buf = ctypes.create_string_buffer(max_bytes)
        nb = _lib().dds_get(self._h, name.encode(), index, owner, buf,
                            max_bytes)
        if nb < 0:
            raise KeyError(f"ddstore get({name}, {index}) failed ({nb})")
        dtype, tail, _, _ = self._meta.get(
            name, (np.dtype(np.float32), (), None, None))
        arr = np.frombuffer(buf.raw[:nb], dtype=dtype)
        return arr.reshape((-1,) + tail) if tail else arr

    def epoch_begin(self):
        _lib().dds_epoch_begin(self._h)

    def epoch_end(self):
        _lib().dds_epoch_end(self._h)

    def free(self):
        if self._h:
            _lib().dds_free(self._h)
            self._h = None


_DD_FIELDS = ("x", "pos", "senders", "receivers", "y_graph", "y_node",
              "edge_attr", "edge_shifts", "energy", "forces", "cell")


class DistDataset:
    """Dataset facade over DDStore shards
    (reference: utils/datasets/distdataset.py:22-183).

    Each rank calls `populate(local_samples, global_base, global_total)`;
    `__getitem__(global_idx)` fetches from whichever rank owns the index
    (block distribution)."""

    def __init__(self, rank: int = 0, world: int = 1):
        self.dd = DDStore(rank, world)
        self.rank = rank
        self.world = world
        self.total = 0
        self._bounds: List[int] = []
        self._fields: List[str] = []

    def listen(self, port: int = 0) -> int:
        return self.dd.listen(port)

    def connect_peers(self, addrs: Sequence[Tuple[str, int]]):
        for peer, (host, port) in enumerate(addrs):
            if peer != self.rank:
                self.dd.connect(peer, host, port)

    def populate(self, samples: Sequence[GraphSample], global_base: int,
                 global_total: int, bounds: Sequence[int]):
        """`bounds`: global start index of each rank's shard + [total]."""
        self.total = global_total
        self._bounds = list(bounds)
        for f in _DD_FIELDS:
            if getattr(samples[0], f) is None:
                continue
            self._fields.append(f)
            arrs = [np.atleast_1d(getattr(s, f)) for s in samples]
            self.dd.add(f, arrs, global_base, global_total)

    def _owner(self, idx: int) -> int:
        for r in range(self.world):
            if self._bounds[r] <= idx < self._bounds[r + 1]:
                return r
        raise IndexError(idx)

    def __len__(self):
        return self.total

    def __getitem__(self, idx: int) -> GraphSample:
        owner = self._owner(idx)
        kw = {}
        for f in self._fields:
            val = self.dd.get(f, idx, owner)
            if f in ("senders", "receivers"):
                val = val.astype(np.int32)
            if f in ("y_graph", "energy"):
                val = val.reshape(-1)
            kw[f] = val
        return GraphSample(**kw)

    def epoch_begin(self):
        self.dd.epoch_begin()

    def epoch_end(self):
        self.dd.epoch_end()

    def free(self):
        self.dd.free()
