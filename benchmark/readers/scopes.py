"""Per-layer metrics read from the op names of the device trace: which
`jax.named_scope` (or flax module) each executed operation was traced under.

The program names what is not a module with a fixed vocabulary of scopes
(PERF.md section 3, `VOCABULARY` below); flax names each module's ops by the
module's name (`conv_0`, `pre_i`, `rbf_proj`, `lin1` ...). XLA keeps the name
stack of every HLO instruction as its `op_name`, and the TPU profiler writes
it into the trace as the stat `tf_op` of the event's METADATA:

    jit(step_body)/transpose(jvp(ef_forces))/jvp(PNAPlusStack)/
        PNAPlusStack.encode/conv_3/edge_gather/gather:

``trace/reduce.read_planes`` reads events through ``jax.profiler.ProfileData``,
which drops metadata stats, so this module reads the ``.xplane.pb`` itself:
a minimal decoder of the protobuf wire format for the five messages it needs
(XSpace, XPlane, XLine, XEvent, XEventMetadata / XStat; field numbers from
tsl/profiler/protobuf/xplane.proto), nothing but the standard library. It
loads the newest trace under ``.bench_trace/`` (the run's own), once.

`share(r, scopes, requires)` is the one reader: percent of device-busy time
inside the window, summed over the chips, in operations whose op name has a
path component matching one of `scopes` (shell patterns: ``conv_*``). The
wrappers JAX puts round a scope (``jit()``, ``jvp()``, ``transpose()``,
``vmap()``, ...) are stripped, so forward and backward of a scope count
together. Time is the UNION of the matching operations' intervals over the
union of all operations' intervals, chip by chip: a share cannot pass 100%.

It returns None, and the harness leaves the metric out, when there is no
device plane (CPU rehearsal), no trace, or no operation under `requires`: a
scope only this vocabulary has (`optimizer` in a train step, `ef_forces` in
a serving forward). That is how the PARENT's program reads: its flax module
names are there (`conv_0`), the vocabulary is not, and a share of module
names alone must not be read as if it were. The same guard covers an
executable fetched from a compile cache that another tree filled: JAX's cache
key strips debug info, so a program with scopes and one without share a key,
and the fetched executable carries the op names of whoever compiled it.

By hand, after a traced run:

    python3 -m benchmark.readers.scopes .bench_trace/<cell> [--out file.json]

prints device time by vocabulary scope, by conv, by module under the convs,
by HLO category (the metadata carries `hlo_category`, `flops` and
`bytes_accessed` too) and the ten longest operations with their scopes.
"""
from __future__ import annotations

import fnmatch
import functools
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..trace import reduce as tr

VOCABULARY = ("conv_*", "neighbor_gather", "edge_gather", "aggregate",
              "geometry", "heads", "loss", "ef_forces", "optimizer",
              "grad_allreduce")
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")
_WRAPPER = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")

# one executed operation: (start_ns, end_ns, components, name, category)
Op = Tuple[float, float, Tuple[str, ...], str, str]


# ------------------------------------------------------ protobuf, by hand --

def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf, at: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair for a length-delimited field; fixed-width fields
    (doubles) are stepped over."""
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, (at, at + size)
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (0, 0)
    for number, got in _fields(buf, *span):
        if number == 1:
            key = got
        elif number == 2:
            value = got
    return key, value


def _plane_ops(buf, span) -> Tuple[str, List[Op]]:
    """(plane name, the operations of its `XLA Ops` line)."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for number, got in _fields(buf, *span):
        if number == 2:
            name = _text(buf, got)
        elif number == 3:
            lines.append(got)
        elif number == 4:
            event_meta.append(got)
        elif number == 5:
            stat_meta.append(got)
    if not tr.DEVICE_PLANE.match(name):
        return name, []
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for number, got in _fields(buf, *value):
            if number == 2:
                stat_names[key] = _text(buf, got)
    meta: Dict[int, Tuple[str, Tuple[str, ...], str]] = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        op, path, category = "", "", ""
        for number, got in _fields(buf, *value):
            if number == 2:
                op = _text(buf, got)
            elif number == 5:            # XStat of the metadata
                stat, string = None, None
                for n2, g2 in _fields(buf, *got):
                    if n2 == 1:
                        stat = stat_names.get(g2)
                    elif n2 == 5:
                        string = g2
                    elif n2 == 7:        # a reference to a stat name
                        string = stat_names.get(g2, "")
                if string is None or stat not in ("tf_op", "hlo_category"):
                    continue
                if not isinstance(string, str):
                    string = _text(buf, string)
                if stat == "tf_op":
                    path = string
                else:
                    category = string
        meta[key] = (op, components(path), category)
    ops: List[Op] = []
    for line in lines:
        line_name, origin_ns, events = "", 0, []
        for number, got in _fields(buf, *line):
            if number == 2:
                line_name = _text(buf, got)
            elif number == 3:
                origin_ns = got
            elif number == 4:
                events.append(got)
        if line_name != tr.OPS_LINE:
            continue
        for event in events:
            which = offset_ps = duration_ps = 0
            for number, got in _fields(buf, *event):
                if number == 1:
                    which = got
                elif number == 2:
                    offset_ps = got
                elif number == 3:
                    duration_ps = got
            op, path, category = meta.get(which, ("", (), ""))
            start = origin_ns + offset_ps * 1e-3
            ops.append((start, start + duration_ps * 1e-3, path, op,
                        category))
    return name, ops


def read_device_ops(path: str) -> List[List[Op]]:
    """The operations of every TPU plane of an ``.xplane.pb``, chip by
    chip in the order of the chips' numbers; [] without a device plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    found = []
    for number, got in _fields(buf, 0, len(buf)):
        if number == 1:
            name, ops = _plane_ops(buf, got)
            if ops:
                found.append((int(tr.DEVICE_PLANE.match(name).group(1)),
                              ops))
    return [ops for _, ops in sorted(found, key=lambda t: t[0])]


def components(op_name: str) -> Tuple[str, ...]:
    """`jit(f)/transpose(jvp(ef_forces))/conv_3/edge_gather/gather:` ->
    ('f', 'ef_forces', 'conv_3', 'edge_gather', 'gather'): the path of an
    op name with the wrappers stripped and the op type after the colon
    dropped."""
    out = []
    for part in op_name.split(":", 1)[0].split("/"):
        while True:
            inner = _WRAPPER.match(part)
            if inner is None:
                break
            part = inner.group(1)
        if part:
            out.append(part)
    return tuple(out)


# ------------------------------------------------------------- the reader --

def newest_trace() -> Optional[str]:
    found = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _ops_of(path: str, mtime: float) -> List[List[Op]]:
    """The device operations of a trace, parsed once (the run's own is the
    newest under ``.bench_trace/``; a later trace has another name)."""
    return read_device_ops(path)


@functools.lru_cache(maxsize=16)
def _seconds_under(path: str, mtime: float, window: Tuple[float, float],
                   pattern: Optional[str]) -> float:
    """Device-busy seconds of a trace inside `window` (`pattern` None), or
    those under `pattern`: the two merges every metric of a line shares
    (busy, and its `requires`), done once a trace and window."""
    keep = ((lambda op: True) if pattern is None
            else (lambda op: matches(op[2], [pattern])))
    return seconds(_ops_of(path, mtime), window, keep)


def matches(path: Sequence[str], patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatchcase(part, pattern)
               for part in path for pattern in patterns)


def seconds(chips: Sequence[Sequence[Op]], window, keep) -> float:
    """Seconds, summed over `chips`, of the union of the intervals of the
    operations `keep(op)` accepts, inside `window` (ns)."""
    total = 0.0
    for ops in chips:
        total += tr.length(tr.merge(tr.clip(
            ((op[0], op[1]) for op in ops if keep(op)), window)))
    return total * 1e-9


def share(r, scopes: Sequence[str], requires: Optional[str] = None,
          within: Optional[str] = None, unscoped: bool = False
          ) -> Optional[float]:
    """Percent of device-busy time in operations under one of `scopes`
    (module docstring). `within`: only operations that ALSO lie under this
    pattern (the conv's inner scopes are read `within` "conv_*", so they
    cannot sum to more than the conv's share). `unscoped`: the complement,
    operations under NONE of `scopes`, those without an op name among
    them."""
    if r.reduced is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    trace = (path, os.path.getmtime(path))
    chips = _ops_of(*trace)
    if not chips:
        return None
    window = tuple(r.reduced.window_ns)
    if requires is not None and _seconds_under(
            *trace, window, requires) == 0.0:
        return None
    busy = _seconds_under(*trace, window, None)
    if busy <= 0.0:
        return None

    def keep(op):
        if within is not None and not matches(op[2], [within]):
            return False
        return matches(op[2], scopes) != unscoped
    return 100.0 * seconds(chips, window, keep) / busy


# ---------------------------------------------------------------- by hand --

def table(chips: Sequence[Sequence[Op]], window) -> Dict:
    """Device seconds (mean over the chips, inside `window`) by vocabulary
    scope, by conv, by module under a conv, by HLO category, and the
    longest operations with where they were traced."""
    n = float(len(chips))
    busy = seconds(chips, window, lambda op: True) / n

    def grouped(label) -> List[List]:
        totals: Dict[str, float] = {}
        for ops in chips:
            for op in ops:
                a, b = max(op[0], window[0]), min(op[1], window[1])
                if b > a:
                    for key in label(op):
                        totals[key] = totals.get(key, 0.0) + (b - a) * 1e-9
        return [[k, v / n] for k, v in sorted(totals.items(),
                                              key=lambda kv: -kv[1])]

    def vocabulary(op):
        return [p for p in VOCABULARY if matches(op[2], [p])] or ["(none)"]

    def conv(op):
        return [c for c in op[2] if fnmatch.fnmatchcase(c, "conv_*")][:1]

    def under_conv(op):
        at = next((i for i, c in enumerate(op[2])
                   if fnmatch.fnmatchcase(c, "conv_*")), None)
        # the component after the conv: a module or an inner scope; the
        # last component is the primitive
        return [] if at is None else [
            op[2][at + 1] if at + 2 < len(op[2]) else "(the conv itself)"]

    longest: Dict[str, List] = {}
    for ops in chips:
        for op in ops:
            a, b = max(op[0], window[0]), min(op[1], window[1])
            if b > a:
                row = longest.setdefault(tr.short_name(op[3]), [
                    0.0, "/".join(op[2]), op[4]])
                row[0] += (b - a) * 1e-9 / n
    top = sorted(longest.items(), key=lambda kv: -kv[1][0])[:10]
    return {"chips": len(chips), "busy_s": busy,
            "by_vocabulary": grouped(vocabulary), "by_conv": grouped(conv),
            "under_conv": grouped(under_conv),
            "by_category": grouped(lambda op: [op[4] or "(none)"]),
            "longest_ops": [[name, *row] for name, row in top]}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace_dir", help=".bench_trace/<cell>")
    parser.add_argument("--out", help="write the table here as JSON")
    args = parser.parse_args(argv)
    path = tr.find_xplane(args.trace_dir)
    if path is None:
        sys.exit(f"no .xplane.pb under {args.trace_dir}")
    chips = read_device_ops(path)
    if not chips:
        sys.exit(f"{path} holds no TPU plane")
    window = tr.traced_window(tr.load_xplane(path))
    result = dict(table(chips, window), path=path)
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
