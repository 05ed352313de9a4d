"""Helpers of the benchmark's tests (no test lives here)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(workload, seed=1, seconds=1.5, trace=0, devices=1, root=None,
             cwd=REPO, extra_env=None, timeout=420):
    """One rehearsal run of the benchmark's command in a child process on
    the CPU; returns (returncode, stdout lines)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTEST_CURRENT_TEST")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(extra_env or {})
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    command = [sys.executable if command[0].startswith("python") else
               command[0]] + command[1:]
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if root is not None:
        args += ["--root", root]
    done = subprocess.run(command + args, cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)
    return done.returncode, done.stdout.strip().splitlines(), done.stderr


def xspace_text(planes):
    """An XSpace text proto from {plane: {line: [(name, start_ns, dur_ns)]}}
    — what `ProfileData.from_text_proto` reads."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur in events:
                mid = names.setdefault(name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(start * 1000)} duration_ps: "
                           f"{int(dur * 1000)} }}")
            body.append(f'lines {{ id: {lid} name: {json.dumps(line)} '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: '
                f'{json.dumps(n)} }} }}' for n, i in names.items()]
        out.append(f'planes {{ id: {pid} name: {json.dumps(plane)} '
                   f'{" ".join(body)} {" ".join(meta)} }}')
    return "\n".join(out)
