"""Preemptible-trial HPO supervision (docs/hpo.md, ISSUE 14).

Tier-1 lane: every trial fault site (trial-kill / trial-hang /
trial-spawn-fail), the retry budget, pruning, the heartbeat watchdog,
and ledger determinism — all via in-process fake TrialHandles so the
suite stays fast. The full subprocess chaos e2e (real child training
processes, kill/resume bitwise vs an uninterrupted twin) lives in the
``slow`` lane as the BENCH_HPO subprocess smoke.
"""
import json
import os
import threading
import time

import pytest

from hydragnn_tpu.hpo import (COMPLETED, FAILED, PRUNED, TERMINAL_STATES,
                              TrialHandle, TrialLedger, TrialSpec,
                              TrialSupervisor)
from hydragnn_tpu.utils.faults import install_fault_plan, parse_fault_plan


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    install_fault_plan(None)


class FakeHandle(TrialHandle):
    """Scripted trial: runs `polls_to_exit` polls then exits `rc`; with
    ``hang=True`` it never progresses and never exits on its own."""

    def __init__(self, polls_to_exit=3, rc=0, objective=1.0, hang=False,
                 ckpt_at=1):
        self.n = 0
        self.polls_to_exit = polls_to_exit
        self.rc = rc
        self.objective = objective
        self.hang = hang
        self.ckpt_at = ckpt_at
        self.killed = False

    def poll(self):
        if self.killed:
            return -9
        self.n += 1
        if self.hang or self.n <= self.polls_to_exit:
            return None
        return self.rc

    def kill(self):
        self.killed = True

    def progress(self):
        return ("wedged",) if self.hang else (self.n,)

    def checkpoint_step(self):
        return self.n if self.n >= self.ckpt_at else None

    def result(self):
        if self.rc == 0 and not self.killed and not self.hang:
            return {"objective": self.objective}
        return None


def _make_launcher(log, **handle_kw):
    def launch(spec, attempt, resume, hang):
        log.append((spec.trial_id, attempt, resume, hang))
        return FakeHandle(hang=hang,
                          objective=float(spec.params.get("lr", 0.0)),
                          **handle_kw)
    return launch


def _fast_supervisor(launch, trials, **kw):
    kw.setdefault("heartbeat_s", 0.15)
    kw.setdefault("backoff_s", 0.01)
    kw.setdefault("poll_interval_s", 0.01)
    return TrialSupervisor(launch, trials, **kw)


def test_all_trials_reach_terminal_and_objectives_recorded():
    log = []
    trials = [TrialSpec(i, {"lr": 0.1 * (i + 1)}, seed=i)
              for i in range(3)]
    sup = _fast_supervisor(_make_launcher(log), trials, concurrency=2)
    recs = sup.run(deadline_s=30)
    assert all(r.state == COMPLETED for r in recs.values())
    assert [recs[i].objective for i in range(3)] == \
        pytest.approx([0.1, 0.2, 0.3])
    assert all(r.attempts == 1 and r.resumes == 0 for r in recs.values())
    # terminal ledger events carry the outcome
    terminals = [e for e in sup.ledger.records()
                 if e["event"] == "terminal"]
    assert sorted(e["trial"] for e in terminals) == [0, 1, 2]


def test_trial_kill_site_drives_kill_and_resume():
    """trial-kill@1 SIGKILLs trial 1's first launch at its first
    committed checkpoint; the relaunch resumes and completes."""
    log = []
    install_fault_plan(parse_fault_plan("trial-kill@1"))
    trials = [TrialSpec(i, {"lr": 1.0}, seed=i) for i in range(2)]
    sup = _fast_supervisor(_make_launcher(log), trials, concurrency=1,
                           max_retries=2)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == COMPLETED and recs[0].resumes == 0
    assert recs[1].state == COMPLETED
    assert recs[1].resumes == 1 and recs[1].preemptions == 1
    # the relaunch carried resume=True
    assert (1, 1, True, False) in log
    killed = [e for e in sup.ledger.records() if e["event"] == "killed"]
    assert len(killed) == 1 and killed[0]["trial"] == 1
    assert killed[0]["data"]["reason"] == "injected-kill"


def test_trial_hang_site_watchdog_kills_and_resumes():
    """trial-hang@0: the launcher is told to produce a wedged trial; the
    heartbeat watchdog kills it and the retry completes."""
    log = []
    install_fault_plan(parse_fault_plan("trial-hang@0"))
    sup = _fast_supervisor(_make_launcher(log),
                           [TrialSpec(0, {"lr": 1.0})], max_retries=1)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == COMPLETED
    assert recs[0].preemptions == 1 and recs[0].resumes == 1
    assert log[0] == (0, 0, False, True)   # hang injected at launch
    assert log[1] == (0, 1, True, False)   # retry is clean
    hung = [e for e in sup.ledger.records() if e["event"] == "hung"]
    assert len(hung) == 1


def test_trial_spawn_fail_retries_without_resume():
    """trial-spawn-fail@0: no child ever existed, so the retry must NOT
    claim resume (there is nothing on disk to continue from)."""
    log = []
    install_fault_plan(parse_fault_plan("trial-spawn-fail@0"))
    sup = _fast_supervisor(_make_launcher(log),
                           [TrialSpec(0, {"lr": 1.0})], max_retries=1)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == COMPLETED
    assert recs[0].attempts == 2 and recs[0].resumes == 0
    assert log == [(0, 1, False, False)]  # only the retry reached launch
    spawn = [e for e in sup.ledger.records()
             if e["event"] == "spawn-failed"]
    assert len(spawn) == 1
    assert "trial-spawn-fail" in spawn[0]["data"]["error"]


def test_real_launcher_exception_counts_as_spawn_failure():
    calls = []

    def flaky_launch(spec, attempt, resume, hang):
        calls.append(attempt)
        if attempt == 0:
            raise OSError("scheduler rejected the job")
        return FakeHandle(objective=2.0)

    sup = _fast_supervisor(flaky_launch, [TrialSpec(0, {"lr": 1.0})],
                           max_retries=1)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == COMPLETED and recs[0].attempts == 2
    assert calls == [0, 1]


def test_retry_budget_exhaustion_is_terminal_failed():
    """A trial that crashes every launch must end FAILED, not loop."""
    log = []
    sup = _fast_supervisor(_make_launcher(log, rc=3),
                           [TrialSpec(0, {"lr": 1.0})], max_retries=2)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == FAILED
    assert recs[0].attempts == 3  # initial + 2 retries
    assert "retries exhausted" in recs[0].outcome_reason


def test_exit_zero_without_result_is_a_crash_not_success():
    log = []

    class NoResult(FakeHandle):
        def result(self):
            return None

    def launch(spec, attempt, resume, hang):
        log.append(attempt)
        return NoResult()

    sup = _fast_supervisor(launch, [TrialSpec(0, {"lr": 1.0})],
                           max_retries=1)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == FAILED
    assert "exit-0-without-result" in recs[0].outcome_reason


def test_prune_is_terminal_and_kills_running():
    handles = []

    def launch(spec, attempt, resume, hang):
        h = FakeHandle(hang=True)  # would run forever
        handles.append(h)
        return h

    sup = _fast_supervisor(launch, [TrialSpec(0, {"lr": 1.0})],
                           heartbeat_s=30.0)
    done = {}

    def _run():
        done.update(sup.run(deadline_s=30))

    t = threading.Thread(target=_run)
    t.start()
    deadline = time.time() + 5
    while not handles and time.time() < deadline:
        time.sleep(0.005)
    sup.prune(0)
    t.join(timeout=10)
    assert not t.is_alive()
    assert done[0].state == PRUNED
    assert handles[0].killed


def test_prune_before_launch_never_spawns_and_is_pruned():
    """prune() on a PENDING trial: no child is ever launched, no
    fault-site consultation is consumed, terminal state is PRUNED (not
    FAILED via a pointless retry loop) — code-review regression."""
    log = []
    trials = [TrialSpec(0, {"lr": 1.0}), TrialSpec(1, {"lr": 2.0})]
    sup = _fast_supervisor(_make_launcher(log), trials, concurrency=1)
    sup.prune(1)
    recs = sup.run(deadline_s=30)
    assert recs[0].state == COMPLETED
    assert recs[1].state == PRUNED and recs[1].attempts == 0
    assert [tid for tid, *_ in log] == [0]  # trial 1 never launched


def test_prune_during_backoff_wins_over_retry():
    """A prune that lands while the trial waits out its retry backoff
    must terminate it PRUNED — not relaunch, not exhaust into FAILED."""
    log = []
    sup = _fast_supervisor(_make_launcher(log, rc=3),
                           [TrialSpec(0, {"lr": 1.0})], max_retries=5,
                           backoff_s=0.5)  # long backoff window
    done = {}
    t = threading.Thread(target=lambda: done.update(sup.run(deadline_s=30)))
    t.start()
    deadline = time.time() + 5
    while not log and time.time() < deadline:
        time.sleep(0.005)
    sup.prune(0)  # lands while pending-in-backoff (or mid-crash)
    t.join(timeout=10)
    assert not t.is_alive()
    assert done[0].state == PRUNED
    assert done[0].attempts <= 2  # never ground through the retry budget


def test_shutdown_kills_running_trials_and_is_terminal():
    """External shutdown(): the handle is killed AND the trial lands in
    a terminal state (failed, reason shutdown) — a dead process must
    never read as 'running' forever (code-review regression)."""
    handles = []

    def launch(spec, attempt, resume, hang):
        h = FakeHandle(hang=True)
        handles.append(h)
        return h

    sup = _fast_supervisor(launch, [TrialSpec(0, {"lr": 1.0})],
                           heartbeat_s=30.0)
    t = threading.Thread(target=lambda: sup.run(deadline_s=30))
    t.start()
    deadline = time.time() + 5
    while not handles and time.time() < deadline:
        time.sleep(0.005)
    sup.shutdown()
    t.join(timeout=10)
    assert not t.is_alive()
    assert handles[0].killed
    recs = sup.snapshot()
    assert recs[0].state in TERMINAL_STATES
    assert recs[0].state == FAILED
    assert recs[0].outcome_reason == "shutdown"
    # duration froze at shutdown time
    d1 = sup.snapshot()[0].duration_s
    time.sleep(0.05)
    assert sup.snapshot()[0].duration_s == d1


def test_shutdown_before_run_launches_nothing():
    """A pre-closed supervisor must not spawn children or resurrect
    terminal trials (the shutdown-vs-launch race, code-review round 2):
    run() returns immediately with everything terminal exactly once."""
    log = []
    sup = _fast_supervisor(_make_launcher(log),
                           [TrialSpec(0, {"lr": 1.0})])
    sup.shutdown()
    recs = sup.run(deadline_s=5)
    assert log == []  # no launch ever happened
    assert recs[0].state == FAILED
    assert recs[0].outcome_reason == "shutdown"
    terminals = [e for e in sup.ledger.records()
                 if e["event"] == "terminal" and e["trial"] == 0]
    assert len(terminals) == 1  # exactly one terminal event, no dupes


def test_deadline_expiry_fails_stuck_trials():
    """A launcher whose handles never exit AND never stop progressing
    (so the watchdog can't call them hung) is bounded by run()'s
    deadline — the supervisor itself must always terminate."""

    class Immortal(FakeHandle):
        def poll(self):
            self.n += 1
            return -9 if self.killed else None

        def progress(self):
            return (self.n,)  # always "progressing"

    sup = _fast_supervisor(lambda *a: Immortal(),
                           [TrialSpec(0, {"lr": 1.0})])
    recs = sup.run(deadline_s=0.3)
    assert recs[0].state == FAILED
    assert recs[0].outcome_reason == "deadline"


def test_ledger_deterministic_across_identical_chaos_runs():
    """The PR 7 contract at trial granularity: two identical chaos runs
    produce identical ledgers modulo timing."""

    def run_once():
        install_fault_plan(parse_fault_plan(
            "trial-kill@1;trial-hang@2;trial-spawn-fail@3"))
        trials = [TrialSpec(i, {"lr": 0.1 * (i + 1)}, seed=i)
                  for i in range(4)]
        sup = _fast_supervisor(_make_launcher([]), trials,
                               concurrency=2, max_retries=2)
        sup.run(deadline_s=30)
        install_fault_plan(None)
        return sup.ledger.data_view()

    d1, d2 = run_once(), run_once()
    assert d1 == d2
    events = {e["event"] for e in d1}
    assert {"launched", "killed", "hung", "spawn-failed",
            "terminal"} <= events


def test_ledger_write_canonical_order(tmp_path):
    led = TrialLedger()
    led.event(1, "launched", data={"attempt": 0})
    led.event(0, "launched", data={"attempt": 0})
    led.event(1, "terminal", data={"state": "completed"},
              timing={"duration_s": 1.0})
    path = str(tmp_path / "ledger.jsonl")
    assert led.write(path) == 3
    recs = [json.loads(line) for line in open(path)]
    assert [(r["trial"], r["seq"]) for r in recs] == [(0, 0), (1, 0),
                                                      (1, 1)]
    # data_view strips timing only
    assert all("timing" not in r for r in led.data_view())


def test_fork_trial_registers_perturbed_spec():
    log = []
    space = {"lr": (0.001, 0.1), "width": [8, 16, 32]}
    trials = [TrialSpec(0, {"lr": 0.01, "width": 16}, seed=0)]
    sup = _fast_supervisor(_make_launcher(log), trials)
    spec = sup.fork_trial(0, 7, space, donor_val=0.5)
    assert spec.trial_id == 7 and spec.forked_from == 0
    assert spec.fork_val == 0.5
    assert 0.001 <= spec.params["lr"] <= 0.1
    assert spec.params["width"] in space["width"]
    # deterministic: forking again with the same ids reproduces params
    sup2 = _fast_supervisor(_make_launcher([]), trials)
    spec2 = sup2.fork_trial(0, 7, space)
    assert spec2.params == spec.params
    recs = sup.run(deadline_s=30)
    assert recs[7].state == COMPLETED  # forks run like any trial


def test_duplicate_trial_ids_rejected():
    with pytest.raises(ValueError, match="duplicate trial ids"):
        TrialSupervisor(lambda *a: FakeHandle(),
                        [TrialSpec(0, {}), TrialSpec(0, {})])


def test_supervisor_telemetry_counters():
    from hydragnn_tpu.telemetry.registry import get_registry
    reg = get_registry()
    before = reg.snapshot().get("hpo.trials_total", {"values": {}})
    before_done = dict(before["values"]) if "values" in before else {}
    install_fault_plan(parse_fault_plan("trial-kill@0"))
    sup = _fast_supervisor(_make_launcher([]), [TrialSpec(0, {"lr": 1.0})],
                           max_retries=1)
    sup.run(deadline_s=30)
    install_fault_plan(None)
    snap = reg.snapshot()
    key = (("outcome", "completed"),)
    assert snap["hpo.trials_total"]["values"][key] >= \
        before_done.get(key, 0) + 1
    assert "hpo.preemptions_total" in snap
    assert "hpo.resumes_total" in snap
    assert "hpo.trials_per_hour" in snap


# --------------------------------------------------- slow-lane chaos e2e

@pytest.mark.slow
def test_bench_hpo_chaos_smoke(tmp_path):
    """BENCH_HPO end-to-end in a subprocess (the nightly hpo-chaos):
    real child training processes under injected kill + hang chaos —
    every trial terminal, zero orphaned process groups, and the
    killed-then-resumed trial bitwise-equal to its uninterrupted twin."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = os.path.join(str(tmp_path), "BENCH_HPO.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_HPO="1",
               BENCH_HPO_TRIALS="3",
               BENCH_HPO_EPOCHS="3", BENCH_HPO_OUT=out_path)
    r = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert os.path.exists(out_path)
    assert out["value"] == 1.0, out
    assert out["all_terminal"] is True
    assert out["zero_orphans"] is True
    assert out["injected_kills_landed"] >= 1
    assert out["injected_hangs_detected"] >= 1
    assert out["trajectory_bitwise_equal"] is True
    assert out["completed"] == out["trials"]
