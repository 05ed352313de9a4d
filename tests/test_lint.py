"""hydralint (tools/hydralint) — the contract-enforcing static analysis
suite (docs/static_analysis.md): clean-tree gate, per-rule fixtures,
suppression grammar, baseline mode, CLI contract."""
import json
import os
import subprocess
import sys

import pytest

from tools.hydralint import engine as lint_engine
from tools.hydralint.rules import ALL_RULES
from tools.hydralint.rules import asserts as r_asserts
from tools.hydralint.rules import determinism as r_det
from tools.hydralint.rules import locks as r_locks
from tools.hydralint.rules import loose_env as r_loose
from tools.hydralint.rules import traced_env as r_traced

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPECTED_RULES = {"traced-env-read", "loose-env-read", "assert-in-library",
                  "nondeterministic-order", "lock-discipline"}


# ------------------------------------------------------------- the CI gate --

def test_repo_is_lint_clean():
    """THE gate: seeding a violation into any covered module fails here.
    Deliberate exceptions carry reasoned inline suppressions instead."""
    findings = lint_engine.run_lint(REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_at_least_five_active_rules():
    names = {cls().name for cls in ALL_RULES}
    assert EXPECTED_RULES <= names
    assert len(names) >= 5


def test_cli_clean_exit_and_json():
    r = subprocess.run([sys.executable, "-m", "tools.hydralint", "--json"],
                       capture_output=True, text=True, timeout=120,
                       cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["findings"] == []
    assert set(doc["rules"]) == EXPECTED_RULES


def test_cli_list_rules():
    r = subprocess.run([sys.executable, "-m", "tools.hydralint",
                        "--list-rules"], capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    assert r.returncode == 0
    assert set(r.stdout.split()) == EXPECTED_RULES


# ------------------------------------------------- per-rule fixture checks --

def test_traced_env_rule_scope():
    rule = r_traced.TracedEnvReadRule()
    assert rule.applies("hydragnn_tpu/ops/segment.py")
    assert rule.applies("hydragnn_tpu/telemetry/registry.py")
    assert rule.applies("hydragnn_tpu/train/precision.py")
    assert rule.applies("hydragnn_tpu/md/farm.py")  # PR 11 farm scan body
    assert rule.applies("hydragnn_tpu/md/active.py")  # scored dispatch:
    # the uncertainty head runs inside the farm's traced scan body
    # PR 14: the HPO supervision layer resolves its knobs via
    # envflags.resolve_hpo_supervisor; process.py is the documented
    # child-env-construction exclusion
    assert rule.applies("hydragnn_tpu/hpo/supervisor.py")
    assert rule.applies("hydragnn_tpu/hpo/runner.py")
    assert not rule.applies("hydragnn_tpu/hpo/process.py")
    assert not rule.applies("hydragnn_tpu/parallel/mesh.py")  # documented
    assert not rule.applies("hydragnn_tpu/train/trainer.py")  # host-side


def test_loose_env_rule_fixtures():
    src = ("import os\n"
           "def f():\n"
           "    return os.getenv('HYDRAGNN_X')\n")
    hits = r_loose.find_env_reads(src, "f.py")
    assert [(h[1], h[2]) for h in hits] == [(3, "os.getenv")]
    rule = r_loose.LooseEnvReadRule()
    # covers host-side drivers the traced rule exempts ...
    assert rule.applies("hydragnn_tpu/train/trainer.py")
    assert rule.applies("hydragnn_tpu/run_training.py")
    # ... but not the documented bootstrap allowlist or envflags itself
    for allowed in r_loose.ALLOWLIST:
        assert not rule.applies(allowed)
    assert "hydragnn_tpu/utils/envflags.py" in r_loose.ALLOWLIST


def test_loose_env_scoped_allowlist_is_function_surgical():
    """PR 14: hpo's former whole-file allowlist entry shrank to the
    child-env-construction function(s) — a raw read anywhere ELSE in a
    scoped file is a finding again."""
    rule = r_loose.LooseEnvReadRule()
    # scoped files still APPLY (unlike full-allowlist entries)
    for rel in r_loose.SCOPED_ALLOWLIST:
        assert rule.applies(rel)
        assert rel not in r_loose.ALLOWLIST
    assert "hydragnn_tpu/utils/hpo.py" in r_loose.SCOPED_ALLOWLIST
    assert "hydragnn_tpu/hpo/process.py" in r_loose.SCOPED_ALLOWLIST

    import ast as _ast
    src = ("import os\n"
           "def _launch(spec):\n"
           "    return dict(os.environ)\n"   # allowed: named function
           "def resolve_thing():\n"
           "    return os.getenv('HYDRAGNN_X')\n")  # still a finding
    tree = _ast.parse(src)
    findings = rule.check(tree, src, "hydragnn_tpu/utils/hpo.py")
    assert [f.line for f in findings] == [5]
    # the same read outside any scoped file is fully covered
    findings_all = rule.check(tree, src, "hydragnn_tpu/hpo/ledger.py")
    assert [f.line for f in findings_all] == [3, 5]


def test_assert_rule_fixtures():
    hits = r_asserts.find_asserts(
        "def f(x):\n"
        "    assert x > 0, 'nope'\n"
        "    y = 'assert in a string is fine'\n"
        "    # assert in a comment is fine\n"
        "    return x\n", "f.py")
    assert [h[1] for h in hits] == [2]
    assert r_asserts.find_asserts("def f():\n    return 1\n", "f.py") == []
    assert r_asserts.AssertInLibraryRule().applies(
        "hydragnn_tpu/models/layers.py")


def test_determinism_rule_positive_fixtures():
    src = ("import glob\n"
           "import os\n"
           "def f(xs, p):\n"
           "    for x in set(xs):\n"
           "        pass\n"
           "    for x in {1, 2, 3}:\n"
           "        pass\n"
           "    ys = [y for y in frozenset(xs)]\n"
           "    zs = list(set(xs))\n"
           "    for n in os.listdir(p):\n"
           "        pass\n"
           "    fs = glob.glob(p)\n")
    hits = r_det.find_unsorted_iteration(src, "f.py")
    assert [h[1] for h in hits] == [4, 6, 8, 9, 10, 12]


def test_determinism_rule_covers_pathlib_spellings():
    src = ("from pathlib import Path\n"
           "def f(d):\n"
           "    for p in Path(d).glob('*.pkl'):\n"
           "        pass\n"
           "    xs = [q for q in Path(d).rglob('*')]\n"
           "    ok = sorted(Path(d).glob('*.pkl'))\n"
           "    ok2 = sorted(Path(d).iterdir())\n")
    hits = r_det.find_unsorted_iteration(src, "f.py")
    assert [h[1] for h in hits] == [3, 5]


def test_determinism_rule_negative_fixtures():
    src = ("import glob\n"
           "import os\n"
           "def f(xs, p, d):\n"
           "    for x in sorted(set(xs)):\n"
           "        pass\n"
           "    fs = sorted(glob.glob(p))\n"
           "    names = sorted(n for n in os.listdir(p))\n"
           "    ok = 3 in {1, 2, 3}\n"       # membership, not iteration
           "    for k in d:\n"               # dict order is insertion order
           "        pass\n"
           "    s = set(xs)\n")              # building a set is fine
    assert r_det.find_unsorted_iteration(src, "f.py") == []


def test_determinism_and_lock_rule_scope_covers_hpo():
    """PR 14: the trial supervisor promises deterministic ledgers and
    fault-site indexing (nondeterministic-order scope) and its state
    machine is cross-thread mutable (lock-discipline scope)."""
    det = r_det.NondeterministicOrderRule()
    assert det.applies("hydragnn_tpu/hpo/supervisor.py")
    assert det.applies("hydragnn_tpu/hpo/pbt.py")
    assert det.applies("hydragnn_tpu/hpo/process.py")
    assert "hydragnn_tpu/hpo/" in r_det.SCOPE_DIRS
    assert "hydragnn_tpu/hpo/supervisor.py" in r_locks.SCOPE_FILES


def test_determinism_rule_scope_covers_md_farm():
    """The trajectory farm's bitwise contract (docs/serving.md "MD
    farm") makes its packing/swap bookkeeping ordering-sensitive — the
    nondeterministic-order rule must cover hydragnn_tpu/md/."""
    rule = r_det.NondeterministicOrderRule()
    assert rule.applies("hydragnn_tpu/md/farm.py")
    assert rule.applies("hydragnn_tpu/md/integrator.py")
    # active.py: the deterministic harvest contract (twin-run bitwise
    # pool equality) makes its ensemble/pool ordering load-bearing
    assert rule.applies("hydragnn_tpu/md/active.py")
    assert "hydragnn_tpu/md/" in r_det.SCOPE_DIRS


LOCK_FIXTURE_HEADER = (
    "import threading\n"
    "import time\n"
    "class Engine:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.count = 0  # guarded-by: _lock\n"
    "        self._queue = object()\n")


def test_lock_rule_flags_unguarded_access():
    src = LOCK_FIXTURE_HEADER + (
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
        "    def bad(self):\n"
        "        return self.count\n")
    hits = r_locks.find_lock_violations(src, "f.py")
    assert len(hits) == 1 and hits[0][1] == 12
    assert "guarded-by _lock" in hits[0][2]


def test_lock_rule_honors_init_and_holds_lock():
    src = LOCK_FIXTURE_HEADER + (
        "    # holds-lock: _lock\n"
        "    def _bump(self):\n"
        "        self.count += 1\n"
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            self._bump()\n")
    assert r_locks.find_lock_violations(src, "f.py") == []


def test_lock_rule_flags_blocking_calls_under_lock():
    src = LOCK_FIXTURE_HEADER + (
        "    def bad(self, fut):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n"
        "            self._queue.get(timeout=1)\n"
        "            self._queue.put(1)\n"
        "            fut.result()\n")
    hits = r_locks.find_lock_violations(src, "f.py")
    assert [h[1] for h in hits] == [10, 11, 12, 13]


def test_lock_rule_nonblocking_queue_forms_pass():
    src = LOCK_FIXTURE_HEADER + (
        "    def ok(self, d, k, os, sep):\n"
        "        with self._lock:\n"
        "            self._queue.get_nowait()\n"
        "            self._queue.get(False)\n"
        "            self._queue.put(1, block=False)\n"
        "            d.get(k)\n"                     # dict.get, not a queue
        "            x = ', '.join(['a'])\n"         # str.join
        "            y = sep.join(['a'])\n"          # str.join via variable
        "            z = os.path.join('a', 'b')\n")  # os.path.join
    assert r_locks.find_lock_violations(src, "f.py") == []


def test_lock_rule_flags_thread_join_under_lock():
    src = LOCK_FIXTURE_HEADER + (
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            self._dispatcher.join()\n")
    hits = r_locks.find_lock_violations(src, "f.py")
    assert len(hits) == 1 and "thread wait" in hits[0][2]


def test_lock_rule_engaged_on_real_tree():
    """The audited concurrent subsystems actually declare guarded
    state — the rule must never become vacuously green."""
    rule = r_locks.LockDisciplineRule()
    for rel in r_locks.SCOPE_FILES:
        assert rule.applies(rel)
        with open(os.path.join(REPO, rel)) as f:
            assert "# guarded-by: _lock" in f.read(), rel


# ------------------------------------------------------ suppression grammar --

def _seed(tmp_path, rel, source):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return str(path)


def test_seeded_violation_fails_lint(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/graphs/bad.py",
          "def f(xs):\n"
          "    for x in set(xs):\n"
          "        pass\n")
    findings = lint_engine.run_lint(str(tmp_path))
    assert [f.rule for f in findings] == ["nondeterministic-order"]
    assert findings[0].file == "hydragnn_tpu/graphs/bad.py"
    assert findings[0].line == 2


def test_seeded_traced_env_read_hits_both_env_rules(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/models/bad.py",
          "import os\n"
          "X = os.getenv('HYDRAGNN_X')\n")
    findings = lint_engine.run_lint(str(tmp_path))
    assert {f.rule for f in findings} == {"traced-env-read",
                                          "loose-env-read"}


def test_reasoned_suppression_is_honored(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/graphs/bad.py",
          "def f(xs):\n"
          "    for x in set(xs):  "
          "# hydralint: disable=nondeterministic-order -- fixture: order "
          "irrelevant here\n"
          "        pass\n")
    assert lint_engine.run_lint(str(tmp_path)) == []


def test_bare_suppression_is_itself_a_violation(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/graphs/bad.py",
          "def f(xs):\n"
          "    for x in set(xs):  "
          "# hydralint: disable=nondeterministic-order\n"
          "        pass\n")
    findings = lint_engine.run_lint(str(tmp_path))
    # the bare disable suppresses NOTHING and is reported itself
    assert {f.rule for f in findings} == {lint_engine.BAD_SUPPRESSION,
                                          "nondeterministic-order"}


def test_suppression_only_silences_named_rules(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/models/bad.py",
          "import os\n"
          "X = os.getenv('X')  "
          "# hydralint: disable=loose-env-read -- fixture: wrong rule\n")
    findings = lint_engine.run_lint(str(tmp_path))
    assert [f.rule for f in findings] == ["traced-env-read"]


# --------------------------------------------------------------- baseline --

def test_baseline_records_debt_and_catches_new_findings(tmp_path):
    bad = ("def f(xs):\n"
           "    for x in set(xs):\n"
           "        pass\n")
    _seed(tmp_path, "hydragnn_tpu/graphs/bad.py", bad)
    base = str(tmp_path / "baseline.json")
    findings = lint_engine.run_lint(str(tmp_path))
    assert lint_engine.write_baseline(findings, base) == 1
    # recorded debt no longer fails ...
    again = lint_engine.run_lint(str(tmp_path))
    assert lint_engine.new_findings(
        again, lint_engine.load_baseline(base)) == []
    # ... but any NEW finding (here: a second instance of the same
    # (file, rule, message) key — the multiset contract) still does
    _seed(tmp_path, "hydragnn_tpu/graphs/bad.py",
          bad + "def g(xs):\n"
                "    for x in set(xs):\n"
                "        pass\n")
    now = lint_engine.run_lint(str(tmp_path))
    new = lint_engine.new_findings(now, lint_engine.load_baseline(base))
    assert [f.line for f in new] == [5]


def test_baseline_cli_roundtrip(tmp_path):
    _seed(tmp_path, "hydragnn_tpu/preprocess/bad.py",
          "import glob\n"
          "def f(p):\n"
          "    return glob.glob(p)\n")
    base = str(tmp_path / "baseline.json")
    args = [sys.executable, "-m", "tools.hydralint", str(tmp_path)]
    kw = dict(capture_output=True, text=True, timeout=120, cwd=REPO)
    assert subprocess.run(args, **kw).returncode == 1  # debt blocks ...
    r = subprocess.run(args + ["--write-baseline", base], **kw)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(args + ["--baseline", base], **kw)  # ... recorded
    assert r.returncode == 0, r.stdout + r.stderr
    _seed(tmp_path, "hydragnn_tpu/preprocess/bad.py",
          "import os\n"
          "def f(p):\n"
          "    return os.listdir(p)\n")
    r = subprocess.run(args + ["--baseline", base], **kw)
    assert r.returncode == 1
    assert "os.listdir" in r.stdout


def test_wrong_root_is_an_error_not_a_pass(tmp_path):
    """An empty walk must never greenwash the gate (exit 2, not 0)."""
    r = subprocess.run([sys.executable, "-m", "tools.hydralint",
                        str(tmp_path)], capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    assert r.returncode == 2
    assert "no Python files" in r.stderr


def test_unknown_rule_selection_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        lint_engine.run_lint(REPO, rule_names=["no-such-rule"])
