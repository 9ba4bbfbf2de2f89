"""The harness end to end on the CPU at a tiny size: it refuses to run
without a chip, runs a cell added by files alone (a member module among
them), and its check passes a sound run."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from perfbench import harness
from perfbench.tests.conftest import BENCH, ROOT, TINY_CONFIG, make_root

RUN = [sys.executable, "perfbench/run.py", "--seed", "2147483659",
       "--seconds", "1", "--trace", "0", "--workload"]


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **kw)
    env.pop("PYTHONPATH", None)
    return env


def test_refuses_without_a_chip():
    p = subprocess.run(RUN + ["mnist_sync_heavy"], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    make_root(str(tmp_path))
    p = subprocess.run(RUN + ["mnist_sync_heavy"], cwd=str(tmp_path),
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


DRIVE = """
import json, sys, time
t0 = time.perf_counter()
sys.path[:0] = [{root!r}, {src!r}]
from perfbench import harness
cell = harness.Cell({cell!r}, root={root!r})
print(json.dumps(harness.run_cell(cell, 2147483659, 1.0, False, t0,
                                  require_chip=False)))
"""


def _drive(root, cell, tmp_path):
    code = DRIVE.format(root=root, src=os.path.join(ROOT, "src"), cell=cell)
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       env=_env(JAX_COMPILATION_CACHE_DIR=str(
                           tmp_path / "cache")),
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            path = os.path.join(d, f)
            out[path] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a metric and BENCHMARK.json
    entries make a new cell runnable; no file of the benchmark changes."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    make_root(root)              # copies the benchmark, adds tiny_cnn/tiny
    before = _digests(root)
    with open(os.path.join(root, "perfbench", "metrics",
                           "rounds_per_s.py"), "w") as f:
        f.write("def read(run):\n    return run.rounds / run.window_s\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["end_to_end"].append({
        "name": "rounds_per_s", "unit": "rounds/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    result = _drive(root, "tiny", tmp_path)
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert result["metrics"]["rounds_per_s"]["value"] > 0
    assert result["metrics"]["samples_per_s"]["value"] > 0
    assert "peak_hbm_gb" not in result["metrics"]     # not a device here
    assert list(result)[-1] == "checks"


def test_sound_run_is_correct(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    make_root(root)
    result = _drive(root, "tiny", tmp_path)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_a_new_member_needs_only_new_files(tmp_path):
    """A member module that is not a CNN (a two-leaf linear model), its
    configuration and BENCHMARK.json entries make a cell that runs and
    reads correct; no file of the benchmark changes."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    make_root(root)
    before = _digests(root)
    shutil.copy(os.path.join(BENCH, "tests", "fixtures", "linear.py"),
                os.path.join(root, "perfbench", "members", "linear.py"))
    cfg = dict(TINY_CONFIG, name="tiny_linear", member="linear")
    del cfg["cnn_spec"]
    with open(os.path.join(root, "perfbench", "configs",
                           "tiny_linear.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(BENCH, "limits", "mnist_sync_heavy.json"),
                os.path.join(root, "perfbench", "limits",
                             "tiny_linear.json"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny_linear", "source": "test",
                             "file": "perfbench/configs/tiny_linear.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_linear",
                               "config": "tiny_linear", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    result = _drive(root, "tiny_linear", tmp_path)
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"loss_gap", "change_gap", "spread_gap",
                                     "ledger_gap"}


@pytest.mark.parametrize("member", ["no_such_member", "incomplete"])
def test_a_member_that_cannot_serve_is_refused_at_the_cell(
        tmp_path, monkeypatch, member):
    """An unknown member, or a module that lacks part of the hook, fails
    ``Cell(...)`` with the configuration and the member named."""
    monkeypatch.setitem(sys.modules, "perfbench.members.incomplete",
                        types.ModuleType("perfbench.members.incomplete"))
    root = make_root(str(tmp_path))
    path = os.path.join(root, "perfbench", "configs", "tiny_cnn.json")
    with open(path, "w") as f:
        json.dump(dict(TINY_CONFIG, member=member), f)
    with pytest.raises(ValueError, match=f"'tiny_cnn'.*'{member}'"):
        harness.Cell("tiny", root=root)


def test_per_leaf_reference_agrees_with_the_program(tmp_path):
    """On the tiny CNN cell the per-leaf reference's round losses lie
    within the cell's loss limit of the program's, and its sync ledger
    equals the program's exactly."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    make_root(root)
    limits = json.load(open(os.path.join(BENCH, "limits",
                                         "mnist_sync_heavy.json")))
    checks = _drive(root, "tiny", tmp_path)["checks"]
    assert checks["loss_gap"]["value"] <= limits["loss_gap"]["limit"]
    assert checks["ledger_gap"]["value"] == 0
