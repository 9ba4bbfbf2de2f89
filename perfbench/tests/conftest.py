"""Shared helpers for the benchmark's own tests (``python -m pytest
perfbench``): a tiny cell built from the real configuration and traffic
schema, small enough for the CPU."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny_cnn", "member": "cnn",
    "source": "a small copy of mnist_cnn for the CPU",
    "cnn_spec": [["conv", 4, 3, 1], ["conv", 8, 3, 1], ["pool", 2],
                 ["flatten"], ["dense", 16], ["dense", 10]],
    "input_shape": [14, 14, 1], "num_outputs": 10,
    "data": {"generator": "synthetic_mnist", "args": {"image_size": 14}},
}


def tiny_traffic(**kw):
    """sync_heavy cut to 8 learners and 4-round chunks: every round of
    the first chunk ends in a full sync, so the check covers the sync
    layer."""
    t = json.load(open(os.path.join(BENCH, "traffic", "sync_heavy.json")))
    t.update({"m": 8, "chunk": 4}, **kw)
    return t


def make_root(dest, cell="tiny", limits_of="mnist_sync_heavy", traffic=None,
              chips=1):
    """A checkout holding BENCHMARK.json and perfbench/, plus a tiny cell
    under the given name that carries ``limits_of``'s limits."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(BENCH, os.path.join(dest, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(dest, "perfbench", "configs",
                           "tiny_cnn.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(dest, "perfbench", "traffic",
                           "tiny.json"), "w") as f:
        json.dump(traffic or tiny_traffic(), f)
    src = os.path.join(BENCH, "limits", limits_of + ".json")
    if os.path.exists(src):
        shutil.copy(src, os.path.join(dest, "perfbench", "limits",
                                      cell + ".json"))
    bench = json.load(open(os.path.join(dest, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny_cnn", "source": "test",
                             "file": "perfbench/configs/tiny_cnn.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny_cnn",
                               "traffic": "tiny", "chips": chips,
                               "why": "test"})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return make_root(str(tmp_path))
