"""CPU tests of the benchmark's pieces at a tiny size.

The command itself refuses a CPU; these tests call its pieces directly
(`run.run(..., require_tpu=False)`) on a copy of the benchmark whose
configurations are cut to a hundredth of their hosts and a 1 s segment."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices, so that a four-chip cell runs sharded here too
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

import pytest  # noqa: E402

TINY_HORIZON = "1 s"


def make_tiny_root(dst: str) -> str:
    """A checkout copy holding BENCHMARK.json and benchmark/ (no tests),
    each configuration cut to a hundredth of its hosts and each cell to
    TINY_HORIZON."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub, edit in (("configs", _hundredth), ("workloads", _short)):
        d = os.path.join(dst, "benchmark", sub)
        for f in os.listdir(d):
            path = os.path.join(d, f)
            with open(path) as fh:
                data = json.load(fh)
            edit(data)
            with open(path, "w") as fh:
                json.dump(data, fh)
    return dst


def _hundredth(conf):
    for h in conf["shadow"]["hosts"].values():
        h["count"] = max(1, h["count"] // 100)


def _short(traffic):
    traffic["horizon"] = TINY_HORIZON


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))
