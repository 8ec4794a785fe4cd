"""``bench/run.py`` as the benchmark command: a CPU rehearsal of a train
and a serve cell prints a last line with exactly the contract's keys, and
the command refuses to measure where there is no TPU or no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _run(args, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("workload, trace, keys", [
    ("dsmoe-train", "0", KEYS),
    ("dsmoe-decode", "1", KEYS | {"breakdown"}),
])
def test_rehearsal_last_line(workload, trace, keys):
    p = _run(["--workload", workload, "--seed", str(2 ** 32 + 5),
              "--seconds", "0.3", "--trace", trace, "--cpu-rehearsal"])
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == keys
    assert list(last)[-1] == "checks"
    assert last["correct"] is False          # never a chip result
    assert last["metrics"] == {}             # no device metric from a CPU
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    for name, c in last["checks"].items():
        assert set(c) == {"value", "limit"}
        assert f"[limit] {name}=" in p.stderr


def test_refuses_without_tpu():
    p = _run(["--workload", "dsmoe-train", "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dsmoe-train", "--seed", "1", "--seconds", "1",
                        "--cpu-rehearsal"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
