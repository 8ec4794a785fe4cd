"""A cell added by new files alone: in a copy of the benchmark
(``BENCHMARK.json``, ``bench/``, ``tests/bench/``), one workload on the
existing Qwen1.5-MoE configuration with a new traffic file and no limits
file is entered in ``BENCHMARK.json`` and rehearsed by the rehearsal test
as it stands, with no file under ``bench/`` or ``tests/bench/`` edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "qwen-l1-train"
TRAFFIC = {"kind": "train", "batch": 1, "seq": 2048, "pool": 8,
           "zipf_exponent": 1.0, "first_steps": 3, "ahead": 8,
           "rehearsal": {"batch": 2, "seq": 64}}


def _digests(root):
    out = {}
    for top in ("bench", os.path.join("tests", "bench")):
        for d, dirs, files in os.walk(os.path.join(root, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                path = os.path.join(d, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_a_cell_added_by_new_files_is_rehearsed(tmp_path):
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=ignore)
    shutil.copytree(os.path.join(ROOT, "tests", "bench"),
                    tmp_path / "tests" / "bench", ignore=ignore)
    before = _digests(tmp_path)

    traffic = tmp_path / "bench" / "traffic" / "train_1x2048.json"
    assert not traffic.exists()
    traffic.write_text(json.dumps(TRAFFIC, indent=2))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": WORKLOAD, "config": "qwen1.5-moe-a2.7b-l1",
        "traffic": "train_1x2048", "chips": 1,
        "why": "one Qwen1.5-MoE layer, 1x2048 Zipf tokens"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(WORKLOAD)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    assert not (tmp_path / "bench" / "limits" / f"{WORKLOAD}.json").exists()

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "tests/bench/test_bench_rehearsal.py",
         "-k", f"test_rehearsal_last_line and {WORKLOAD}"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "1 passed" in p.stdout, p.stdout[-3000:]

    after = _digests(tmp_path)
    added = sorted(set(after) - set(before))
    assert added == [os.path.join("bench", "traffic", "train_1x2048.json")]
    assert {k: after[k] for k in before} == before
