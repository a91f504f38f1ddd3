"""The command's contract on a machine without a chip, and the schema of
the result line."""

import json
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
CMD = [sys.executable, "bench/run.py", "--workload", "train.lda-nytimes",
       "--seed", "0", "--seconds", "10", "--trace", "0"]


def test_no_chip_no_result(tmp_path):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    r = subprocess.run(CMD, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(CMD, cwd=tmp_path, capture_output=True, text=True,
                       timeout=300, env={"PATH": "/usr/bin:/bin",
                                         "HOME": str(tmp_path)})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_benchmark_json_is_within_the_contract():
    from bench import harness
    b = harness.benchmark()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    cells = [w["name"] for w in b["workloads"]]
    for w in b["workloads"]:
        assert harness.config(w["config"])["name"] == w["config"]
        tr = harness.traffic(w["traffic"])
        assert (ROOT / "bench" / f"{tr['driver']}.py").is_file()
        assert set(harness.limits(w["name"]))
        assert len(w["why"]) <= 200
        e2e = harness.cell_metrics(b, w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.cell_metrics(b, w["name"], "per_layer")
    for m in b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_result_line_schema(tiny_train):
    from bench.tests.conftest import run_small
    cfg, tr = tiny_train
    out = run_small("train.lda-nytimes", cfg, tr)
    line = json.loads(out["line"])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
