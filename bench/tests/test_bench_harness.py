"""The harness finds cells' parts by name, and refuses to run off a TPU
(CPU only)."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import Benchmark  # noqa: E402


def _copy_benchmark(dest):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    _copy_benchmark(tmp_path)
    before = _digests(tmp_path)
    b = os.path.join(tmp_path, "bench")
    # a new deployment, a new mix and a new per-layer metric: files ...
    cfg = json.load(open(os.path.join(b, "configs", "serve256.json")))
    cfg["server"]["capacity"] = 512
    json.dump(cfg, open(os.path.join(b, "configs", "serve512.json"), "w"))
    shutil.copy(os.path.join(b, "configs", "serve256_reference.py"),
                os.path.join(b, "configs", "serve512_reference.py"))
    mix = json.load(open(os.path.join(b, "traffic", "think.json")))
    mix["jobs"] = 512
    json.dump(mix, open(os.path.join(b, "traffic", "burst.json"), "w"))
    with open(os.path.join(b, "metrics", "queue_depth.py"), "w") as f:
        f.write("def read(obs, metric):\n    return 7.0\n")
    # ... and entries in BENCHMARK.json
    spec = json.load(open(os.path.join(tmp_path, "BENCHMARK.json")))
    spec["configs"].append({"name": "serve512", "source": "x",
                            "file": "bench/configs/serve512.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "serve512.burst", "config": "serve512",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("serve512.burst")
    spec["per_layer"].append({"name": "queue_depth.serve_burst", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "serve loop", "moves": "decision_p99_ms",
                              "workloads": ["serve512.burst"]})
    json.dump(spec, open(os.path.join(tmp_path, "BENCHMARK.json"), "w"))

    bench = Benchmark(str(tmp_path))
    cell = bench.cell("serve512.burst")
    assert bench.config(cell["config"])["server"]["capacity"] == 512
    assert bench.traffic(cell["traffic"])["jobs"] == 512
    assert hasattr(bench.kind(bench.config("serve512")["kind"]), "run")
    assert hasattr(bench.reference("serve512"), "decide")
    assert [m["name"] for m in bench.end_to_end("serve512.burst")] == [
        "decision_p99_ms", "setup_s"]
    layer = [m["name"] for m in bench.per_layer("serve512.burst")]
    assert layer == ["queue_depth.serve_burst"]
    assert bench.metric_reader(layer[0]).read({}, {}) == 7.0
    # a suffixed name falls back to the reader of its stem
    assert hasattr(bench.metric_reader("device_idle_share.serve_sat"), "read")
    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items()), \
        "an existing file of the benchmark was edited"


def test_every_named_part_of_the_benchmark_exists():
    bench = Benchmark(ROOT)
    for cell in bench.spec["workloads"]:
        cfg = bench.config(cell["config"])
        assert hasattr(bench.kind(cfg["kind"]), "run")
        bench.reference(cell["config"])
        bench.traffic(cell["traffic"])
        assert bench.end_to_end(cell["name"])
        assert bench.per_layer(cell["name"])
    for m in bench.spec["per_layer"]:
        assert hasattr(bench.metric_reader(m["name"]), "read")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve256.sat",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_run_without_a_tpu(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "src"), os.path.join(tmp_path, "src"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    _copy_benchmark(tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
