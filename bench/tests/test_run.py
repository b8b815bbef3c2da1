"""The command itself: it refuses to run where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

from bench import common

RUN = [sys.executable, "bench/run.py", "--workload", "borg-cell.steady",
       "--seed", str(2**31 + 99), "--seconds", "1", "--trace", "0"]


def env():
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("PYTHONPATH", None)
    return e


def test_no_tpu_exits_2_with_no_result():
    p = subprocess.run(RUN, cwd=common.ROOT, env=env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(RUN, cwd=tmp_path, env=env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    bench = common.benchmark()
    assert bench["command"] == ["python3", "bench/run.py"]
    for c in bench["configs"]:
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (common.BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
    for w in bench["workloads"]:
        assert (common.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert (common.BENCH / "metrics" / f"{m['name']}.py").is_file()
