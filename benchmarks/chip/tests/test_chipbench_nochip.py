"""Without a TPU the benchmark exits non-zero and prints no result; in a
directory holding only BENCHMARK.json and the benchmark's own files (no
program) it does the same."""
import os
import shutil
import subprocess
import sys

import chipbench_tiny


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite-fedagrac-kasync", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cpu_run_fails_without_a_result():
    p = _run(chipbench_tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copytree(chipbench_tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(chipbench_tiny.ROOT, "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    # past the chip check too: the program is not there to import
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
            "import harness; print(harness.run('granite-fedagrac-kasync', "
            "1, 1.0, False, 0.0, chip_check=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr
