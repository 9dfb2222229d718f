"""bench/run.py refuses to measure anywhere but on the chip."""
import json
import os
import shutil
import subprocess
import sys

import benchtiny


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stream4k.ingest",
         "--seed", str(2 ** 33 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_without_a_tpu_it_exits_non_zero_with_no_result():
    out = _run(benchtiny.REPO)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "no TPU" in out.stderr


def test_with_only_the_benchmark_files_it_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(benchtiny.REPO, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(benchtiny.REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for path in paths:
        shutil.copytree(os.path.join(benchtiny.REPO, path),
                        os.path.join(tmp_path, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert not _has_result(out.stdout)
