"""The command refuses to measure anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import harness

CMD = [sys.executable, "chipbench/run.py", "--workload",
       "mamba2-370m.c2.t2048", "--seed", "3000000019", "--seconds", "1",
       "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            return "correct" in json.loads(line)
        except ValueError:
            continue
    return False


def test_the_command_exits_nonzero_on_the_cpu():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "needs a TPU" in proc.stderr


def test_the_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
