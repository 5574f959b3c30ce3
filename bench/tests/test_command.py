"""The command and the four-chip cell's geometry, each in a process of its
own: the command refuses a machine without a TPU and a directory without
the program; cell 2 runs on four virtual CPU devices."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = str(harness.ROOT)
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "src")])}
POOL4 = """
import json, sys, time
from bench import harness
from bench.tests.smoke import smoke_cell
cell = smoke_cell("pool4")
print(json.dumps(harness.run_cell(cell, 2 ** 32 + 7, 3.0, False,
                                  time.perf_counter())))
"""


def _cmd(cwd, env=ENV):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-0.6b.chat-shared", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("argv", [
    ["bench/control.py", "--seconds", "1", "--seeds", "1"],
    ["bench/sweep.py", "--seconds", "1", "--seed", "1", "--rates", "0.1"]])
def test_readings_refuse_without_a_tpu(argv):
    """The control's and the sweep's readings are taken on the chip only."""
    p = subprocess.run([sys.executable, *argv, "--workload",
                        "qwen3-0.6b.chat-shared"], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    p = _cmd(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_pool4_cell_on_four_virtual_devices():
    env = {**ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", POOL4], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, p.stderr[-3000:]
    assert r["device"]["count"] == 4
    assert r["attempted"] > 0 and r["metrics"]["itl_p95_s"]["value"] > 0
