"""``chip_smoke.py`` on the CPU: its refusals, and its check phases at tiny
sizes in Pallas interpret mode, so a break in the smoke script shows up
before a chip is spent on it."""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=300,
        env=env, cwd=cwd,
    )


def test_refuses_without_tpu():
    proc = _run(SCRIPT, REPO)
    assert proc.returncode != 0
    assert "needs a TPU, found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_outside_a_checkout(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path)
    proc = _run(alone, tmp_path)
    assert proc.returncode != 0
    assert "run from a checkout" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_kernel_checks_pass_in_interpret_mode(smoke):
    checks = smoke.Checks()
    smoke.check_kernels(checks, seed=0, impl="pallas_interpret", B=2, S=256, page=16)
    assert checks.failed == []


def test_bound_check_fails_past_its_bound(smoke):
    checks = smoke.Checks()
    checks.bound("close", [1.0, 2.0], [1.0, 2.01], 2e-2)
    checks.bound("far", [1.0, 2.0], [1.0, 2.5], 2e-2)
    checks.bound("nan", [float("nan")], [0.0], 2e-2)
    checks.bound("dead rows", [float("-inf")], [float("-inf")], 2e-2)
    assert checks.failed == ["far", "nan"]


def test_serving_check_reduced(smoke):
    """Both serving passes and the greedy-token comparison, on the reduced
    qwen3-1.7b with interpret-mode kernels."""
    args = [
        "--arch", "qwen3-1.7b", "--reduced", "--page-size", "16",
        "--max-len", "128", "--max-batch", "4", "--requests", "4",
        "--shared-prefix", "40", "--prefill-chunk", "16", "--max-new", "12",
        "--max-pages", "24",
    ]
    checks = smoke.Checks()
    smoke.check_serving(checks, seed=0, impl="pallas_interpret", args=args)
    assert checks.failed == []


def test_step_memory_check_reduced(smoke):
    """The step-memory phase on the reduced qwen3-1.7b: both donated paged
    steps compile on the CPU and alias the whole K/V pool."""
    from repro.configs import ARCHS

    checks = smoke.Checks()
    smoke.check_step_memory(
        checks, impl="xla", cfg=ARCHS["qwen3-1.7b"].reduced(), max_batch=4,
        max_len=128, page_size=16, prefill_chunk=16, max_pages=24,
    )
    assert checks.failed == []


def test_step_memory_phase_sized_from_the_cell_config(smoke):
    """Phase 4 takes the engine's sizes from the qwen3-longdoc cell's
    configuration, so it compiles the steps the benchmark runs."""
    import json

    serving = json.loads(smoke.CELL_CONFIG.read_text())["serving"]
    assert all(isinstance(serving[k], int) for k in smoke.CELL_STEP_KEYS)
