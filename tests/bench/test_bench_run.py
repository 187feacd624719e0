"""The benchmark command as a run invokes it: refusals without a chip or outside a
checkout, the result line's keys, and the comparison that decides
``correct`` -- a sound run passes, each planted fault and the control fail.

The harness's look for a chip is skipped by ``bench_tiny.py``, which drives
the rest of a run at a tiny size in a fresh interpreter (a JAX parent cannot
change its device count)."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
RUN = [sys.executable, "bench/run.py", "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def _cmd(args, cwd=ROOT):
    return subprocess.run(args, cwd=cwd, env=ENV, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    r = _cmd(RUN + ["--workload", "qwen3-longdoc"])
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_unknown_workload_exits_nonzero():
    r = _cmd(RUN + ["--workload", "no-such-cell"])
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_benchmark_files_alone_are_not_enough(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    r = _cmd(RUN + ["--workload", "qwen3-longdoc"], cwd=tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


def _scenarios(kind):
    r = subprocess.run([sys.executable, "tests/bench/bench_tiny.py", kind], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith('{"scenario"'):
            d = json.loads(line)
            out[d["scenario"]] = d
    return out


@pytest.fixture(scope="module")
def serve_runs():
    return _scenarios("serve")


@pytest.fixture(scope="module")
def sp_runs():
    return _scenarios("sp")


def _check_sound(runs):
    for trace in (0, 1):
        d = runs[f"sound_trace{trace}"]
        assert d["rc"] == 0
        line = d["line"]
        want = ["correct", "attempted", "failed", "metrics", "device"]
        want += ["breakdown"] if trace else []
        assert list(line) == want + ["checks"]  # the compared numbers come last
        assert line["correct"] is True
        assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        if trace:
            assert set(line["device"]) >= {"busy_s", "window_s"}
            assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for v in line["checks"].values():
            assert v["value"] <= v["limit"]


def _check_faults(runs, names):
    for name in names:
        line = runs[name]["line"]
        assert runs[name]["rc"] == 0 and line["correct"] is False, (name, line["checks"])


def _due_in_window():
    sys.path.insert(0, str(ROOT / "tests" / "bench"))
    import bench_tiny

    return math.floor(bench_tiny.serve_mix()["arrivals"]["rate_per_s"] * bench_tiny.SECONDS)


def test_serve_sound_run(serve_runs):
    _check_sound(serve_runs)
    m = serve_runs["sound_trace0"]["line"]["metrics"]
    assert set(m) == {"setup_s", "ttft_p90_s", "tpot_p90_ms", "output_tokens_per_s"}
    assert serve_runs["sound_trace0"]["line"]["attempted"] == _due_in_window()


def test_serve_requests_never_submitted_still_count(serve_runs):
    """A tick that runs past the close leaves requests due in the window
    unsubmitted; they count as attempted, with no token."""
    d = serve_runs["stall_at_close"]
    assert d["rc"] == 0 and d["line"]["attempted"] == _due_in_window()
    note = next(x for x in d["stderr"] if "never submitted" in x)
    assert int(note.rsplit(" ", 1)[1]) > 0, note
    assert d["line"]["metrics"]["ttft_p90_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged", "half_batch"])
def test_serve_fault_is_not_correct(serve_runs, fault):
    _check_faults(serve_runs, [fault])


def test_serve_control_separates(serve_runs):
    rows = serve_runs["control_readings"]["rows"]
    limit = serve_runs["control_readings"]["limits"]["logit_gap_max"]
    lo = max(r["program"]["logit_gap_max"] for r in rows)
    up = min(r["control"]["logit_gap_max"] for r in rows)
    assert up >= 3 * lo
    assert lo <= limit < up


def test_serve_control_in_the_programs_place_is_not_correct(serve_runs):
    _check_faults(serve_runs, ["control"])


def test_sp_sound_run(sp_runs):
    _check_sound(sp_runs)
    assert set(sp_runs["sound_trace0"]["line"]["metrics"]) == {"setup_s", "sp_tokens_per_s"}
    assert sp_runs["sound_trace0"]["line"]["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["no_exchange", "token_altered"])
def test_sp_fault_is_not_correct(sp_runs, fault):
    _check_faults(sp_runs, [fault])


def test_sp_control_in_the_programs_place_is_not_correct(sp_runs):
    _check_faults(sp_runs, ["control"])


def test_sp_control_fails_the_limits(sp_runs):
    limits = sp_runs["control_readings"]["limits"]
    for r in sp_runs["control_readings"]["rows"]:
        assert any(r["control"][k] > limits[k] for k in limits)
        assert all(r["program"][k] <= limits[k] for k in limits)
