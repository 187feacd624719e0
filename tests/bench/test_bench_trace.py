"""The trace reduction on a synthetic event list whose answers are known.
Op names are HLO text as a TPU trace carries it."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

KERNEL = ('%closed_call.23 = (bf16[16,16,256,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
          'f32[16,16,1,256]{3,2,1,0:T(1,128)S(1)}) custom-call(s32[16,1,256]{2,1,0} %a, '
          's32[16,1,256]{2,1,0} %b, bf16[16,16,256,128]{3,2,1,0} %c, bf16[16,8,256,128] %d, '
          'bf16[16,8,256,128] %e), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
BWD = ('%closed_call.40 = bf16[1,16,8192,128]{3,2,1,0} custom-call(s32[1,1,8192] %a, '
       's32[1,1,8192] %b, bf16[1,16,8192,128] %q, bf16[1,8,8192,128] %k, '
       'bf16[1,8,8192,128] %v, bf16[1,16,8192,128] %do, f32[1,16,1,8192] %l, '
       'f32[1,16,1,8192] %dl), custom_call_target="tpu_custom_call"')
WHILE = "%while.3 = (s32[], bf16[8,128]{1,0}) while((s32[], bf16[8,128]{1,0}) %tuple), condition=%cond, body=%body"
FUSION = "%fusion.7 = bf16[16,256,2048]{2,1,0:T(8,128)(2,1)} fusion(bf16[16,256,2048] %p, bf16[2048,2048] %w), kind=kOutput"
PERMUTE = "%collective-permute-done.1 = bf16[1,4096,16,128]{3,2,1,0} collective-permute-done((bf16[1,4096,16,128], bf16[1,4096,16,128]) %collective-permute-start.1)"
IOTA = "%iota = s32[1,1,2048]{2,1,0:T(1,128)S(1)} iota(), iota_dimension=2"


def test_opcodes_and_kinds():
    assert trace.opcode(KERNEL) == "custom-call" and trace.is_kernel(KERNEL)
    assert trace.opcode(FUSION) == "fusion" and not trace.is_kernel(FUSION)
    assert trace.opcode(IOTA) == "iota"
    assert trace.opcode(PERMUTE) == "collective-permute-done" and trace.is_collective(PERMUTE)
    assert not trace.is_collective(FUSION)
    assert trace.operand_count(KERNEL) == 5  # flash forward: positions, q, k, v
    assert trace.operand_count(BWD) == 8


def _planes():
    # One chip, harness spans over 100..2100 ns of a longer profile: a step
    # program 100..1100 whose layer loop (a while op) spans its body: a
    # fusion 100..400, a permute wait 400..600 and a kernel 600..900; then
    # idle while the host runs a tick.  A program before the first span
    # (the profiler starting) is outside the window.
    dev = {
        "XLA Modules": [("jit_warm(1)", 0, 50), ("jit_sp_step(123)", 100, 1000)],
        "XLA Ops": [(FUSION, 0, 50), (WHILE, 100, 900), (FUSION, 100, 300),
                    (PERMUTE, 400, 200), (KERNEL, 600, 300)],
    }
    host = {"python3": [("sp_step", 100, 1000), ("engine_tick", 1100, 1000),
                        ("PjitFunction(x)", 0, 10)]}
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


def test_reduce_busy_idle_and_leaves():
    red = trace.reduce_planes(_planes(), 5000, ("engine_tick", "sp_step"))
    assert len(red.devices) == 1
    (ex,) = red.executions()
    assert ex.name == "jit_sp_step"
    assert [trace.opcode(o.text) for o in ex.ops] == ["fusion", "collective-permute-done", "custom-call"]
    assert red.busy_s() == pytest.approx(800e-9)
    assert red.window_s == pytest.approx(2000e-9)
    assert len(ex.kernels()) == 1


def test_exposed_collective_share():
    red = trace.reduce_planes(_planes(), 5000, ("sp_step",))
    (ex,) = red.executions()
    assert trace.exposed_collective_ns(ex.ops) == 200
    # a collective that compute covers in part is exposed only for the rest
    ops = [trace.Op(PERMUTE, 0, 100), trace.Op(FUSION, 50, 100)]
    assert trace.exposed_collective_ns(ops) == 50


def test_interval_arithmetic():
    assert trace.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert trace.subtract_length([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace.subtract_length([(0, 10)], []) == 10


def test_window_without_harness_spans_is_the_profile():
    red = trace.reduce_planes(_planes(), 5000, ())
    assert red.window_s == pytest.approx(5000e-9)
    assert len(red.executions()) == 2
    assert red.busy_s() == pytest.approx(850e-9)


def test_idle_gaps_take_the_host_span_they_overlap():
    red = trace.reduce_planes(_planes(), 5000, ("engine_tick", "sp_step"))
    bd = trace.breakdown(red)
    assert bd["idle_gaps"][0][0] == "engine_tick"
    assert bd["idle_gaps"][0][1] == pytest.approx(1200e-9)
    names = [k for k, _ in bd["device_ops"]]
    assert names[0] in ("jit_sp_step:fusion", "jit_sp_step:kernel")
    assert "jit_sp_step:while" not in names  # containers are not double counted
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_two_chips_average():
    planes = _planes()
    planes.append(("/device:TPU:1", {"XLA Modules": [("jit_sp_step(1)", 100, 400)],
                                     "XLA Ops": [(FUSION, 100, 400)]}))
    red = trace.reduce_planes(planes, 5000, ("sp_step", "engine_tick"))
    assert red.busy_s() == pytest.approx((800 + 400) / 2 * 1e-9)
    assert len(red.executions()) == 2
