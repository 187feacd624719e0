"""The program's own spans and op scopes, read from synthetic planes whose
answers are known, and the readers of the metrics built on them, each on a
hand-built record."""

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_trace as pt  # noqa: E402
from bench import trace  # noqa: E402

FUSION = "%fusion.7 = bf16[16,256,2048]{2,1,0} fusion(bf16[16,256,2048] %p), kind=kOutput"
MERGE = "%fusion.9 = f32[1,4096,16,128]{3,2,1,0} fusion(f32[1,4096,16,128] %o), kind=kLoop"
MERGE_T = "%fusion.12 = f32[1,4096,16,128]{3,2,1,0} fusion(f32[1,4096,16,128] %d), kind=kLoop"
FWD = ('%jvp_flash_fwd_.1 = (bf16[1,16,8192,128], f32[1,16,1,8192]) custom-call(s32[1,1,8192] %a, '
       's32[1,1,8192] %b, bf16[1,16,8192,128] %q, bf16[1,8,8192,128] %k, bf16[1,8,8192,128] %v), '
       'custom_call_target="tpu_custom_call"')
DQ = ('%transpose_jvp_flash_bwd_dq__.3 = f32[1,16,8192,128] custom-call(s32[1,1,8192] %a, '
      's32[1,1,8192] %b, bf16[1,16,8192,128] %q, bf16[1,8,8192,128] %k, bf16[1,8,8192,128] %v, '
      'bf16[1,16,8192,128] %do, f32[1,16,1,8192] %l, f32[1,16,1,8192] %d, f32[1,16,1,8192] %dl), '
      'custom_call_target="tpu_custom_call"')


def _planes():
    # One chip.  Harness span engine_tick 100..2100 holds the engine's tick
    # 150..2000: admit 150..300, prefill 300..700 (its sync_bt 350..450),
    # decode 700..900, sample 900..1800.  Device busy 500..700 and
    # 1000..1600; the rest of the window is idle.
    host = {"python3": [
        ("engine_tick", 100, 2000, {}),
        ("engine.tick", 150, 1850, {"tick": 7}),
        ("engine.admit", 150, 150, {"uid": 3}),
        ("engine.prefill", 300, 400, {"valid_tokens": 300, "padded_tokens": 4096}),
        ("engine.sync_bt", 350, 100, {}),
        ("engine.decode", 700, 200, {}),
        ("engine.sample", 900, 900, {}),
        ("$engine.py:101 prefilled", 120, 5, {}),
    ]}
    dev = {
        "XLA Modules": [("jit_prefill_chunk_paged(1)", 500, 200),
                        ("jit_decode_step_paged(2)", 1000, 600)],
        "XLA Ops": [(FUSION, 500, 200, {}), (MERGE, 1000, 600, {})],
    }
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


def _reduction(planes):
    strip = [(p, {ln: [e[:3] for e in evs] for ln, evs in lines.items()}) for p, lines in planes]
    return trace.reduce_planes(strip, 5000, ("engine_tick", "submit", "sp_step"))


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_program_spans_do_not_move_the_window():
    planes = _planes()
    red = _reduction(planes)
    assert red.window == (100, 2100)
    assert [n for n, _, _ in red.host_spans] == ["engine_tick"]
    prog = pt.from_planes(planes)
    assert [s.name for s in prog.spans] == ["engine.tick", "engine.admit", "engine.prefill",
                                            "engine.sync_bt", "engine.decode", "engine.sample"]
    assert prog.spans[0].meta == {"tick": 7}
    assert pt._window(planes) == red.window  # how a run's profile is found again


def test_idle_goes_to_the_innermost_engine_span():
    planes = _planes()
    red, prog = _reduction(planes), pt.from_planes(planes)
    idle = pt.phase_idle(red, prog)
    assert idle == {
        "engine_tick": 50 + 100,  # 100..150 before the tick, 2000..2100 after it
        "engine.admit": 150,
        "engine.prefill": 50 + 50,  # 300..350 and 450..500
        "engine.sync_bt": 100,
        "engine.decode": 200,
        "engine.sample": 100 + 200,  # 900..1000 and 1600..1800
        "engine.tick": 200,  # 1800..2000, between the phases
    }
    assert sum(idle.values()) == red.window_ns - 800  # every idle ns, once


def test_op_scope_read_from_event_metadata():
    """A TPU profile keeps an op's scope path in the ``tf_op`` stat of its
    event metadata, as a string or as a reference to a stat name; the
    ``:<type>`` tail goes."""
    space = pt._xspace_class()()
    host = space.planes.add(name=b"/host:CPU")
    host.event_metadata.add(key=1).value.name = b"engine.tick"
    dev = space.planes.add(name=b"/device:TPU:0")
    for k, name in ((1, b"tf_op"), (2, b"hlo_category"), (3, b"jit(s)/ring_send/ppermute:")):
        dev.stat_metadata.add(key=k).value.name = name
    md = dev.event_metadata.add(key=7).value
    md.name = FUSION.encode()
    md.stats.add(metadata_id=2, str_value=b"loop fusion")
    md.stats.add(metadata_id=1, str_value=b"jit(prefill_chunk_paged)/while/body/dot_general:")
    md = dev.event_metadata.add(key=8).value
    md.name = MERGE.encode()
    md.stats.add(metadata_id=1, ref_value=3)
    dev.event_metadata.add(key=9).value.name = b"%copy.1 = f32[2] copy(f32[2] %a)"
    scopes = pt.read_scopes(space.SerializeToString())
    assert scopes == {"/device:TPU:0": {
        FUSION: "jit(prefill_chunk_paged)/while/body/dot_general",
        MERGE: "jit(s)/ring_send/ppermute"}}
    prog = pt.ProgramTrace([], scopes)
    red = _reduction(_planes())
    (fusion,) = red.devices[0].executions[0].ops
    assert prog.scope("/device:TPU:0", fusion) == "jit(prefill_chunk_paged)/while/body/dot_general"
    assert prog.scope("/device:TPU:1", fusion) == ""


def test_kernel_names_through_transformations():
    assert pt.kernel_name(FWD) == "flash_fwd"
    assert pt.kernel_name(DQ) == "flash_bwd_dq"
    dkv = "%transpose_jvp_flash_bwd_dkv__ = f32[2] custom-call()"
    assert pt.kernel_name(dkv) == "flash_bwd_dkv"
    assert pt.kernel_name("%closed_call.23 = f32[2] custom-call()") is None


def _serve_rec():
    planes = _planes()
    red = _reduction(planes)
    return {"reduction": red, "program_trace": pt.from_planes(planes),
            "prefill_execs": red.executions(lambda e: e.name == "jit_prefill_chunk_paged"),
            "decode_execs": red.executions(lambda e: e.name == "jit_decode_step_paged")}


def test_prefill_row_fill_reader():
    read = _reader("prefill_row_fill.serve")
    assert read(_serve_rec()) == pytest.approx(100.0 * 300 / 4096)
    rec = _serve_rec()
    rec["program_trace"] = pt.ProgramTrace([], {})  # a program without the spans
    assert read(rec) is None
    assert read({}) is None


def test_tick_host_idle_reader():
    read = _reader("tick_host_idle_ms.serve")
    engine_idle = 150 + 100 + 100 + 200 + 300 + 200  # ns, one tick
    assert read(_serve_rec()) == pytest.approx(engine_idle / 1e6)
    rec = _serve_rec()
    rec["program_trace"] = pt.ProgramTrace([], {})
    assert read(rec) is None


def test_ring_merge_share_reader():
    read = _reader("ring_merge_share.sp")
    dev = {
        "XLA Modules": [("jit_sp_step(1)", 0, 1000)],
        "XLA Ops": [(FWD, 0, 600, {}), (MERGE, 600, 100, {}), (MERGE_T, 700, 50, {}),
                    (FUSION, 750, 250, {})],
    }
    planes = [("/device:TPU:0", dev), ("/host:CPU", {"python3": [("sp_step", 0, 1000, {})]})]
    scopes = {"/device:TPU:0": {
        FWD: "jit(sp_step)/jvp()/shard_map/ring_compute/flash_fwd/pallas_call",
        MERGE: "jit(sp_step)/jvp()/shard_map/ring_merge/add",
        MERGE_T: "jit(sp_step)/transpose(jvp())/shard_map/ring_merge/mul",
        FUSION: "jit(sp_step)/transpose(jvp())/shard_map/convert_element_type"}}
    red = _reduction(planes)
    rec = {"reduction": red, "program_trace": pt.ProgramTrace([], scopes),
           "step_execs": red.executions(lambda e: e.name == "jit_sp_step")}
    assert read(rec) == pytest.approx(15.0)
    notes = pt.name_notes(rec, rec["program_trace"])
    assert "by name {'flash_fwd': 1, 'flash_bwd_dq': 0, 'flash_bwd_dkv': 0}" in notes[0]
    assert not any("WARNING" in n for n in notes)
    unscoped = {"/device:TPU:0": {o: "jit(sp_step)/jvp()/x" for o in scopes["/device:TPU:0"]}}
    rec["program_trace"] = pt.ProgramTrace([], unscoped)  # a program without the scopes
    assert read(rec) is None


def test_name_notes_warn_when_counts_differ():
    rec = _serve_rec()
    notes = pt.name_notes(rec, rec["program_trace"])
    assert notes == ["serving programs by name {'prefill': 1, 'decode': 1}, "
                     "by shape {'prefill': 1, 'decode': 1}"]
    rec["decode_execs"] = []
    assert any("WARNING" in n for n in pt.name_notes(rec, rec["program_trace"]))
