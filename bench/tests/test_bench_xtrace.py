"""The reduction from a profiler trace to the per-layer numbers: on events
made by hand, and on a trace recorded on a TPU v5e."""

import gzip
import json
import time

import pytest

import bench_testlib as tl
from benchlib import harness, peaks, xtrace
from benchlib.xtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _mod(program, start, end, run):
    return Event(DEV, xtrace.MODULES_LINE, program, start, end, program, run)


def _op(text, start, end):
    return Event(DEV, xtrace.OPS_LINE, text, start, end)


def _span(name, start, end):
    return Event(HOST, "python", name, start, end)


PREFILL, DECODE, ARGMAX = "jit__lambda(1)", "jit__lambda(2)", "jit_argmax(3)"


def _synthetic():
    """Window 0..100: a prefill at 10..30, two decode steps at 40..50 and
    70..75, an argmax after each."""
    ev = [_span(xtrace.SPAN_WINDOW, 0, 100),
          _span(xtrace.SPAN_PREFILL, 5, 8), _span("PjitFunction(x)", 6, 7),
          _span(xtrace.SPAN_DECODE, 35, 38), _span("readout", 52, 69),
          _span("int", 58, 64), _span(xtrace.SPAN_DECODE, 66, 68),
          _mod(PREFILL, 10, 30, 1), _mod(ARGMAX, 31, 32, 2),
          _mod(DECODE, 40, 50, 3), _mod(ARGMAX, 51, 52, 4),
          _mod(DECODE, 70, 75, 5), _mod(ARGMAX, 76, 77, 6),
          _op("%while.3 = (s32[], bf16[16,1,1024]{2,0,1}) while((s32[]) %t)",
              40, 50),
          _op("%copy.9 = bf16[28,16,1156,8,128]{4,1,3,2,0} copy(bf16[1] %c)",
              41, 45),
          _op("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 10, 30)]
    calls = [("prefill", 4, 16), ("decode", 4, 16), ("decode", 4, 17)]
    return ev, calls


def test_busy_is_the_union_of_op_intervals():
    r = xtrace.reduce(*_synthetic())
    # 10..30, 31..32, 40..50, 51..52, 70..75, 76..77
    assert r.busy_s == pytest.approx(38e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_programs_are_told_apart_by_the_spans_before_them():
    r = xtrace.reduce(*_synthetic())
    assert r.matched
    (p,) = r.of_kind("prefill")
    assert (p.program, p.batch, p.seq, p.start, p.end) == (PREFILL, 4, 16,
                                                           10, 30)
    d = r.of_kind("decode")
    assert [(x.program, x.held, x.start) for x in d] == [(DECODE, 16, 40),
                                                         (DECODE, 17, 70)]
    assert {x.kind for x in r.executions if x.program == ARGMAX} == {"other"}


def test_device_ops_by_self_time():
    r = xtrace.reduce(*_synthetic())
    ops = dict(r.device_ops)
    assert ops["prefill:fusion.1 bf16[8] fusion"] == pytest.approx(20e-9)
    assert ops["decode:while.3 s32[] while"] == pytest.approx(6e-9)
    assert ops["decode:copy.9 bf16[28,16,1156,8,128] copy"] == pytest.approx(
        4e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = xtrace.reduce(*_synthetic())
    gaps = {(s, e): n for s, e, n in r.idle_gaps}
    assert gaps[(52, 70)] == "int"          # its middle, 61: readout > int
    assert gaps[(0, 10)] == xtrace.SPAN_PREFILL
    assert gaps[(77, 100)] == "(no host span)"
    assert r.idle_between(50, 70) == pytest.approx(19e-9)


def test_no_window_no_record():
    ev, calls = _synthetic()
    assert xtrace.reduce(ev[1:], calls) is None


def test_unmatched_calls_leave_the_step_metrics_out():
    ev, calls = _synthetic()
    r = xtrace.reduce(ev, calls[:2])
    assert not r.matched


CHIP_TRACE = tl.BENCH / "tests" / "data" / "trace_v5e_qwen3_chat.json"


def test_trace_recorded_on_a_v5e():
    """qwen3-0.6b.chat on a "TPU v5 lite": the first prefill of a traced
    call and the four decode steps after it, cut from its trace with the
    harness's call log: the device's modules, the ops of the first decode
    step (their names cut after the opcode), the Python thread's spans."""
    ev, calls = xtrace.load_json(CHIP_TRACE)
    r = xtrace.reduce(ev, calls)
    assert r.matched and r.devices == 1
    (p,) = r.of_kind("prefill")
    d = r.of_kind("decode")
    assert (p.batch, p.seq) == (16, 1024)
    assert [x.held for x in d] == [1024, 1025, 1026, 1027]
    assert p.seconds == pytest.approx(0.209845712)
    assert [x.seconds for x in d] == pytest.approx(
        [0.019014933, 0.019018401, 0.019016506, 0.019013752])
    assert r.window_s == pytest.approx(0.386195026)
    assert r.busy_s == pytest.approx(0.286167221)

    cell = harness.load_cell(tl.REPO, "qwen3-0.6b.chat")
    run = harness.Run(cell, peaks.lookup("TPU v5 lite"), 3.0, r)
    got = {k: m.read(run) for k, m in cell.readers.items()}
    assert got == pytest.approx({
        "compile_s": 3.0, "decode_gap_ms": 24.90527033333333,
        "decode_roofline": 19.78057162257246,
        "prefill_roofline": 39.579703399966164, "mfu": 21.626389826280935,
        "idle_share": 25.90085274687095})
    for k in ("decode_roofline", "prefill_roofline", "mfu"):
        assert 0 < got[k] <= 100
    b = harness.breakdown(r)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == pytest.approx(
        ["decode:copy.9 bf16[28,16,1156,8,128] copy", 0.003218706])
    assert dict(b["idle_gaps"])["DevicePut"] == pytest.approx(0.030161259)
