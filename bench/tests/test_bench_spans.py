"""The serving engine's spans on the device's clock: the bracket of the
device clock's lead, the shift of the spans alone, and the device idle
inside each phase, on events made by hand, on a CPU run of the small cell,
and on traces recorded on a TPU v5e."""

import json
import statistics

import pytest

import bench_testlib as tl
import phases
from benchlib import spans, xtrace
from benchlib.xtrace import Event
from repro.serving import engine as E

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _span(name, start, end):
    return Event(HOST, "python", name, start, end)


def _mod(program, start, end, run):
    return Event(DEV, xtrace.MODULES_LINE, program, start, end, program, run)


PREFILL, DECODE = "jit_serve_prefill(1)", "jit_serve_decode(2)"
SLICE = "jit_dynamic_slice(3)"


def _synthetic(lead=3):
    """One wave of two decode steps, window 0..300 on the host's clock;
    the device's timestamps run ``lead`` ahead of it (3: it reads the
    program that starts 1 after its dispatch span at 5, as at 2)."""
    d = 3 - lead                       # device times, as recorded
    host = [_span(xtrace.SPAN_WINDOW, 0, 300),
            _span(E.SPAN_GENERATE, 2, 210),
            _span(E.SPAN_ADMIT, 2, 4),
            _span(E.SPAN_PREFILL, 5, 20), _span(xtrace.SPAN_PREFILL, 5, 18),
            _span(E.SPAN_READOUT, 21, 100),
            _span(spans.BLOCKING_READ, 22, 64),
            _span(spans.BLOCKING_READ, 70, 80),
            _span(E.SPAN_DISPATCH, 101, 110),
            _span(xtrace.SPAN_DECODE, 102, 109),
            _span(E.SPAN_READOUT, 111, 195),
            _span(spans.BLOCKING_READ, 112, 152),
            _span(E.SPAN_DISPATCH, 200, 205),
            _span(xtrace.SPAN_DECODE, 200, 204),
            _span(E.SPAN_RELEASE, 206, 208)]
    dev = [_mod(PREFILL, 5 + d, 57 + d, 1), _mod("jit_argmax(4)", 58 + d,
                                                 59 + d, 2),
           _mod(SLICE, 60 + d, 61 + d, 3),
           _mod(DECODE, 102 + d, 147 + d, 4), _mod(SLICE, 148 + d, 149 + d, 5),
           _mod(DECODE, 197 + d, 237 + d, 6)]
    calls = [("prefill", 2, 8), ("decode", 2, 8), ("decode", 2, 9)]
    return host + dev, calls


def _phases(lead=3):
    ev, calls = _synthetic(lead)
    red = xtrace.reduce(ev, calls)
    return spans.phases(ev, red), red


def test_bracket_from_dispatch_and_read_pairs():
    ph, red = _phases()
    # dispatch span start less execution start: 5-5, 101-102, 200-197;
    # first read's end less the step program's end: 64-57, 152-147
    assert (ph.lower_ns, ph.upper_ns, ph.shift_ns) == (3, 5, 3)
    assert ph.late_starts() == 0
    assert red.matched and [x.kind for x in red.executions] == [
        "prefill", "other", "other", "decode", "other", "decode"]


@pytest.mark.parametrize("lead,shift", [(3, 3), (-1, 0), (-7, -5)])
def test_shift_is_the_least_that_breaks_no_bound(lead, shift):
    ph, _ = _phases(lead)
    assert (ph.lower_ns, ph.upper_ns) == (lead, lead + 2)
    assert ph.shift_ns == shift
    assert ph.late_starts() == 0


def test_only_the_engine_spans_move():
    ev, calls = _synthetic()
    ph = spans.phases(ev, xtrace.reduce(ev, calls))
    plain = xtrace.reduce(*_synthetic())
    assert [(s.name, s.start, s.end) for s in ph.spans][:3] == [
        (E.SPAN_GENERATE, -1, 207), (E.SPAN_ADMIT, -1, 1),
        (E.SPAN_PREFILL, 2, 17)]
    assert {s.name for s in ph.spans} == set(spans.SPANS)
    assert ph.window == (-3, 297)
    # the reduction and the events it came from are as they were
    assert ph.trace.executions == plain.executions
    assert ph.trace.idle_gaps == plain.idle_gaps
    assert ph.trace.busy_s == plain.busy_s
    assert [e.start for e in ev if e.name == E.SPAN_PREFILL] == [5]


def test_idle_inside_spans_of_one_name():
    ph, _ = _phases()
    # busy on the device: 5..57, 58..59, 60..61, 102..147, 148..149, 197..237
    assert ph.idle_in(E.SPAN_READOUT) == pytest.approx([38e-9, 44e-9])
    assert ph.idle_in(E.SPAN_DISPATCH) == pytest.approx([4e-9, 0.0])
    idle = ph.idle_s()
    assert idle == pytest.approx({
        E.SPAN_GENERATE: 98e-9, E.SPAN_ADMIT: 2e-9, E.SPAN_PREFILL: 3e-9,
        E.SPAN_READOUT: 82e-9, E.SPAN_DISPATCH: 4e-9, E.SPAN_RELEASE: 0.0,
        # before the admit span, between spans, and the drain's tail
        spans.NO_PHASE: 69e-9})
    assert sum(idle[n] for n in spans.PHASES) + idle[spans.NO_PHASE] == \
        pytest.approx(300e-9 - 140e-9)
    line = ph.line()
    assert line.startswith("trace: idle_s serve.generate=")
    assert "lower=0.000 upper=0.000 shift=0.000" in line


def test_no_engine_spans_no_phases():
    ev, calls = _synthetic()
    bare = [e for e in ev if not e.name.startswith("serve.")]
    assert spans.phases(bare, xtrace.reduce(bare, calls)) is None
    assert spans.phases(ev, None) is None


def test_a_mismatched_kind_gives_no_pairs():
    """A decode execution the trace lost: its kind gives no pair, the
    prefill still bounds the lead from below."""
    ev, calls = _synthetic()
    ev = [e for e in ev if not (e.program == DECODE and e.start == 197)]
    ph = spans.phases(ev, xtrace.reduce(ev, calls))
    assert ph.lower_ns == 0
    # 64 - 57; step 1's read pairs with the prefill too, a looser bound
    assert ph.upper_ns == 7


def test_cpu_run_of_the_small_cell(tmp_path, capsys):
    """The whole measurement off the chip, at the small size: 2 slots, two
    waves of 8 steps; one read per lane and step."""
    root = tl.small_root(tmp_path)
    lines = []
    with tl.jax_config_kept():
        phases.main(["--workload", "qwen3-0.6b.chat", "--seeds",
                     str(2 ** 33 + 5)], root=root, require_tpu=False,
                    peak=tl.CPU_PEAK, out=lines.append)
    (got,) = [json.loads(s) for s in lines]
    assert got["engine"] == {"calls": 1, "waves": 2, "decode_steps": 16,
                             "tokens_out": 32, "host_reads": 32,
                             "streams_parked": 2}
    assert got["host_reads_per_step"] == 2.0
    assert got["matched"] and got["late_starts"] == 0
    assert got["readout_idle_ms"] > 0 and got["decode_gap_ms"] > 0
    assert got["clock_lower_ms"] <= got["clock_upper_ms"]
    assert set(got["idle_s"]) == set(spans.SPANS) | {spans.NO_PHASE}
    assert "trace: idle_s serve.generate=" in capsys.readouterr().err


OLD_TRACE = tl.BENCH / "tests" / "data" / "trace_v5e_qwen3_chat.json"


def test_trace_without_engine_spans():
    """The trace recorded before the engine had spans gives no phases."""
    ev, calls = xtrace.load_json(OLD_TRACE)
    assert spans.phases(ev, xtrace.reduce(ev, calls)) is None


SPAN_TRACE = tl.BENCH / "tests" / "data" / "trace_v5e_qwen3_serve_spans.json"


def _between_decodes(r):
    idx = [i for i, x in enumerate(r.executions) if x.kind == "decode"]
    return [j - i - 1 for i, j in zip(idx, idx[1:])]


def test_trace_with_engine_spans_recorded_on_a_v5e():
    """qwen3-0.6b.chat on a "TPU v5 lite", recorded by ``bench/phases.py
    --save``: one wave of 16 lanes, a prefill and 4 decode steps, the
    device's modules and the Python thread's spans (no ops)."""
    ev, calls = xtrace.load_json(SPAN_TRACE)
    r = xtrace.reduce(ev, calls)
    assert r.matched and r.devices == 1
    assert [x.program.split("(")[0] for x in r.executions
            if x.kind != "other"] == ["jit_serve_prefill"] + [
                "jit_serve_decode"] * 4
    # the engine's spans add no device work: as many programs between two
    # decode steps as in the trace recorded before they existed
    old, old_calls = xtrace.load_json(OLD_TRACE)
    assert _between_decodes(r) == _between_decodes(
        xtrace.reduce(old, old_calls)) == [43, 43, 43]

    ph = spans.phases(ev, r)
    # this trace's device clock did not run ahead: the bracket holds 0
    assert (ph.lower_ns, ph.upper_ns, ph.shift_ns) == (-556737, 946087, 0)
    assert ph.late_starts() == 0
    readout = ph.idle_in(E.SPAN_READOUT)
    dispatch = ph.idle_in(E.SPAN_DISPATCH)
    assert readout == pytest.approx([0.0271088, 0.0254703, 0.024261629,
                                     0.025276572])
    assert dispatch == pytest.approx([0.000694765, 0.000635243,
                                      0.000556737, 0.000639234])
    # the idle between decode steps is the readout's and the dispatch's
    d = r.of_kind("decode")
    gap = statistics.mean(r.idle_between(a.end, b.start)
                          for a, b in zip(d, d[1:]))
    assert gap == pytest.approx(0.025617881333)
    assert sum(readout[1:]) / 3 >= 0.8 * gap
    assert sum(readout[1:]) + sum(dispatch[:3]) == pytest.approx(3 * gap,
                                                                 rel=1e-3)
    idle = ph.idle_s()
    assert idle[spans.NO_PHASE] < 0.05 * (r.window_s - r.busy_s)
