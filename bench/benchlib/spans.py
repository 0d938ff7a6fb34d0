"""The serving engine's own host spans, moved onto the device trace's clock,
and the device idle inside each of its phases.

``JaxServeEngine.generate`` marks its host loop with profiler spans
(``repro.serving.engine.SPAN_*``): ``serve.generate`` per call;
``serve.admit``, ``serve.prefill`` and ``serve.release`` per wave;
``serve.readout`` (the lanes' token reads) and ``serve.dispatch`` (the
decode step and its argmax) per decode step.  ``phases(events, reduced)``
takes them from the thread that opened the traced window (``xtrace.load``
keeps that thread) and estimates how far the device's timestamps run ahead
of the host's, from pairs whose order is certain:

* lower bound: a program starts no earlier than the span that enqueued it.
  The ``serve.dispatch`` spans and the ``jit_serve_decode`` executions,
  taken in order, pair up, as do ``serve.prefill`` and
  ``jit_serve_prefill``; each pair bounds the lead from below by span start
  less execution start, and the largest counts;
* upper bound: the first blocking read inside a ``serve.readout`` span
  returns no earlier than the end of the step program before it, since the
  token it reads comes from that program; each bounds the lead from above
  by read end less execution end, and the smallest counts.

The shift applied is the least that breaks neither bound: the lower bound
where it is positive, the upper where it is negative, else none.  Only the
spans returned here move; the ``Reduced`` record and what the per-layer
readers take from it keep the clocks they had.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, replace
from typing import Dict, List, Optional

from repro.serving.engine import (SPAN_ADMIT, SPAN_DISPATCH, SPAN_GENERATE,
                                  SPAN_PREFILL, SPAN_READOUT, SPAN_RELEASE)

from . import xtrace

SPANS = (SPAN_GENERATE, SPAN_ADMIT, SPAN_PREFILL, SPAN_READOUT, SPAN_DISPATCH,
         SPAN_RELEASE)
PHASES = SPANS[1:]                    # disjoint, inside serve.generate
NO_PHASE = "(no serve phase)"
# the step programs the engine jits, as the device's trace names them
PROGRAMS = {SPAN_PREFILL: "jit_serve_prefill",
            SPAN_DISPATCH: "jit_serve_decode"}
# JAX's host span around a device-to-host copy of an array
BLOCKING_READ = "np.asarray(jax.Array)"


@dataclass
class Phases:
    spans: List[xtrace.Event]         # the window thread's serve.* spans
    window: tuple                     # (start, end), moved like the spans
    lower_ns: Optional[int]           # bounds of the device clock's lead
    upper_ns: Optional[int]
    shift_ns: int                     # subtracted from every host time
    trace: xtrace.Reduced

    def idle_in(self, name: str) -> List[float]:
        """Seconds of device idle inside each span called ``name``."""
        return [self.trace.idle_between(s.start, s.end)
                for s in self.spans if s.name == name]

    def idle_s(self) -> Dict[str, float]:
        """Device idle seconds under each serve.* span name, and under none
        of the phases (admit, prefill, readout, dispatch, release)."""
        out = {n: sum(self.idle_in(n)) for n in SPANS}
        out[NO_PHASE] = (self.trace.idle_between(*self.window)
                         - sum(out[n] for n in PHASES))
        return out

    def late_starts(self) -> int:
        """Step executions that start before the span that enqueued them,
        after the shift: 0 when the bracket is sound."""
        return sum(x.start < s.start
                   for s, x in _step_pairs(self.spans, self.trace))

    def line(self) -> str:
        """The operator's view: idle per phase, and the clock bracket."""
        def ms(ns):
            return "none" if ns is None else f"{ns / 1e6:.3f}"

        idle = " ".join(f"{n}={v:.4f}" for n, v in self.idle_s().items())
        return (f"trace: idle_s {idle} clock_lead_ms lower={ms(self.lower_ns)}"
                f" upper={ms(self.upper_ns)} shift={ms(self.shift_ns)}")


def _program(x: xtrace.Execution) -> str:
    return x.program.split("(", 1)[0]


def _step_pairs(spans, trace) -> list:
    """(span, execution) of the step programs, paired in order for each
    kind whose span and execution counts agree, in time order."""
    pairs = []
    for name, prog in PROGRAMS.items():
        ss = [s for s in spans if s.name == name]
        xs = [x for x in trace.executions if _program(x) == prog]
        if ss and len(ss) == len(xs):
            pairs.extend(zip(ss, xs))
    return sorted(pairs, key=lambda p: p[0].start)


def phases(events: List[xtrace.Event],
           trace: Optional[xtrace.Reduced]) -> Optional[Phases]:
    """None without a reduced window or without serve.* spans inside it."""
    windows = [e for e in events if e.name == xtrace.SPAN_WINDOW]
    if trace is None or not windows:
        return None
    w = windows[0]
    thread = [e for e in events if e.plane == w.plane and e.line == w.line
              and e is not w and w.start <= e.start and e.end <= w.end]
    spans = sorted((e for e in thread if e.name in SPANS),
                   key=lambda e: e.start)
    if not spans:
        return None
    pairs = _step_pairs(spans, trace)
    lower = max((s.start - x.start for s, x in pairs), default=None)

    reads = sorted((e.start, e.end) for e in thread
                   if e.name == BLOCKING_READ)
    starts = [s.start for s, _ in pairs]
    upper = None
    for r in (s for s in spans if s.name == SPAN_READOUT):
        i = bisect.bisect_right(starts, r.start) - 1
        j = bisect.bisect_left(reads, (r.start,))
        if i >= 0 and j < len(reads) and reads[j][1] <= r.end:
            lead = reads[j][1] - pairs[i][1].end
            upper = lead if upper is None else min(upper, lead)

    if lower is not None and lower > 0:
        shift = lower
    elif upper is not None and upper < 0:
        shift = upper
    else:
        shift = 0
    moved = [replace(s, start=s.start - shift, end=s.end - shift)
             for s in spans]
    return Phases(moved, (w.start - shift, w.end - shift), lower, upper,
                  shift, trace)
