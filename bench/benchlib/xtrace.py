"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads JAX's ``.xplane.pb`` into plain events; ``reduce`` turns the
events of one traced window, with the harness's log of the engine calls it
made, into a ``Reduced`` record:

* the window: the host span ``bench.window`` around the traced call;
* program executions: on a TPU the device's "XLA Modules" line, one event
  per execution, named after the program and its fingerprint; on a backend
  without device planes (the CPU), op events grouped by program and run;
* device busy time: the union of the executions inside the window,
  averaged over the devices that ran any;
* which program is the prefill and which the decode step: the harness wraps
  the engine's two jitted steps in host spans ``bench.prefill`` and
  ``bench.decode`` and logs each call; a program is the decode step when
  it ran as often as the log says the step was called (see ``_classify``);
* those executions matched, in order, with the calls the harness logged
  (their batch and, for decode, how many positions the lanes held);
* idle gaps on the device inside the window, each named by the innermost
  host span over its middle, on the thread that opened the window;
* the device ops by self time (their time less that of the ops inside
  them), each named by its instruction, result shape and opcode and by the
  kind of program it ran in.
"""

from __future__ import annotations

import bisect
import json
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

SPAN_WINDOW = "bench.window"
SPAN_PREFILL = "bench.prefill"
SPAN_DECODE = "bench.decode"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
KINDS = {SPAN_PREFILL: "prefill", SPAN_DECODE: "decode"}


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start: int                      # ns
    end: int                        # ns
    program: Optional[str] = None   # executions, and ops on the CPU
    run: Optional[int] = None

    def to_list(self):
        return [self.plane, self.line, self.name, self.start, self.end,
                self.program, self.run]


def load(path) -> List[Event]:
    """The events ``reduce`` reads: the device's modules and ops, the host
    thread that holds a ``bench.`` span, and, where no device plane exists,
    the op events the CPU backend puts on its threads."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            evs = list(line.events)
            ours = device or any(e.name.startswith("bench.") for e in evs)
            for e in evs:
                start, dur = int(e.start_ns), int(e.duration_ns)
                if device and line.name == MODULES_LINE:
                    stats = dict(e.stats)
                    events.append(Event(plane.name, line.name, e.name, start,
                                        start + dur, e.name,
                                        stats.get("run_id")))
                elif ours:
                    if dur > 0 or e.name.startswith("bench."):
                        events.append(Event(plane.name, line.name, e.name,
                                            start, start + dur))
                elif "hlo_op" in (stats := dict(e.stats)):
                    events.append(Event(plane.name, line.name, e.name, start,
                                        start + dur,
                                        f"{stats.get('hlo_module')}"
                                        f"({stats.get('program_id')})",
                                        stats.get("run_id")))
    return events


def save_json(events: List[Event], calls: list, path) -> None:
    """Events and the call log as plain JSON (a trace kept for tests)."""
    with open(path, "w") as f:
        f.write('{"calls": %s,\n "events": [\n' % json.dumps(calls))
        f.write(",\n".join(json.dumps(e.to_list()) for e in events))
        f.write("\n]}\n")


def load_json(path):
    with open(path) as f:
        d = json.load(f)
    return [Event(*e) for e in d["events"]], [tuple(c) for c in d["calls"]]


# ---------------------------------------------------------------------------


@dataclass
class Execution:
    program: str
    start: int
    end: int
    kind: str = "other"              # prefill | decode | other
    batch: int = 0
    held: int = 0                    # decode: positions held before it
    seq: int = 0                     # prefill: prompt length

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    executions: List[Execution]
    idle_gaps: List[tuple]           # (start, end, host span name)
    device_ops: List[tuple]          # (op, self seconds), most time first
    matched: bool                    # executions matched the call log
    _busy: List[tuple] = field(default_factory=list, repr=False)

    def of_kind(self, kind: str) -> List[Execution]:
        return [x for x in self.executions if x.kind == kind]

    def idle_between(self, t0: int, t1: int) -> float:
        """Seconds of [t0, t1] with nothing running on the device."""
        return (max(t1 - t0, 0) - _overlap(self._busy, t0, t1)) / 1e9


def _union(intervals) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _overlap(union: List[tuple], t0: int, t1: int) -> int:
    i = max(bisect.bisect_right(union, (t0,)) - 1, 0)
    tot = 0
    while i < len(union) and union[i][0] < t1:
        s, e = union[i]
        tot += max(0, min(e, t1) - max(s, t0))
        i += 1
    return tot


def _executions(events: List[Event]) -> Dict[str, List[Execution]]:
    """Executions per plane, in time order."""
    modules = [e for e in events if e.line == MODULES_LINE]
    if modules:
        per = defaultdict(list)
        for e in modules:
            per[e.plane].append(Execution(e.program, e.start, e.end))
    else:
        spans: Dict[tuple, list] = {}
        for e in events:
            if e.program is None:
                continue
            k = (e.plane, e.program, e.run)
            if k in spans:
                spans[k][0] = min(spans[k][0], e.start)
                spans[k][1] = max(spans[k][1], e.end)
            else:
                spans[k] = [e.start, e.end]
        per = defaultdict(list)
        for (plane, prog, _), (s, e) in spans.items():
            per["cpu"].append(Execution(prog, s, e))
    return {p: sorted(xs, key=lambda x: x.start) for p, xs in per.items()}


def reduce(events: List[Event], calls: list) -> Optional[Reduced]:
    """``calls``: the harness's log, in order, of ("prefill", batch, seq)
    and ("decode", batch, held) tuples.  None when the trace holds no
    ``bench.window`` span or no execution inside it."""
    windows = [e for e in events if e.name == SPAN_WINDOW]
    if not windows:
        return None
    w = windows[0]
    w0, w1 = w.start, w.end
    per_plane = {p: [x for x in xs if x.end > w0 and x.start < w1]
                 for p, xs in _executions(events).items()}
    per_plane = {p: xs for p, xs in per_plane.items() if xs}
    if not per_plane:
        return None
    unions = {p: _union((max(x.start, w0), min(x.end, w1)) for x in xs)
              for p, xs in per_plane.items()}
    busy_s = sum(sum(e - s for s, e in u) for u in unions.values()) \
        / 1e9 / len(unions)
    plane = sorted(per_plane)[0]
    execs = per_plane[plane]
    _classify(execs, events, calls)
    matched = _match(execs, calls)

    timeline = _timeline([e for e in events if e.plane == w.plane
                          and e.line == w.line and e is not w
                          and e.end > w0 and e.start < w1])
    gaps = []
    prev = w0
    for s, e in unions[plane] + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s, _host_at(timeline, (prev + s) // 2)))
        prev = max(prev, e)
    ops = [e for e in events if e.line == OPS_LINE and e.plane == plane
           and e.end > w0 and e.start < w1]
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy_s,
                   devices=len(unions), executions=execs, idle_gaps=gaps,
                   device_ops=_self_times(ops, execs), matched=matched,
                   _busy=unions[plane])


def _classify(execs: List[Execution], events: List[Event],
              calls: list) -> None:
    """A program is the decode (prefill) program when it ran as many times
    as the harness called the engine's decode (prefill) step; of several
    such, the one most executions of which start after that kind of host
    span, then the one with most device time.  Counts, not times, decide,
    since the device's clock and the host's agree only to about a
    millisecond."""
    marks = sorted((e.start, KINDS[e.name]) for e in events if e.name in KINDS)
    starts = [m[0] for m in marks]
    runs: Counter = Counter()
    busy: Counter = Counter()
    votes: Dict[str, Counter] = defaultdict(Counter)
    for x in execs:
        runs[x.program] += 1
        busy[x.program] += x.end - x.start
        i = bisect.bisect_right(starts, x.start) - 1
        votes[x.program][marks[i][1] if i >= 0 else None] += 1
    wanted = Counter(c[0] for c in calls)
    kind: Dict[str, str] = {}
    for k in sorted(wanted, key=lambda k: -wanted[k]):
        cands = [p for p in runs if runs[p] == wanted[k] and p not in kind]
        if cands:
            kind[max(cands, key=lambda p: (votes[p][k], busy[p]))] = k
    for x in execs:
        x.kind = kind.get(x.program, "other")


def _match(execs: List[Execution], calls: list) -> bool:
    ok = True
    for k in KINDS.values():
        xs = [x for x in execs if x.kind == k]
        cs = [c for c in calls if c[0] == k]
        if len(xs) != len(cs):
            ok = False
            continue
        for x, (_, batch, n) in zip(xs, cs):
            x.batch = batch
            if k == "prefill":
                x.seq = n
            else:
                x.held = n
    return ok


_OP = re.compile(r"%?([^\s=]+) = \(?([a-z0-9]+\[[\d,]*\])?.*?\s([a-z][\w\-]*)\(")


def _op_label(text: str) -> str:
    """"%copy.9 = bf16[28,16,1156,8,128]{...} copy(...)" ->
    "copy.9 bf16[28,16,1156,8,128] copy"."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    return " ".join(g for g in m.groups() if g)


def _self_times(ops: List[Event], execs: List[Execution]) -> List[tuple]:
    """(kind:op, seconds of the op less its nested ops), most first."""
    starts = [x.start for x in execs]
    out: Counter = Counter()
    stack: List[list] = []          # [event, child time]

    def close(top):
        ev, child = top
        i = bisect.bisect_right(starts, ev.start) - 1
        kind = execs[i].kind if i >= 0 and execs[i].end >= ev.end else "other"
        out[f"{kind}:{_op_label(ev.name)}"] += (ev.end - ev.start - child) / 1e9

    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            top = stack.pop()
            close(top)
        if stack:
            stack[-1][1] += e.end - e.start
        stack.append([e, 0])
    while stack:
        close(stack.pop())
    return out.most_common()


def _timeline(spans: List[Event]) -> tuple:
    """The innermost span of one host thread at each moment, as sorted
    segment starts and names (spans of one thread nest)."""
    starts: List[int] = []
    names: List[Optional[str]] = []

    def mark(t, name):
        if starts and starts[-1] == t:
            names[-1] = name
        else:
            starts.append(t)
            names.append(name)

    # at one time, ends before starts, and an enclosing span first
    points = sorted([(e.start, 1, -e.end, i) for i, e in enumerate(spans)]
                    + [(e.end, 0, 0, i) for i, e in enumerate(spans)])
    stack: List[int] = []
    open_: set = set()
    for t, is_start, _, i in points:
        if is_start:
            stack.append(i)
            open_.add(i)
        elif i in open_:
            while stack:
                j = stack.pop()
                open_.discard(j)
                if j == i:
                    break
        mark(t, spans[stack[-1]].name if stack else None)
    return starts, names


def _host_at(timeline: tuple, t: int) -> str:
    starts, names = timeline
    i = bisect.bisect_right(starts, t) - 1
    name = names[i] if i >= 0 else None
    return name if name is not None else "(no host span)"
