"""Mean device idle time between two consecutive decode-step executions of
one wave, in ms: what the engine's host loop (token readout, argmax
dispatch, admission) costs the device per step."""


def read(run):
    t = run.trace
    if t is None or not t.matched:
        return None
    xs = t.of_kind("decode")
    gaps = [t.idle_between(a.end, b.start) for a, b in zip(xs, xs[1:])
            if b.held == a.held + 1]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
