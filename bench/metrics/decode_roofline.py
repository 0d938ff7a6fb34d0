"""Share of its roofline the decode step reaches, in %: the least time the
chip could take for the work the steps need (the larger of FLOPs over peak
FLOP/s and bytes over peak HBM bandwidth, per step) over the steps' device
time, summed over the traced decode steps."""


def read(run):
    t = run.trace
    if t is None or not t.matched:
        return None
    xs = t.of_kind("decode")
    if not xs:
        return None
    need = sum(run.needed_s(*run.decode_work(x)) for x in xs)
    return 100.0 * need / sum(x.seconds for x in xs)
