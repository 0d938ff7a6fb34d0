"""Share of its roofline the prefill reaches, in %: the least time the chip
could take for the work the prefills need (the larger of FLOPs over peak
FLOP/s and bytes over peak HBM bandwidth, per prefill) over their device
time, summed over the traced prefills."""


def read(run):
    t = run.trace
    if t is None or not t.matched:
        return None
    xs = t.of_kind("prefill")
    if not xs:
        return None
    need = sum(run.needed_s(*run.prefill_work(x)) for x in xs)
    return 100.0 * need / sum(x.seconds for x in xs)
