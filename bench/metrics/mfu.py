"""The whole step's share of the chip's peak FLOP/s over the traced window,
in %: the FLOPs that all the window's prefills and decode steps need,
over the window's seconds times the peak."""


def read(run):
    t = run.trace
    if t is None or not t.matched:
        return None
    flops = sum(run.prefill_work(x)[0] for x in t.of_kind("prefill"))
    flops += sum(run.decode_work(x)[0] for x in t.of_kind("decode"))
    if flops <= 0:
        return None
    return 100.0 * flops / (t.window_s * run.peak["bf16_flop_s"])
