"""Share of its roofline the dropless MoE's grouped matmul reaches, in %:
the least time the chip could take for the work its calls need (the
larger of FLOPs over peak FLOP/s and bytes over peak HBM bandwidth, per
execution; ``benchlib/moe_gmm.py``) over the self time of its ops (the
kernel's calls and the expert-bank copies before them), summed over the
traced call's prefill and decode executions."""

from benchlib import moe_gmm


def read(run):
    need = spent = 0.0
    for kind in ("prefill", "decode"):
        sec = moe_gmm.self_seconds(run, kind)
        if sec is None:
            return None
        spent += sec
        need += sum(run.needed_s(*moe_gmm.work(run, x, kind))
                    for x in run.trace.of_kind(kind))
    return 100.0 * need / spent if spent > 0 else None
