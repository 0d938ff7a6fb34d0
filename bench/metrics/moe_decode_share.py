"""Share of the decode steps' device time the dropless MoE's grouped
matmul takes, in %: the self time of its ops in the traced call's decode
executions (``benchlib/moe_gmm.py``) over those executions' time."""

from benchlib import moe_gmm


def read(run):
    sec = moe_gmm.self_seconds(run, "decode")
    if sec is None:
        return None
    return 100.0 * sec / sum(x.seconds for x in run.trace.of_kind("decode"))
