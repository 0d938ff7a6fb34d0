"""Share of the decode steps' device time the ops that produce the Mamba2
layers' states take, in %: their self time in the traced call's decode
executions (``benchlib/ssm.py``) over those executions' time."""

from benchlib import ssm


def read(run):
    sec = ssm.self_seconds(run)
    if sec is None:
        return None
    return 100.0 * sec / sum(x.seconds for x in run.trace.of_kind("decode"))
