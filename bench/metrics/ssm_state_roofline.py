"""Share of its roofline the Mamba2 layers' decode state traffic reaches,
in %: the least time the chip could take to read each layer's float32 SSM
state and its conv state once and write them once (bytes over peak HBM
bandwidth, per decode execution; ``benchlib/ssm.py``), over the self time
of the decode ops that produce those states, summed over the traced call's
decode executions."""

from benchlib import ssm


def read(run):
    sec = ssm.self_seconds(run)
    if not sec:
        return None
    need = sum(run.needed_s(0.0, ssm.state_bytes(run, x))
               for x in run.trace.of_kind("decode"))
    return 100.0 * need / sec
