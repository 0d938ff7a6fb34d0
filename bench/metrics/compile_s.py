"""Seconds JAX spent tracing, lowering, compiling and fetching cached
programs during set-up (its monitoring events), the part of ``setup_s``
that compilation takes."""


def read(run):
    return run.setup_compile_s
