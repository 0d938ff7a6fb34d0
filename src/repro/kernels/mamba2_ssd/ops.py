"""Public op: chunked SSD with kernel/reference dispatch."""

from __future__ import annotations

from .. import interpret_mode
from .kernel import ssd_fwd
from .ref import ssd_ref


def ssd(xdt, a, Bm, Cm, *, chunk: int = 128, impl: str = "auto"):
    """impl: auto | pallas | interpret | ref."""
    if impl == "ref":
        return ssd_ref(xdt, a, Bm, Cm, chunk=chunk)
    return ssd_fwd(xdt, a, Bm, Cm, chunk=chunk,
                   interpret=interpret_mode(impl))
