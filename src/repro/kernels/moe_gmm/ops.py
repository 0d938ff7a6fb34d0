"""Public op: grouped expert matmul with kernel/reference dispatch."""

from __future__ import annotations

from .. import interpret_mode
from .kernel import gmm
from .ref import gmm_ref


def grouped_matmul(x, w, *, impl: str = "auto"):
    """impl: auto | pallas | interpret | ref."""
    if impl == "ref":
        return gmm_ref(x, w)
    return gmm(x, w, interpret=interpret_mode(impl))
