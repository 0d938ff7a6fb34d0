"""Public op: RWKV6 WKV with kernel/reference dispatch."""

from __future__ import annotations

from .. import interpret_mode
from .kernel import wkv_fwd
from .ref import wkv_ref


def wkv(r, k, v, w, u, *, chunk: int = 16, impl: str = "auto"):
    """impl: auto | pallas | interpret | ref."""
    if impl == "ref":
        return wkv_ref(r, k, v, w, u, chunk=chunk)
    return wkv_fwd(r, k, v, w, u, chunk=chunk,
                   interpret=interpret_mode(impl))
