"""Public op: decode attention over a stacked, lane-dense K/V ring; the
Pallas kernel on a TPU, the plain oracle elsewhere (the model calls it)."""

from __future__ import annotations

import jax

from .. import interpret_mode
from .kernel import ring_decode_fwd
from .ref import ring_decode_ref


def ring_decode(q, ring_k, ring_v, index, valid, new_k=None, new_v=None,
                slots=None, *, scale: float, impl: str = "auto"):
    """q: (B, R, C) query rows, block-diagonal over the C = kv_heads *
    head_dim lanes; ring_k, ring_v: (N, B, T, C) stacked rings, of which
    ring ``index`` (int32 scalar) is read; valid: (R, T), nonzero where row
    r may attend slot t; new_k, new_v: (B, n, C) tokens that stand in for
    ring slots ``slots`` (int32[n]), not yet written there.  -> (B, R, C) in
    q's dtype: each row's softmax over its valid slots (float32 scores,
    times ``scale``) of the values.

    impl: ``auto`` is the kernel on a TPU and the oracle on any other
    backend; ``pallas`` | ``interpret`` force the kernel, ``ref`` the
    oracle."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return ring_decode_ref(q, ring_k, ring_v, index, valid, new_k, new_v,
                               slots, scale=scale)
    return ring_decode_fwd(q, ring_k, ring_v, index, valid, new_k, new_v,
                           slots, scale=scale,
                           interpret=interpret_mode(impl))
