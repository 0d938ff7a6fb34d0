"""Public op: flash attention with kernel/reference dispatch.

On a TPU the Pallas kernel runs natively; on a CPU the tests run it with
``impl="interpret"`` against ``ref.attention_ref`` (tests/test_kernels.py
sweeps shapes and dtypes).
"""

from __future__ import annotations

from .. import interpret_mode
from .kernel import flash_attention_fwd
from .ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    """impl: auto | pallas | interpret | ref."""
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret_mode(impl))
