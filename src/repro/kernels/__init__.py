"""Pallas TPU kernels, each as ``kernel.py`` + ``ops.py`` + ``ref.py``;
``moe_gmm`` has no ``kernel.py``: its op calls megablox's ``gmm``.

Every op takes ``impl``: ``pallas`` runs the kernel natively, ``interpret``
runs it in the Pallas interpreter (how the tests check it on a CPU), ``ref``
runs the plain oracle, and ``auto`` means ``pallas`` on a TPU and is an
error anywhere else, so no caller falls back to the interpreter unawares;
``moe_gmm``'s, ``moe_combine``'s and ``ring_decode``'s ``auto`` is ``ref``
off a TPU, because the model calls them.
"""

import jax


def interpret_mode(impl: str) -> bool:
    """The ``interpret`` flag of a ``pallas_call`` for ``impl``."""
    if impl == "auto":
        backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                f"impl='auto' runs Pallas kernels on a TPU only, and the "
                f"backend is {backend!r}; pass impl='interpret' or 'ref'")
        return False
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown kernel impl {impl!r}")
    return impl == "interpret"
