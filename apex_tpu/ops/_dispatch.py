"""Implementation dispatch for fused ops.

Every op in apex_tpu.ops has (a) a pure-jnp reference implementation that XLA
already fuses well, and (b) optionally a Pallas TPU kernel for the cases where
hand control of VMEM tiling wins. ``resolve_impl`` picks between them:

- ``"auto"``   : Pallas on a TPU backend, XLA elsewhere.
- ``"pallas"`` : force Pallas — compiled by Mosaic on a TPU, interpreted
                 off-TPU (tests exercise kernel code paths on the CPU mesh).
                 Nothing on a TPU ever resolves to ``interpret=True``.
- ``"xla"``    : force the jnp reference implementation.
"""

import functools

import jax


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    """Is the default backend a TPU? A backend that fails to initialise
    raises here: answering "not a TPU" would silently route every op to the
    XLA reference and hide the broken device behind working numbers."""
    return jax.devices()[0].platform == "tpu"


def resolve_impl(impl: str):
    """Returns (use_pallas: bool, interpret: bool)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "pallas":
        return True, not on_tpu()
    if impl == "xla":
        return False, False
    raise ValueError(f"unknown impl {impl!r}; expected auto|pallas|xla")
