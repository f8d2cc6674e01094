"""The one platform probe.

Every dispatch decision that depends on the backend — Mosaic kernel vs
Pallas interpreter, flash vs XLA attention, paged-kernel vs gather decode
— asks here. There is deliberately no ``try``: a backend that fails to
initialise raises at the call site instead of quietly selecting the
interpreter or the XLA reference path and reporting success.
"""

import jax

__all__ = ["on_tpu"]


def on_tpu() -> bool:
    """True when the default backend's first device is a TPU."""
    return jax.devices()[0].platform == "tpu"
