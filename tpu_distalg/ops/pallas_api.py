"""The one door to ``jax.experimental.pallas``: every kernel module
takes ``pl`` and ``pltpu`` from here, so that the package's first
import (1.2 to 1.4 s on the chip's host, most of it Mosaic's and the
GPU lowering's own imports) lies under one span, ``import:pallas``, and
shows in ``tda report``'s tree under whatever phase first needed a
kernel. Only the first import pays: a module's body runs once."""

from tpu_distalg.telemetry import events as _events

with _events.span("import:pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["pl", "pltpu"]
