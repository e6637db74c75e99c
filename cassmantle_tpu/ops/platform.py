"""Which device the process runs on: the one place kernel dispatch and
host-side init ask.

Nothing here catches: a backend that fails to initialise raises out of
the caller. Swallowing that into "not on TPU" would send a chip process
down the Pallas interpreter or the XLA reference path and report its
timings as the device's.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default backend is a TPU (Pallas kernels compile
    through Mosaic); False selects interpret mode / the XLA paths."""
    return jax.default_backend() == "tpu"


def host_cpu_device():
    """The host CPU device used for big-model init and host-side
    quantization beside an accelerator. Needs the ``cpu`` platform to be
    initialised next to the default one: ``JAX_PLATFORMS=tpu`` alone
    hides it, ``tpu,cpu`` (or unset) keeps it."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            "host-side param init/quantization needs jax's cpu platform "
            "beside the accelerator, and this process has none: set "
            "JAX_PLATFORMS to include cpu (e.g. 'tpu,cpu') or leave it "
            f"unset (JAX_PLATFORMS={jax.config.jax_platforms!r})"
        ) from exc
