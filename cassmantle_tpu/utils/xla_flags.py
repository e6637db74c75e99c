"""XLA_FLAGS management shared by every multi-device CPU entry point.

Must stay importable BEFORE jax (no jax imports here): XLA parses the env
var once at first backend initialization, so tests/conftest.py and
__graft_entry__.py both append these flags at module import time.
"""

from __future__ import annotations

import os

# On few-core hosts the virtual CPU devices' programs serialize, and XLA's
# default 40 s collective termination timeout kills the process while
# straggler devices are still computing. Harmless on real-TPU paths. The
# installed XLA (jaxlib 0.9.0) registers both flags; it aborts the
# process on a flag name it does not know, so a jaxlib change that drops
# them shows at the first backend init of any test run.
COLLECTIVE_TIMEOUT_FLAGS = (
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=300",
    "--xla_cpu_collective_call_terminate_timeout_seconds=3600",
)


def virtual_device_flag(count: int) -> str:
    return f"--xla_force_host_platform_device_count={count}"


def append_xla_flags(*flags: str) -> None:
    """Append each flag to XLA_FLAGS unless its name is already set."""
    current = os.environ.get("XLA_FLAGS", "")
    for flag in flags:
        name = flag.split("=")[0].lstrip("-")
        if name not in current:
            current = (current + " " + flag).strip()
    os.environ["XLA_FLAGS"] = current


def pin_cpu_platform(
    virtual_devices: bool = True, device_count: int = 8
) -> None:
    """Force jax onto host CPU devices.

    The one place the ordering rules live (used by tests/conftest.py,
    the CLI's ``--platform cpu``, and the dryrun):

    - XLA flags must land in the env before the first backend init;
    - the environment may name an accelerator in JAX_PLATFORMS, and jax
      may already be imported, so the env var alone is not enough:
      ``jax_platforms`` is also forced through the config API
      (``jax_platform_name`` only picks the *default* among the
      platforms that still get initialised).
    """
    flags = COLLECTIVE_TIMEOUT_FLAGS
    if virtual_devices:
        flags = (virtual_device_flag(device_count),) + flags
    append_xla_flags(*flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_platform_name", "cpu")
