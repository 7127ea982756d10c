"""Pin this process to the CPU platform (dry runs, drills, CPU-side tools).

There is no accelerator probe here: a process either owns the chip or fails
at its first use of it. One process may hold a chip at a time, so a probe in
a child would itself be a second holder.
"""
from __future__ import annotations

import os
import re

_DEVCOUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def force_cpu_platform(virtual_devices: int | None = None) -> None:
    """Run on the CPU platform, with at least `virtual_devices` host devices.

    Must run before the jax backend initializes. Importing this module has
    already imported jax (the package does), and jax reads JAX_PLATFORMS only
    at its own import — so the platform goes through jax.config; the
    environment is set as well for child processes."""
    if virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        m = _DEVCOUNT_RE.search(flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={virtual_devices}"
            ).strip()
        elif int(m.group(1)) < virtual_devices:
            os.environ["XLA_FLAGS"] = _DEVCOUNT_RE.sub(
                f"--xla_force_host_platform_device_count={virtual_devices}",
                flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
