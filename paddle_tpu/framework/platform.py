"""Host-platform pinning for CPU-mesh runs.

Tests, the multi-process launcher's single-host harness and the driver
dryrun need N virtual CPU devices instead of whatever accelerator the host
has. `pin_host_platform` sets JAX_PLATFORMS=cpu and the host device count,
then checks that the backend that came up is the one asked for.

Must be called in a process that has NOT yet initialized a jax backend
(backend platform and XLA_FLAGS are frozen at first device use).
"""
from __future__ import annotations

import os
import re


def with_host_device_count(flags: str, n_devices: int) -> str:
    """Return `flags` with --xla_force_host_platform_device_count set to
    exactly `n_devices`, replacing any existing value."""
    want = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        return re.sub(r"--xla_force_host_platform_device_count=\d+",
                      want, flags)
    return (flags + " " + want).strip()


def pin_host_platform(n_devices: int = 8, verify: bool = True):
    """Force jax onto the host (CPU) platform with `n_devices` virtual
    devices. Returns the imported jax module. Raises RuntimeError if the
    platform config can no longer be changed (backend already initialized —
    run in a fresh process).

    `verify=False` skips the devices() check — REQUIRED when the caller
    will run jax.distributed.initialize next (a multi-process rank), which
    must happen before anything initializes the XLA backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = with_host_device_count(
        os.environ.get("XLA_FLAGS", ""), n_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")
    if not verify:
        return jax
    # config.update is a silent no-op once a backend is up, so verify: a
    # caller that believes it runs on the CPU must not run on the chip
    devs = jax.devices()
    if any(d.platform != "cpu" for d in devs) or len(devs) < n_devices:
        raise RuntimeError(
            f"pin_host_platform: wanted {n_devices} cpu devices but the "
            f"backend has {devs}; it must run before any jax backend "
            f"initializes — start a fresh process")
    return jax
