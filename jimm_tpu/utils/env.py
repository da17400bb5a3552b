"""Backend selection for entry points.

JAX honours ``JAX_PLATFORMS`` and ``XLA_FLAGS`` by itself. What is left for
``configure_platform`` is the CLI's ``--platform`` / ``--host-devices`` flags
and the launcher's pass-through of the same two values to its children as
``JIMM_PLATFORM`` / ``JIMM_HOST_DEVICES`` (`jimm_tpu/launch.py`): both are
applied in-process *before* the first backend use — call it at the top of
every script entry point.
"""

from __future__ import annotations

import os


#: fields a caller set EXPLICITLY (argument, not env) in this process —
#: later env-fallback calls (e.g. initialize_distributed's bootstrap) must
#: not clobber them with JIMM_* values
_explicit: set[str] = set()


def configure_platform(platform: str | None = None,
                       host_devices: int | None = None) -> None:
    """Apply backend overrides from arguments, falling back to the
    ``JIMM_PLATFORM`` / ``JIMM_HOST_DEVICES`` env vars. Explicit arguments
    win over env for the rest of the process: a bare re-invocation never
    overrides what a caller set by hand."""
    # `is None` (not truthiness): an explicit empty/zero argument must be
    # able to override a JIMM_PLATFORM/JIMM_HOST_DEVICES env setting
    if platform is not None:
        _explicit.add("platform")
    if host_devices is not None:
        _explicit.add("host_devices")
    plat = os.environ.get("JIMM_PLATFORM") if platform is None else platform
    n = os.environ.get("JIMM_HOST_DEVICES") if host_devices is None else host_devices
    if platform is None and "platform" in _explicit:
        plat = None
    if host_devices is None and "host_devices" in _explicit:
        n = None
    if not plat and not n:
        return
    import jax
    if plat:
        jax.config.update("jax_platforms", plat)
    if n:
        jax.config.update("jax_num_cpu_devices", int(n))
