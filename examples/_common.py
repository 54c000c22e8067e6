"""Shared example bootstrap.

Runs on the platform named by `platform=` / `JAX_PLATFORMS`, or else on
whatever JAX picks (a TPU slice runs the same example code unchanged). An
example that needs a mesh says how many devices (`min_devices`); finding
fewer is an error that names the platform — never a silent move to the CPU.
To run a mesh example without an accelerator, ask for the CPU explicitly
(`JAX_PLATFORMS=cpu`): the host platform is then split into virtual devices.
"""

import os


def setup(platform=None, min_devices=1):
    plat = platform or os.environ.get("JAX_PLATFORMS")
    # Only the CPU platform reads this flag (accelerator backends ignore it),
    # and it must be in the env before that backend initializes.
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={max(min_devices, 8)}"
        ).strip()
    import jax

    if plat is not None:
        jax.config.update("jax_platforms", plat)
    devices = jax.devices()
    if len(devices) < min_devices:
        raise SystemExit(
            f"this example needs {min_devices} devices; platform "
            f"{devices[0].platform!r} has {len(devices)}. Run it on a larger "
            f"slice, or on virtual CPU devices with JAX_PLATFORMS=cpu.")
    return jax
