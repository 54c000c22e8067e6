"""Test harness: force an 8-device virtual CPU platform so all sharding /
multi-chip tests run without TPU hardware — the TPU-native equivalent of the
reference's Spark `local[N]` simulated clusters
(dl4j-spark BaseSparkTest.java:89)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Both the env vars and the config update below: a hosting environment may
# have pre-configured jax_platforms, and the tests must land on the CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

assert jax.devices()[0].platform == "cpu", f"tests must run on CPU, got {jax.devices()}"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
