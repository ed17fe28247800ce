"""Test config: JAX on a virtual 8-device CPU mesh, so multi-chip
sharding paths compile and execute without a TPU, and the suite (and
every rank process a test spawns, which inherits the environment) never
loads the chip."""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running benches/soaks (tier-1 runs "
        "-m 'not slow')")
