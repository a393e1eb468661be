"""Test environment: 8 virtual CPU devices (the JAX-native 'fake backend' the
reference lacks — SURVEY.md §4). Must run before jax initializes."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["HF_HUB_OFFLINE"] = "1"
os.environ["TRANSFORMERS_OFFLINE"] = "1"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# XLA CPU bug: the AllReducePromotion pass check-fails ("Invalid binary
# instruction opcode copy") cloning the bf16 expert-axis all-reduces the
# pipe x EP backward emits. CPU-only pass, CPU-only workaround — the TPU
# pipeline never runs it.
if "xla_disable_hlo_passes" not in flags:
    flags = (flags + " --xla_disable_hlo_passes=all-reduce-promotion").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

# The suite runs on the CPU wherever it is started, a machine with a chip
# included: the config update holds even if jax was imported before this file.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2 for deviceless compiles. Only one
    process may load libtpu unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1
    command sets it: six workers ask): described inside this fixture, never at
    import, in a ``skipif`` or in ``parametrize`` arguments, and every compile
    happens in the test's own process."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless executable can be written to the persistent cache but not
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])
