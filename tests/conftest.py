"""Test fixtures.

Mirrors the reference's fixture strategy (ref: python/ray/tests/conftest.py:410
ray_start_regular; cluster fixtures building real multi-raylet clusters
in-process). JAX tests run on a virtual 8-device CPU mesh
(--xla_force_host_platform_device_count), the reference-recommended way to
exercise 256-chip sharding logic in CI.
"""
import os
import sys

# Tests run on the CPU: a virtual 8-device mesh. Set before jax is imported
# anywhere in the test process (workers inherit the environment).
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_resources={"CPU": 2.0})
    yield cluster
    cluster.shutdown()
