"""The cluster of the RL test files whose tests leave nothing behind on it.

Module-scoped: each test of those files builds its own algorithm and stops
it, so one `ray_tpu.init` serves a whole file, as in test_rllib.py. Import
the fixture by name into the test module. A file whose tests leave actors
behind keeps a cluster per test (test_rllib_longtail_replay.py)."""
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()
