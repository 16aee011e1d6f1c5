"""What the files that compile for a DESCRIBED TPU v5e share
(``test_chip_compile.py``: the kernels and the smoke programs, tier-1;
``test_cell_step_compile.py``: a cell's whole train step, ``slow``): the
described chip, the one switch that puts every kernel on its compiled
path, and the tool that compiles a cell's step. Each file imports the
fixtures by name; this is no ``conftest.py``, so no other test asks for a
topology (on-chip-measurement guide, section 2).
"""
import importlib
import importlib.util
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 15.75e9  # what the v5e compiler reports as its HBM capacity


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Every kernel file decides interpret mode by
    ``kernel_common.use_interpret``, which reads jax.default_backend(), the
    CPU here: steer them all to the compiled path for these tests."""
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.kernel_common"),
                        "use_interpret", lambda: False)


def fits(compiled) -> float:
    m = compiled.memory_analysis()
    resident = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes)
    assert resident < HBM_BYTES
    return resident


def hlo_tool():
    """scripts/train_step_hlo.py as a module."""
    spec = importlib.util.spec_from_file_location(
        "train_step_hlo", os.path.join(os.path.dirname(__file__), "..",
                                       "scripts", "train_step_hlo.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool
