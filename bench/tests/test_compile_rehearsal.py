"""Compile rehearsal of the Brunel cells' chunk program for a TPU v5e that
is described, not attached: the plastic ``unfused`` step on the ``ref``
backend at N = 12,500 (15 delay buckets of 12,504 x 128 slots), a scan of
20 steps with the raster recorded, as ``bal_stdp_k1`` runs it.  Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library.
"""
import jax
import pytest
from jax.sharding import SingleDeviceSharding

import bench_helpers  # noqa: F401  (puts the repository on the path)

HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_plastic_chunk_program_fits_one_chip(one_chip):
    from bench import harness, spec
    from repro.snn import Session, SimConfig

    cell = spec.load_cell("bal_stdp_k1")
    ses = Session(harness.build_spec(cell.config), SimConfig(record_raster=True))
    sim = ses._current_engine.sim
    assert sim.engine_choice.engine == "unfused" and sim.backend == "ref"

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    dev = jax.tree.map(shape, sim.dev)
    state = jax.tree.map(shape, sim.init_state(0))
    ids = shape(sim._noise_ids)
    steps = cell.traffic["chunk_steps"]
    compiled = type(sim)._run.lower(sim, dev, ids, [], state, steps=steps).compile()
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    print(f"arguments {ma.argument_size_in_bytes} B, outputs "
          f"{ma.output_size_in_bytes} B, temporaries {ma.temp_size_in_bytes} B")
    assert total < HBM_BYTES
