"""The benchmark's programs at their configured sizes, through
``Daisy.compile``, compile for a described v5e chip and fit its 16 GB."""
import pytest

from bench import harness

V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """gemm_b and jacobi-2d_b at the polybench-xl sizes, mini_cloudsc at the
    cloudsc-l137 sizes, each compiled once for the described chip."""
    import jax
    import jax.numpy as jnp

    picks = {"polybench-xl.b": ("gemm", "jacobi-2d"), "cloudsc-l137.step": ("mini_cloudsc",)}
    out = {}
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for cell_name, names in picks.items():
        config, traffic = cell_name.split(".")
        cell = harness.make_cell(spec, cell_name, config, f"bench/configs/{config}.json", traffic)
        cell.config["programs"] = {n: cell.config["programs"][n] for n in names}
        progs = harness.build_programs(cell.config, cell.traffic)
        fns, _, _ = harness.compile_programs(progs)
        for p, fn in zip(progs, fns):
            args = {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=one_chip)
                    for a in p.program.input_arrays}
            out[p.program.name] = fn.lower(args).compile()
    return out


@pytest.mark.parametrize("name", ["gemm_b", "jacobi2d_b", "mini_cloudsc"])
def test_compiles_for_v5e_and_fits(compiled, name):
    m = compiled[name].memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    assert 0 < used < V5E_HBM
