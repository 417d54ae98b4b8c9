"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology (one chip of it), which refuses what interpret mode accepts —
misaligned blocks, VMEM overruns, unpartitionable kernels.  Nothing runs,
so these tests say nothing about results or speed.  The topology is
described inside a fixture (never at import), so every pytest worker
collects the same tests and only the one that runs this file loads the
TPU library.
"""
from functools import partial

import pytest
import jax
import jax.numpy as jnp

from repro.cloudsc import mini_cloudsc_program
from repro.core import Schedule, compile_jax, normalize
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm
from repro.kernels.rmsnorm import rmsnorm
from repro.polybench import BENCHMARKS

TILED = Schedule(mode="canonical", use_idioms=False, pallas_nest=True,
                 pallas_reduce=True, interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), s)
        for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_gemm_bf16_4096(one_chip):
    c = _compile(partial(gemm, interpret=False), one_chip,
                 _sds((4096, 4096)), _sds((4096, 4096)))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_danube_shapes(one_chip):
    # h2o-danube-3-4b: 32 query heads over 8 KV heads, head dim 120
    c = _compile(partial(flash_attention, interpret=False), one_chip,
                 _sds((32, 2048, 120)), _sds((8, 2048, 120)), _sds((8, 2048, 120)))
    assert "tpu_custom_call" in c.as_text()


def test_rmsnorm_4096x3840(one_chip):
    c = _compile(partial(rmsnorm, interpret=False), one_chip,
                 _sds((4096, 3840)), _sds((3840,)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tokens", [12, 8192])
def test_expert_layer_mellum2_held_experts(one_chip, tokens):
    # Mellum2: the 16 held of 64 experts, d_model 2304 -> 896, top-8; a
    # decode step of 12 slots and an 8192-token prefill: the grouped
    # products compile to the TPU's ragged-dot kernel
    from repro.configs import get_config
    from repro.models.layers import moe_ffn

    cfg = get_config("mellum2-12b-a2.5b")
    e, d, f = cfg.held_experts, cfg.d_model, cfg.d_ff
    p = {"router": _sds((d, cfg.n_experts), jnp.float32), "wg": _sds((e, d, f)),
         "wu": _sds((e, d, f)), "wd": _sds((e, f, d))}
    c = _compile(lambda x, p: moe_ffn(x, p, cfg), one_chip, _sds((tokens, d)), p)
    assert "ragged-dot" in c.as_text() and "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("name, prog", [
    ("gemm_b", lambda: BENCHMARKS["gemm"].make("b", "bench")),
    ("mini_cloudsc", lambda: mini_cloudsc_program(nproma=4096, klev=137)),
])
def test_tiled_nest_kernels(one_chip, name, prog):
    p = normalize(prog())
    fn = compile_jax(p, TILED)
    shapes = {a.name: _sds(a.shape, jnp.float32) for a in p.input_arrays}
    c = _compile(fn, one_chip, shapes)
    assert "tpu_custom_call" in c.as_text(), name
