"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached: what it refuses here (a block not aligned to the
(8, 128) tiling, a load from an unplaced memory space, a program larger
than HBM) it would refuse on the chip. Cases, at the paper's width:

  * the six ONU kernels over one ONU (C=20 clients × N=6,603,710 params,
    the full LEAF CNN), each lowering to a Mosaic ``tpu_custom_call``;
  * one ``ClientStackedBackend`` chunk step at full CNN width: the vmapped
    local update over ``client_chunk=16`` clients, then the
    ``sfl_two_step`` aggregate over 128 stacked rows.

Nothing runs, so this says nothing about results or times. The topology is
described inside a fixture (never at import): only one process at a time
may load the TPU library, and each test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro import configs, fl
from repro.core.fedavg import FLConfig
from repro.kernels import agg_reduce as agg_k
from repro.kernels import quantize as quant_k
from repro.models import femnist_cnn

C, N = 20, 6_603_710          # one ONU's clients × the full CNN
HBM_BYTES = 16 * 2**30        # one v5e chip
KERNELS = ("agg_reduce", "agg_reduce_quant", "quantize_int8",
           "quantize_int4", "dequantize_int8", "topk_sparsify")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"
    return used


def _kernel_case(name, sd):
    key = sd((2,), jnp.uint32)
    stack = (sd((C, N), jnp.float32), sd((C,), jnp.float32),
             sd((C,), jnp.float32))
    row = sd((N,), jnp.float32)
    return {
        "agg_reduce": (agg_k.agg_reduce, stack),
        "agg_reduce_quant": (agg_k.agg_reduce_quant, stack + (key,)),
        "quantize_int8": (quant_k.quantize_int8, (row, key)),
        "quantize_int4": (quant_k.quantize_int4, (row, key)),
        "dequantize_int8": (quant_k.dequantize_int8,
                            (sd((N,), jnp.int8), sd((), jnp.float32))),
        "topk_sparsify": (lambda x: quant_k.topk_sparsify(x, N // 100),
                          (row,)),
    }[name]


@pytest.mark.parametrize("name", KERNELS)
def test_onu_kernel_compiles_for_v5e(one_chip, name):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = _kernel_case(name, sd)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_client_stacked_chunk_step_compiles_for_v5e(one_chip):
    cfg = configs.get("femnist_cnn")
    flc = FLConfig(n_selected=128, local_steps=8, local_lr=0.06)
    strategy = fl.make_strategy("sfl_two_step")
    params = jax.eval_shape(
        lambda: femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))[0])
    assert sum(x.size for x in jax.tree.leaves(params)) == N
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    chunk, H, B = flc.client_chunk, flc.local_steps, flc.local_batch
    batches = place({
        "images": jax.ShapeDtypeStruct((chunk, H, B, 28, 28, 1), jnp.float32),
        "labels": jax.ShapeDtypeStruct((chunk, H, B), jnp.int32)})

    def local_chunk(p, cb):
        return jax.vmap(lambda b: strategy.local_update(
            p, b, femnist_cnn.loss_fn, flc))(cb)

    train = jax.jit(local_chunk).lower(place(params), batches).compile()
    _fits(train)

    rows = flc.n_selected
    deltas = place(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((rows,) + x.shape, jnp.float32),
        params))
    vec = lambda dt: jax.ShapeDtypeStruct((rows,), dt, sharding=one_chip)
    aggregate = jax.jit(lambda d, w, m, o: strategy.aggregate(
        d, w, m, o, flc.total_onus))
    compiled = aggregate.lower(deltas, vec(jnp.float32), vec(jnp.float32),
                               vec(jnp.int32)).compile()
    # 128 stacked f32 deltas of 26.4 MB each are the program's argument
    assert _fits(compiled) >= rows * N * 4
