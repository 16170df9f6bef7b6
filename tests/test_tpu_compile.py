"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached: what it refuses here (a block not aligned to the
(8, 128) tiling, a load from an unplaced memory space, a program larger
than HBM) it would refuse on the chip. Cases, at the paper's width:

  * the six ONU kernels over one ONU (C=20 clients × N=6,603,710 params,
    the full LEAF CNN), each lowering to a Mosaic ``tpu_custom_call``;
  * one ``ClientStackedBackend`` chunk step at full CNN width: the vmapped
    local update over ``client_chunk=16`` clients, whose H-step loop holds
    no per-client copy of the dense weight, then the ``sfl_two_step``
    aggregate over 128 stacked rows.

Nothing runs, so this says nothing about results or times. The topology is
described inside a fixture (never at import): only one process at a time
may load the TPU library, and each test worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs, fl
from repro.core import fedavg
from repro.core.fedavg import FLConfig
from repro.kernels import agg_reduce as agg_k
from repro.kernels import quantize as quant_k
from repro.models import femnist_cnn

C, N = 20, 6_603_710          # one ONU's clients × the full CNN
HBM_BYTES = 16 * 2**30        # one v5e chip
KERNELS = ("agg_reduce", "agg_reduce_quant", "quantize_int8",
           "quantize_int4", "dequantize_int8", "topk_sparsify")


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
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
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"
    return used


def _while_bodies(text: str) -> str:
    """The HLO text of every ``while`` body in a compiled module, with the
    computations (fusions, reductions) they call."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    comps = {k: "\n".join(v) for k, v in comps.items()}
    todo = [b for c in comps.values() for b in re.findall(r"body=%([\w.\-]+)", c)]
    seen = set()
    while todo:
        n = todo.pop()
        if n not in seen:
            seen.add(n)
            todo += [c for c in re.findall(r"%([\w.\-]+)", comps[n]) if c in comps]
    return "\n".join(comps[n] for n in sorted(seen))


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

    program = fedavg.chunk_program(femnist_cnn.loss_fn, flc,
                                   strategy.local_update)
    train = program.lower(place(params), batches).compile()
    _fits(train)
    # the dense weight rides the H-step loop as the global weight plus the
    # steps' rows: no per-client copy of it is carried or rewritten there
    assert program.factored_params == 3136 * 2048
    loops = _while_bodies(train.as_text())
    assert loops and f"f32[{chunk},3136,2048]" not in loops

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


def test_olmo_1b_fsdp_step_fits_four_chips(v5e_2x2):
    """OLMo-1B at its published widths, the gradient regime's step as
    ``GradientBackend`` builds it: FSDP rules on a (4, 1) ("data",
    "model") mesh, AdamW, 32 rows of 2048 tokens. Each chip's share fits
    its 16 GiB (12.85 GiB), and the step gathers and reduces over the
    mesh."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch import specs
    from repro.launch.train import build_rules
    from repro.models.config import ShapeConfig

    cfg = configs.get("olmo_1b")
    mesh = Mesh(np.array(v5e_2x2).reshape(4, 1), ("data", "model"))
    rules = build_rules(mesh, "sfl")
    shape = ShapeConfig("olmo1b_sfl_fsdp4", 2048, 32, "train")
    fn, args, _ = specs.input_specs(cfg, shape, mesh, rules)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert _fits(compiled) > 8 * 2**30
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce-scatter" in text

