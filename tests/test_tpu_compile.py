"""Ahead-of-time compiles of the main-path kernels for a TPU v5e chip.

Nothing runs: each test lowers a kernel or a step program at its real
width for one chip of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what the chip's compiler would refuse
(block tiling, Mosaic layouts, scoped memory).  The topology is described
inside a module fixture, so collecting this file never loads the TPU
library; where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lm_small import LM100M
from repro.configs.vgg import VGG5
from repro.fl.flatbuf import ServerStep
from repro.kernels import compat
from repro.kernels.quant_transfer.ops import dequantize, quantize
from repro.kernels.topk_compress.ops import density_block_meta
from repro.kernels.topk_compress.topk_compress import topk_compress_pallas
from repro.models.split_program import get_split_program

CONFIGS = {"vgg5": VGG5, "lm100m": LM100M}
BLOCK = 1024
# the cut activation chip_smoke.py trains lm100m with: batch 8 x seq 128
LM_CUT = (8, 128, LM100M.d_model)
VGG_OP1_CUT = (100, 16, 16, 32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache here: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _leaf_sizes(cfg):
    shapes = jax.eval_shape(get_split_program(cfg).init,
                            jax.random.PRNGKey(0))
    return [int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes)]


def _compile(fn, *args, **kw):
    return jax.jit(fn).lower(*args, **kw).compile()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_topk_kernel_compiles_at_flat_buffer_width(name, one_chip):
    meta = np.concatenate([density_block_meta(sz, BLOCK, 0.01)
                           for sz in _leaf_sizes(CONFIGS[name])])
    nb, kmax = len(meta), int(meta[:, 1].max())
    x = jax.ShapeDtypeStruct((nb * BLOCK,), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((nb, 2), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda v, mm: topk_compress_pallas(
        v, mm, kmax, BLOCK, interpret=False), x, m)
    assert "tpu_custom_call" in compiled.as_text()


def _server_rows(name):
    return (sum(-(-sz // BLOCK) for sz in _leaf_sizes(CONFIGS[name])), BLOCK)


@pytest.mark.parametrize("shape", [VGG_OP1_CUT, LM_CUT, "vgg5", "lm100m"],
                         ids=["vgg5-op1-cut", "lm100m-cut", "vgg5-server-rows",
                              "lm100m-server-rows"])
def test_int8_quant_roundtrip_compiles(shape, one_chip):
    if isinstance(shape, str):
        shape = _server_rows(shape)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = _compile(lambda v: dequantize(*quantize(v, interpret=False),
                                             interpret=False), x)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_vgg5_topk_int8_server_step_compiles(one_chip):
    program = get_split_program(VGG5)
    layout = program.flat_layout(program.init(jax.random.PRNGKey(0)))
    step = ServerStep(layout, density=0.01, quantize=True, interpret=False)
    K = 4

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = step._step.lower(sds((layout.padded,)),
                                sds((K, layout.padded)), sds((K,)),
                                sds((K, layout.padded)), None).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vgg5_batched_fleet_step_with_int8_cut_compiles(one_chip,
                                                        monkeypatch):
    """The batched engine's vmap-of-scan training program at OP1 with the
    int8 cut: under vmap the quant kernel gains a grid dimension."""
    from repro.fl.fleet import make_fleet_step
    # this process runs on the CPU backend, which would resolve the kernels
    # to interpret mode: compile them as the chip would run them
    monkeypatch.setattr(compat, "default_interpret", lambda: False)
    program = get_split_program(VGG5)
    step = make_fleet_step(program, quantize=True)
    G, iters, batch = 2, 2, 100
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(program.init, jax.random.PRNGKey(0)))
    batches = {
        "images": jax.ShapeDtypeStruct((G, iters, batch, 32, 32, 3),
                                       jnp.float32, sharding=one_chip),
        "labels": jax.ShapeDtypeStruct((G, iters, batch), jnp.int32,
                                       sharding=one_chip)}
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = step.lower(params, batches, lr, op=VGG5.ops[0]).compile()
    assert "tpu_custom_call" in compiled.as_text()
