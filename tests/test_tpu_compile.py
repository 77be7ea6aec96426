"""Every Pallas kernel entry point compiles for a TPU v5e.

The chip is described (`v5e:2x2`), not attached: the TPU compiler runs
here and refuses what the chip would refuse (unaligned blocks, scalar
stores to VMEM, casts Mosaic lacks), which interpret mode cannot show.
Shapes are the chip smoke's real widths: k=3 contributions of a flat
batch of two phi3-mini-3.8b MLP leaves (3072 x 8192 each), in the
dtypes the engine's routes stream. The topology is described inside a
fixture, never at import: only one process may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dare import dare_block_pallas, dare_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.histogram import (
    block_amax_pallas, block_hist_pallas, ties_block_pallas)
from repro.kernels.nary_accum import nary_accum_pallas
from repro.kernels.quant import quant_nary_pallas
from repro.kernels.slerp import slerp_pallas
from repro.kernels.ties import ties_pallas

K, D, F = 3, 3072, 8192
BLOCK = 2048
LEAVES = 2
N = LEAVES * D * F
NB = N // BLOCK
f32, bf16, i8, i32, u32 = (jnp.float32, jnp.bfloat16, jnp.int8,
                           jnp.int32, jnp.uint32)
STREAM = [((K, N), f32), ((1, N), f32)]
LEAF_ID, FIRST = ((NB,), i32), ((LEAVES,), i32)

# entry point -> (callable, argument shapes)
CASES = {
    "nary_accum_f32": (functools.partial(nary_accum_pallas, block=BLOCK),
                       STREAM + [((K, 1), f32)]),
    "nary_accum_bf16": (functools.partial(nary_accum_pallas, block=BLOCK),
                        [((K, N), bf16), ((1, N), f32), ((K, 1), f32)]),
    "ties": (functools.partial(ties_pallas, block=BLOCK),
             STREAM + [((K, 1), f32)]),
    "dare_block": (functools.partial(dare_block_pallas, p=0.5, block=BLOCK),
                   STREAM + [LEAF_ID, ((3 * LEAVES,), i32)]),
    "dare": (functools.partial(dare_pallas, p=0.5, block=BLOCK),
             STREAM + [((1, 1), u32)]),
    "block_amax": (functools.partial(block_amax_pallas, block=BLOCK),
                   STREAM + [LEAF_ID, FIRST]),
    "block_hist": (functools.partial(block_hist_pallas, bins=512,
                                     block=BLOCK),
                   STREAM + [LEAF_ID, FIRST, FIRST,
                             ((LEAVES, K, 1), f32)]),
    "ties_block": (functools.partial(ties_block_pallas, block=BLOCK),
                   STREAM + [LEAF_ID, ((LEAVES, K, 1), f32)]),
    "quant_nary": (functools.partial(quant_nary_pallas, block=BLOCK),
                   [((K, N), i8), ((1, N), f32), LEAF_ID,
                    ((LEAVES, K, 1), f32), ((K, 1), f32)]),
    "slerp": (functools.partial(slerp_pallas, t=0.5, block=BLOCK),
              [((1, N), f32), ((1, N), f32)]),
    # phi3-mini attention: 32 heads of 96, 2048 tokens
    "flash_attention": (functools.partial(flash_attention, causal=True),
                        [((1, 2048, 32, 96), bf16)] * 3),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent cache off
    (a compile for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe it with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
