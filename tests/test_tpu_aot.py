"""Compiled for a described v5e, with no chip attached: the serving path's
Pallas kernel at the benchmark's widths.

The TPU's compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached. Nothing runs; what the chip's
compiler refuses (a misaligned block, an index map Mosaic cannot lower, a
program that does not fit) is refused here, and `memory_analysis()` says
whether XLA had to copy a buffer the program meant to update in place.

The topology is described inside a fixture of THIS file only, so one
xdist worker loads the TPU's library and every worker collects the same
tests. Keep further such compiles in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk

# gpt3-1.3b as the served cells run it, cut to three layers
L, B, H, T, D = 3, 24, 16, 1024, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _decode_chain(q, kc, vc, ks, vs, lens, nk, nv):
    """What `_decode_fn` does to the cache: every layer's kernel call in
    turn on the one stacked buffer."""
    for layer in range(L):
        q, kc, vc, ks, vs = pk._paged_decode(
            q, kc, vc, lens, nk, nv, ks, vs, layer=layer,
            block_k=pk._paged_block(T, interpret=False), interpret=False)
    return q, kc, vc, ks, vs


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_chain_updates_the_cache_in_place(one_chip, kv_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quantized = kv_dtype == "int8"
    tok = sds((B, H, 1, D), jnp.bfloat16)
    cache = sds((L, B, H, T, D), jnp.dtype(kv_dtype))
    scale = sds((L, B, H, T), jnp.float32) if quantized else None
    compiled = jax.jit(_decode_chain, donate_argnums=(1, 2, 3, 4)).lower(
        tok, cache, cache, scale, scale, sds((B,), jnp.int32), tok, tok
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == L
    ma = compiled.memory_analysis()
    cache_bytes = 2 * L * B * H * T * D * jnp.dtype(kv_dtype).itemsize
    scale_bytes = 2 * L * B * H * T * 4 if quantized else 0
    # donated in, aliased through every call, out: one buffer each
    assert ma.alias_size_in_bytes >= cache_bytes + scale_bytes
    # a copy XLA had to insert would be a layer of K or V at the least
    assert ma.temp_size_in_bytes < cache_bytes // (2 * L) // 2
