"""Compile the serving path's Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed with JAX; it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (unaligned
tiles, unsupported primitives, Mosaic calls the partitioner cannot split),
which interpret-mode tests cannot show.  Each test asserts that the kernel
reached the program as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# qwen3-0.6b (configs/archs.py) and recurrentgemma-2b serving widths
HEADS, KV_HEADS, HEAD_DIM = 16, 8, 128
D_RNN = 2560
SLOTS, MAX_LEN, BLOCK = 8, 2048, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    import repro.kernels.common as common
    import repro.kernels.flash_attention.ops as flash_ops
    import repro.kernels.pavlov_rglru.ops as rglru_ops

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # the kernels pick interpret mode from the default backend (the CPU
    # here); a compile for the described chip needs the Mosaic lowering
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        for mod in (common, flash_ops, rglru_ops):
            mp.setattr(mod, "use_interpret", lambda: False)
        yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# the programs under test, at module level so each jit has one cache key
@partial(jax.jit, static_argnames="layout")
def _flash(q, k, v, layout=None):
    from repro.models.attention import flash_attention
    return flash_attention(q, k, v, impl="pallas", layout=layout)


@jax.jit
def _paged_decode(q, k, v, k_pool, v_pool, lengths, table):
    from repro.models.attention import PagedKVCache, paged_decode_attention
    return paged_decode_attention(
        q, k, v, PagedKVCache(k_pool, v_pool, lengths), table, impl="pallas")


@jax.jit
def _rglru_kernel(a, b):
    from repro.kernels.pavlov_rglru.ops import pavlov_rglru
    from repro.models.recurrent import _divisor_block
    return pavlov_rglru(a, b, block_t=_divisor_block(a.shape[1], 128, 8),
                        block_e=_divisor_block(a.shape[2], 512, 128))


@partial(jax.jit, static_argnames="layout")
def _rglru_core(params, x, layout):
    from repro.models.recurrent import rglru_core
    return rglru_core(params, x, impl="pallas", layout=layout)


def _mesh(topo, dp, mp):
    from repro.models.layout import MeshLayout
    mesh = Mesh(np.asarray(topo.devices).reshape(dp, mp), ("data", "model"))
    return mesh, MeshLayout(mesh, ("data",))


@pytest.mark.parametrize("bucket", [16, 256])
def test_flash_prefill_compiles(topo, bucket):
    one = SingleDeviceSharding(topo.devices[0])
    q = _sds((1, bucket, HEADS, HEAD_DIM), jnp.bfloat16, one)
    kv = _sds((1, bucket, KV_HEADS, HEAD_DIM), jnp.bfloat16, one)
    _assert_kernel(_flash.lower(q, kv, kv).compile())


def test_paged_decode_compiles(topo):
    one = SingleDeviceSharding(topo.devices[0])
    nb = MAX_LEN // BLOCK
    q = _sds((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16, one)
    new = _sds((SLOTS, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16, one)
    pool = _sds((SLOTS * nb, BLOCK, KV_HEADS, HEAD_DIM), jnp.bfloat16, one)
    lengths = _sds((SLOTS,), jnp.int32, one)
    table = _sds((SLOTS, nb), jnp.int32, one)
    _assert_kernel(_paged_decode.lower(q, new, new, pool, pool, lengths,
                                       table).compile())


@pytest.mark.parametrize("kvh,hd", [(8, 128), (2, 256), (2, 64), (1, 128),
                                    (3, 128)])
def test_paged_decode_lowers_matches_the_compiler(topo, kvh, hd):
    """``lowers`` (which keeps the placement off the kernel) agrees with the
    compiler on bf16 head shapes either side of its rule."""
    from repro.kernels.paged_attention.kernel import (
        lowers, paged_decode_attention_raw)
    one = SingleDeviceSharding(topo.devices[0])
    nb = MAX_LEN // BLOCK
    q = _sds((SLOTS, 2 * kvh, hd), jnp.bfloat16, one)
    pool = _sds((SLOTS * nb, BLOCK, kvh, hd), jnp.bfloat16, one)
    table = _sds((SLOTS, nb), jnp.int32, one)
    lengths = _sds((SLOTS,), jnp.int32, one)
    try:
        jax.jit(paged_decode_attention_raw).lower(q, pool, pool, table,
                                                  lengths).compile()
        compiled = True
    except Exception:  # noqa: BLE001 — Mosaic's refusal
        compiled = False
    assert compiled == lowers(kvh, hd, 2)


@pytest.mark.parametrize("steps", [1, 128])
def test_rglru_compiles(topo, steps):
    one = SingleDeviceSharding(topo.devices[0])
    a = _sds((SLOTS, steps, D_RNN), jnp.float32, one)
    _assert_kernel(_rglru_kernel.lower(a, a).compile())


@pytest.mark.parametrize("dp,mp", [(4, 1), (2, 2)])
def test_flash_prefill_shard_map_compiles_on_mesh(topo, dp, mp):
    """Batch-sharded flash prefill over four described chips: the Mosaic
    call runs under the layout's shard_map (the partitioner cannot split
    it), slots on ``data`` and heads on ``model``."""
    mesh, layout = _mesh(topo, dp, mp)
    sh = NamedSharding(mesh, P("data", None, "model" if mp > 1 else None,
                               None))
    q = _sds((4, 256, HEADS, HEAD_DIM), jnp.bfloat16, sh)
    kv = _sds((4, 256, KV_HEADS, HEAD_DIM), jnp.bfloat16, sh)
    compiled = _flash.lower(q, kv, kv, layout=layout).compile()
    _assert_kernel(compiled)
    # per-slot and per-head math is independent: nothing crosses chips
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text


def test_rglru_shard_map_compiles_on_mesh(topo):
    """The RG-LRU decode step on a 2x2 mesh: slots on ``data``, the
    recurrence width on ``model``, the kernel under shard_map."""
    mesh, layout = _mesh(topo, 2, 2)
    repl = NamedSharding(mesh, P())
    params = {"w_a": _sds((D_RNN, D_RNN), jnp.float32, repl),
              "w_i": _sds((D_RNN, D_RNN), jnp.float32, repl),
              "lambda": _sds((D_RNN,), jnp.float32, repl)}
    x = _sds((SLOTS, 1, D_RNN), jnp.bfloat16,
             NamedSharding(mesh, P("data", None, "model")))
    _assert_kernel(_rglru_core.lower(params, x, layout=layout).compile())
