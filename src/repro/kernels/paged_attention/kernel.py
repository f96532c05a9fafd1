"""Paged decode-attention kernel — live pages fetched by hand, many at a time.

One query token per slot attends to its logical KV sequence, which lives
scattered across a global pool of fixed-size blocks (vLLM-style paging).
The pool stays in HBM (``memory_space=pltpu.HBM``); the block table and the
per-slot lengths are *scalar-prefetch* operands in SMEM, and the kernel
copies exactly the physical blocks a slot's live positions 0..lengths[b]
occupy — K/V are never materialized per slot in HBM, which is the whole
point of paging.

Grid: (B,), one step per slot, run in order.  Inside, a ``fori_loop`` walks
the slot's page groups: ``min(lengths[b] // bs, nb - 1) // P + 1`` of them,
a trip count read from SMEM, so pages past the slot's length (and the
sentinel entries behind them) are never fetched and an empty slot costs one
group.  A group is P pages, P = min(256 // bs, nb // 2) at least 1 — about
256 tokens, and at most half the table so a full slot always streams through
both buffers; the last group of a slot copies only its live pages.

Buffers: K and V each get a two-slot VMEM buffer (2, P, bs, KVH, hd), one
page a copy, with one DMA semaphore per slot and operand.  Group g+1's
copies (or, after a slot's last group, the next slot's first group) start
before group g is computed, so the fetch of one group hides behind the math
of the other.  The buffers are zeroed once per call: a page a short group
does not copy holds zeros or an earlier live page, never uninitialized
memory, so its zero probabilities contribute exactly nothing.

Math: ``q * scale`` in fp32 against the group's K flattened to (P*bs*KVH,
hd) — one matmul for all H query rows, no per-head relayout — with the
entries whose kv head is not h // G, or whose position is past lengths[b],
masked.  The online-softmax state (m, l, acc) accumulates in fp32 VMEM
scratch across groups; p stays fp32 for the PV product.

Pool layout contract: k_pool/v_pool (N, bs, KVH, hd), block_table (B, nb)
with every entry in [0, N) (ops.py and models/attention.py clamp the
sentinels), lengths (B,) the highest valid position per slot.  The TPU
compiler copies a page only for the head shapes ``lowers`` names; the
placement keeps other models' decode attention on XLA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens a page group holds at most: big enough that the per-group step cost
# is small against its copy, small enough that a group's scores stay a few
# vregs per query row
GROUP_TOKENS = 256


def pages_per_group(bs: int, nb: int) -> int:
    """P for a pool of ``bs``-token blocks and ``nb``-block tables."""
    return max(1, min(GROUP_TOKENS // bs, nb // 2))


def lowers(kv_heads: int, head_dim: int, itemsize: int) -> bool:
    """Whether the TPU compiler can copy one page (bs, KVH, hd) out of the
    pool as XLA tiles it in HBM: hd whole 128-lane rows, and KVH a power of
    two that fills the dtype's packed sublanes.  Other heads (hd 64, KVH 1 or
    3 in bf16) are refused at compile time."""
    return (head_dim % 128 == 0 and kv_heads & (kv_heads - 1) == 0
            and kv_heads * itemsize >= 4)


def _paged_decode_kernel(table_ref, length_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, cur_ref, m_ref, l_ref, acc_ref,
                         *, scale: float, bs: int, nb: int, pages: int,
                         kvh: int, group: int):
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    tokens = pages * bs
    h, hd = q_ref.shape[1], q_ref.shape[2]

    def last_page(slot):
        return jnp.minimum(length_ref[slot] // bs, nb - 1)

    def live_pages(slot, g):
        return jnp.minimum(pages, last_page(slot) + 1 - g * pages)

    def page_copy(which, slot, g, j, buf):
        blk = table_ref[slot, g * pages + j]
        pool, dst = ((k_hbm, k_buf), (v_hbm, v_buf))[which]
        return pltpu.make_async_copy(pool.at[pl.ds(blk, 1)],
                                     dst.at[buf, pl.ds(j, 1)],
                                     sems.at[which, buf])

    def start_group(slot, g, buf):
        def one(j, carry):
            page_copy(0, slot, g, j, buf).start()
            page_copy(1, slot, g, j, buf).start()
            return carry
        jax.lax.fori_loop(0, live_pages(slot, g), one, 0)

    def wait_group(which, g, buf):
        def one(j, carry):
            page_copy(which, b, g, j, buf).wait()
            return carry
        jax.lax.fori_loop(0, live_pages(b, g), one, 0)

    @pl.when(b == 0)
    def _first():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        cur_ref[0] = 0
        start_group(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = jnp.minimum(length_ref[b], nb * bs - 1)
    groups = last_page(b) // pages + 1
    q = q_ref[0].astype(jnp.float32) * scale                 # (H, hd)
    cols = (h, tokens * kvh)
    col = jax.lax.broadcasted_iota(jnp.int32, cols, 1)
    # flattened column t*KVH + n holds token t of kv head n; query row r
    # reads kv head r // G
    own_head = (col % kvh) == (jax.lax.broadcasted_iota(jnp.int32, cols, 0)
                               // group)
    col_pos = col // kvh

    def step(g, carry):
        buf = cur_ref[0]
        nxt = 1 - buf

        @pl.when(g + 1 < groups)
        def _prefetch_group():
            start_group(b, g + 1, nxt)

        @pl.when(jnp.logical_and(g + 1 == groups, b + 1 < slots))
        def _prefetch_slot():
            start_group(b + 1, 0, nxt)

        wait_group(0, g, buf)
        k = k_buf[buf].astype(jnp.float32).reshape(tokens * kvh, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        live = jnp.logical_and(own_head, g * tokens + col_pos <= length)
        s = jnp.where(live, s, NEG_INF)                      # incl. new token

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (H, T*KVH)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        wait_group(1, g, buf)
        v = v_buf[buf].astype(jnp.float32).reshape(tokens * kvh, hd)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        cur_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, groups, step, 0)
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_raw(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_table: jax.Array,
                               lengths: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k_pool/v_pool: (N, bs, KVH, hd); block_table: (B, nb)
    int32 with every entry in [0, N); lengths: (B,) int32 — the highest valid
    logical position per slot (the freshly written token's position).
    Returns (B, H, hd)."""
    b, h, hd = q.shape
    n, bs, kvh, _ = k_pool.shape
    _, nb = block_table.shape
    assert h % kvh == 0
    pages = pages_per_group(bs, nb)
    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / math.sqrt(hd), bs=bs, nb=nb,
        pages=pages, kvh=kvh, group=h // kvh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_table, lengths
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, tbl, lens: (i, 0, 0)),
            # the pools stay in HBM: the kernel copies the live blocks
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, tbl, lens: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, kvh, hd), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, kvh, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # (K|V, buffer)
            pltpu.SMEM((1,), jnp.int32),       # buffer of the next group
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        # the slots run in order: a slot's last group prefetches the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table, lengths, q, k_pool, v_pool)
