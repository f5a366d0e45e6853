"""Paged int8-KV flash-decode Pallas kernel (the paged serving pool).

The dense int8 decode kernel streams one contiguous ``(B, KV, S, hd)`` cache
row per request — which forces the pool to RESERVE ``s_max`` tokens per slot
whether the stream uses them or not. The paged pool instead keeps one global
arena of fixed-size pages plus a per-request page table; a stream holds
exactly the pages its tokens occupy, so colocation is bounded by tokens in
flight, not by ``num_slots × s_max``.

**Arena layout.** The kernel reads the arena as the pool stores it:
``(num_pages, page_size, KV * hd)`` int8, one page of every KV head in one
contiguous block (token rows, head-major columns), plus float32 scales per
(page, KV head). The row width ``KV * hd`` keeps the TPU's default device
layout row-major (a minor dimension under 128 lanes, such as stablelm's
``hd = 64``, would make XLA lay pages out minor-most and copy the whole arena
before every call), so the decode step's scatter and this kernel touch the
same buffer and nothing relayouts it.

**One grid step per slot.** Each step walks its slot's pages in blocks of
``ppb`` whole pages, double-buffered with manual DMAs through the
scalar-prefetched page table; the last block of a slot prefetches the first
block of the next live slot. Pages at or past ``ceil(len / page_size)``, and
with a sliding window the pages wholly before ``len - window``, cost neither
a DMA nor compute. ``ppb`` comes from the shapes: about 512 KiB of K+V per
block.

**Trash-row contract.** Page 0 is the pool's trash page and never holds a
live stream's tokens. A row whose FIRST page-table entry is 0 holds no
stream — free slots keep stepping with growing lengths and garbage in page 0
— so the kernel treats it as length 0: no DMA, and its output is zeros.

**Mathematics.** Per block, every query row meets every key row of the
block in one matmul against the block-diagonal query matrix (row ``r`` holds
query head ``r`` in the columns of its KV head ``r // G``), so G = H / KV
query heads share their KV head's pages and one kernel serves MHA and GQA.
The per-(page, KV head) K scale multiplies the scores and the V scale
multiplies the probabilities — the same products as dequantizing K and V,
without building a dequantized block. Online-softmax statistics and the
accumulator stay float32; the scores' matmul runs in q's dtype (int8 codes
are exact in bfloat16) and the output is written in q's dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 512 * 1024         # K+V bytes one block of pages aims at


def _kernel(len_ref, ptab_ref, q_ref, sc_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, nxt_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: Optional[int], ps: int, ppb: int, G: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    MP = ptab_ref.shape[1]
    H, W = acc_ref.shape
    hd = q_ref.shape[2]
    T = ppb * ps

    def bounds(s):
        """(live, length, first page, last page) of slot ``s``."""
        n = len_ref[s]
        live = (n > 0) & (ptab_ref[s, 0] != 0)
        last = jnp.minimum((n - 1) // ps, MP - 1)
        first = 0 if window is None else jnp.minimum(
            jnp.maximum(n - window, 0) // ps, last)
        return live, n, first, last

    def copies(s, j, buf, first, last):
        """The DMAs of block ``j`` of slot ``s`` into buffer ``buf``: one K
        and one V page copy per page of the block in [first, last]."""
        out = []
        for i in range(ppb):
            p = j * ppb + i
            page = ptab_ref[s, jnp.minimum(p, MP - 1)]
            out.append(((p >= first) & (p <= last),
                        pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, i],
                                              sem.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, i],
                                              sem.at[1, buf])))
        return out

    def start(s, j, buf, first, last):
        for ok, ck, cv in copies(s, j, buf, first, last):
            @pl.when(ok)
            def _():
                ck.start()
                cv.start()

    def wait(s, j, buf, first, last):
        for ok, ck, cv in copies(s, j, buf, first, last):
            @pl.when(ok)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(b == 0)
    def _():
        nxt_ref[0] = 0      # buffer that holds this slot's first block
        nxt_ref[1] = 0      # 1 when an earlier slot already started it

    live, n, first, last = bounds(b)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        jb0 = first // ppb
        jb1 = last // ppb
        buf0 = nxt_ref[0]

        @pl.when(nxt_ref[1] == 0)
        def _():
            start(b, jb0, buf0, first, last)

        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # block-diagonal queries: row r keeps head r in its KV head's columns
        q = q_ref[0]                                          # (H, hd)
        q_tiled = jnp.concatenate([q] * (W // hd), axis=1)    # (H, W)
        rows = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1) // hd
        q_bd = jnp.where(rows // G == cols, q_tiled, jnp.zeros_like(q_tiled))

        def block(j, buf):
            nbuf = 1 - buf

            @pl.when(j < jb1)
            def _():
                start(b, j + 1, nbuf, first, last)

            @pl.when(j == jb1)
            def _():
                # prefetch the first block of the next live slot
                s2 = jax.lax.while_loop(
                    lambda s: (s < nb) & jnp.logical_not(
                        bounds(jnp.minimum(s, nb - 1))[0]),
                    lambda s: s + 1, b + 1)
                nxt_ref[0] = nbuf
                nxt_ref[1] = (s2 < nb).astype(jnp.int32)

                @pl.when(s2 < nb)
                def _():
                    _, _, f2, l2 = bounds(s2)
                    start(s2, f2 // ppb, nbuf, f2, l2)

            wait(b, j, buf, first, last)
            k = kbuf[buf].astype(q.dtype).reshape(T, W)
            v = vbuf[buf].astype(jnp.float32).reshape(T, W)
            # per-token scales of the block's pages, heads along sublanes
            sc = sc_ref[0, :, pl.ds(j * ppb, ppb), :]          # (2, ppb, H)
            sc = jnp.broadcast_to(sc[:, :, None, :], (2, ppb, ps, H))
            sc = sc.reshape(2 * T, H).T                        # (H, 2T)
            s = jax.lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * (sc[:, :T] * scale)
            pos = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            mask = pos < n
            if window is not None:
                mask &= pos >= n - window
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
            # masked keys may sit on pages this block did not fetch: their
            # scales and codes are whatever the buffers held, so select
            pv = jnp.where(mask, p * sc[:, T:], 0.0)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                pv, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return nbuf

        jax.lax.fori_loop(jb0, jb1 + 1, block, buf0)
        # row r's output is the diagonal block of its KV head's columns
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.concatenate(
            [jnp.sum(jnp.where(rows == cols * G + g, o, 0.0), axis=0,
                     keepdims=True) for g in range(G)],
            axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale, page_table,
                           lengths, *, window: Optional[int] = None,
                           interpret: bool = False):
    """q: (B, H, hd) float; k_pages/v_pages: (num_pages, ps, KV * hd) int8
    arena as the pool stores it; k_scale/v_scale: (num_pages, KV) f32
    per-page dequant scales; page_table: (B, max_pages) int32 arena page ids
    (a row whose first entry is the trash page 0 holds no stream and reads
    zeros); lengths: (B,) keys attended -> (B, H, hd) in q's dtype."""
    B, H, hd = q.shape
    _, ps, W = k_pages.shape
    KV = W // hd
    G = H // KV
    MP = page_table.shape[1]
    # pages per block: the largest power of two within BLOCK_BYTES of K+V,
    # at most the table's width
    ppb = max(1, BLOCK_BYTES // (2 * ps * W * k_pages.dtype.itemsize))
    ppb = min(1 << (ppb.bit_length() - 1), MP)
    MPp = -(-MP // ppb) * ppb
    # (B, 2, MPp, H): the scales of the pages the tables reference, one
    # column per query head (padded columns only ever meet masked keys)
    pt = jnp.pad(page_table, ((0, 0), (0, MPp - MP)))
    scales = jnp.repeat(jnp.stack([k_scale[pt], v_scale[pt]], axis=1), G,
                        axis=-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                               # lengths, page_table
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, lens, pt: (b, 0, 0)),
            pl.BlockSpec((1, 2, MPp, H), lambda b, lens, pt: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),            # K arena
            pl.BlockSpec(memory_space=pltpu.HBM),            # V arena
        ],
        out_specs=pl.BlockSpec((1, G, W), lambda b, lens, pt: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, W), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, W), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, W), jnp.float32),
        ],
    )
    arenas = (k_pages, v_pages)
    if not interpret:
        # pinned in HBM: XLA would otherwise prefetch an arena that fits in
        # VMEM whole before the call, a copy of every page
        arenas = [pltpu.with_memory_space_constraint(a, pltpu.HBM)
                  for a in arenas]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / (hd ** 0.5), window=window,
                          ps=ps, ppb=ppb, G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, W), q.dtype),
        # slots run in order: each prefetches the next live slot's pages
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths, page_table, q, scales, *arenas)
    # (B, G, KV*hd) -> (B, H, hd), query head r = kv * G + g
    return out.reshape(B, G, KV, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)
