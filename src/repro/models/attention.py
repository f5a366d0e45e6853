"""GQA attention: chunked flash-style prefill (memory-sane lowering) + decode.

The prefill path is a pure-jnp online-softmax flash attention (lax.scan over KV
chunks nested in a scan over Q chunks). It is (a) the reference oracle for the
Pallas kernel in ``repro.kernels.flash_attention`` and (b) what the dry-run
lowers — naive (S×S)-materializing attention would blow past HBM in
memory_analysis() at 32k.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec
from repro.models.layers import apply_mrope, apply_rope

NEG_INF = -1e30


# ---------------- specs ----------------

def attn_spec(cfg, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias and not cross:
        spec["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return spec


# ---------------- core flash attention (jnp reference) ----------------

def _chunk(n: int, target: int) -> int:
    c = min(n, target)
    while n % c:
        c -= 1
    return c


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_chunk", "kv_chunk"))
def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q_chunk: int = 1024, kv_chunk: int = 1024, kv_len=None,
                    prefix_k=None, prefix_v=None, prefix_len=None):
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H % KV == 0 (GQA).
    Returns (B, Sq, H, hd). Q/K positions are aligned at the end (standard
    causal self-attention when Sq == Sk; for Sq < Sk, q is the suffix).

    ``kv_len``: optional (B,) per-row valid key count — key positions at or
    beyond a row's length are masked out (right-padded variable-length
    prefill). Query rows past the length still see a non-empty causal
    window, so their (discarded) outputs stay finite.

    ``prefix_k``/``prefix_v``: optional (B, Sp, KV, hd) precomputed prefix
    K/V prepended before ``k``/``v`` on the key axis (chunked shared-prefix
    prefill: the tail's queries attend to dequantized prefix pages that were
    never part of this dispatch's QKV projection). ``prefix_len`` (B,) gives
    each row's true prefix length — positions at or beyond it are masked out
    (bucket-padded prefix tables point the slack at the trash page). Queries
    sit causally AFTER the whole prefix: q position 0 is absolute position
    ``prefix_len``, so every valid prefix key is visible to every query.
    """
    B, Sq, H, hd = q.shape
    Sk_new = k.shape[1]
    KV = k.shape[2]
    if prefix_k is not None:
        assert prefix_v is not None and prefix_len is not None
        assert window is None, "sliding window over a prefix is unsupported"
        Sp = prefix_k.shape[1]
        k = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
        v = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
        tail_ok = (jnp.arange(Sk_new)[None] < kv_len[:, None]
                   if kv_len is not None
                   else jnp.ones((B, Sk_new), jnp.bool_))
        key_valid = jnp.concatenate(
            [jnp.arange(Sp)[None] < prefix_len[:, None], tail_ok], axis=1)
    elif kv_len is not None:
        key_valid = jnp.arange(Sk_new)[None] < kv_len[:, None]
    else:
        key_valid = None
    _, Sk, KV, _ = k.shape
    G = H // KV
    qc = _chunk(Sq, q_chunk)
    kc = _chunk(Sk, kv_chunk)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q_off = Sk - Sq  # absolute position offset of q block

    # operands stay in their storage dtype (bf16 on TPU); the MXU accumulates
    # in f32 via preferred_element_type — avoids materializing f32 copies of
    # Q/K/V, which would double HBM traffic (§Perf iteration 2)
    qr = q.reshape(B, Sq // qc, qc, KV, G, hd)
    kr = k.reshape(B, Sk // kc, kc, KV, hd)
    vr = v.reshape(B, Sk // kc, kc, KV, hd)

    def q_step(_, qi):
        qblk, qidx = qi                                   # (B, qc, KV, G, hd)
        q_pos = q_off + qidx * qc + jnp.arange(qc)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki[:3]
            k_pos = kidx * kc + jnp.arange(kc)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qc, kc), jnp.bool_)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            mask = mask[None, None, None]                 # (1, 1, 1, qc, kc)
            if key_valid is not None:
                vmask = ki[3]                             # (B, kc)
                mask = mask & vmask[:, None, None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, hd), jnp.float32)
        xs = (kr.swapaxes(0, 1), vr.swapaxes(0, 1), jnp.arange(Sk // kc))
        if key_valid is not None:
            xs = xs + (key_valid.reshape(B, Sk // kc, kc).swapaxes(0, 1),)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B, KV, G, qc, hd)
        return None, out.transpose(0, 3, 1, 2, 4)          # (B, qc, KV, G, hd)

    _, chunks = jax.lax.scan(q_step, None, (qr.swapaxes(0, 1), jnp.arange(Sq // qc)))
    out = chunks.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: Optional[int] = None):
    """Single-token attention against a KV cache.

    q: (B, H, hd); caches: (B, S, KV, hd); cache_len: (B,) valid lengths
    (the new token's position is cache_len - 1 after insertion).
    """
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    # bf16 operands + f32 accumulation: the KV cache is streamed once in its
    # storage dtype instead of being copied to f32 first (§Perf iteration 2)
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)[None]                              # (1, S)
    mask = pos < cache_len[:, None]
    if window is not None:
        mask &= pos >= (cache_len[:, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------- full layer ops ----------------

def qkv_project(p, x, cfg, pos=None, pos3=None, rope: bool = True,
                lora=None, adapter_idx=None, lora_impl: str = "gather",
                lora_seg=None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if lora is not None and adapter_idx is not None:
        from repro.models.lora import qv_lora
        q, v = qv_lora(x, lora, adapter_idx, q, v, impl=lora_impl,
                       seg=lora_seg)
    if rope:
        if cfg.mrope_sections is not None and pos3 is not None:
            q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
        elif pos is not None:
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def out_project(p, attn_out, dtype):
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"].astype(dtype))


def self_attention(p, x, cfg, shard, *, causal=True, pos=None, pos3=None,
                   lora=None, adapter_idx=None, lora_impl="gather",
                   lora_seg=None, seq_lens=None, prefix=None, prefix_len=None):
    """Full-sequence self attention (train / prefill). Returns (out, (k, v)).

    ``seq_lens``: (B,) true lengths of right-padded rows — pad key positions
    are masked out of the attention (variable-length prefill admission).

    ``prefix``: optional dict(k, v) of precomputed (B, Sp, KV, hd) prefix K/V
    (dequantized shared-prefix pages, chunked prefill) that the queries attend
    to in ADDITION to this dispatch's own K/V; ``prefix_len`` (B,) true prefix
    lengths. The returned (k, v) stay tail-only — the cache stores only what
    this dispatch computed."""
    q, k, v = qkv_project(p, x, cfg, pos=pos, pos3=pos3, lora=lora,
                          adapter_idx=adapter_idx, lora_impl=lora_impl,
                          lora_seg=lora_seg)
    q = shard(q, ("batch", None, "heads", None))
    k = shard(k, ("batch", None, "kv_heads", None))
    v = shard(v, ("batch", None, "kv_heads", None))
    if prefix is not None:
        o = flash_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                            kv_len=seq_lens, prefix_k=prefix["k"],
                            prefix_v=prefix["v"], prefix_len=prefix_len)
    else:
        o = flash_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                            kv_len=seq_lens)
    return out_project(p, o, x.dtype), (k, v)


def cross_attention(p, x, enc_kv, cfg, shard):
    """Decoder cross-attention over precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k, v = enc_kv
    o = flash_attention(q, k.astype(x.dtype), v.astype(x.dtype), causal=False)
    return out_project(p, o, x.dtype)


def self_attention_decode(p, x, cache, cfg, shard, *, pos=None, pos3=None,
                          lora=None, adapter_idx=None, lora_impl="gather",
                          lora_seg=None):
    """One-step decode. x: (B, 1, d); cache: dict(k, v, len). Returns (out, cache').

    When the cache carries ``k_scale``/``v_scale`` it is an int8 KV pool
    (persistent decode serving, see ``core.decode_engine``): the new token's
    K/V are quantized into the scales fixed at prefill admission and attention
    runs through ``kernels.decode_attention_int8``, so the cache is only ever
    streamed as int8.
    """
    q, k, v = qkv_project(p, x, cfg, pos=pos, pos3=pos3, lora=lora,
                          adapter_idx=adapter_idx, lora_impl=lora_impl,
                          lora_seg=lora_seg)
    B = x.shape[0]
    idx = cache["len"]                                    # (B,) insert position
    bidx = jnp.arange(B)
    if "page_table" in cache:
        # paged int8 pool: the new token lands in arena page
        # page_table[b, len // ps] at offset len % ps. The FIRST token of a
        # page quantizes with the slot's admission-era running scale and
        # stamps it as the page scale (a recycled page's stale scale must
        # never leak in); later tokens reuse the page's stamped scale — for
        # a partial prompt page that is its admission per-page scale, so
        # earlier codes keep dequantizing correctly. Attention gathers K/V
        # through the page table (ops.paged_decode_attention). The slot's
        # decode-era |K|/|V| running maxima ride in ``k_max``/``v_max`` for
        # the engine's proactive scale refresh.
        ps = cache["k"].shape[1]
        page = jnp.take_along_axis(cache["page_table"],
                                   (idx // ps)[:, None], axis=1)[:, 0]
        off = idx % ps
        fresh = (off == 0)[:, None]
        ks = jnp.maximum(jnp.where(fresh, cache["slot_k_scale"],
                                   cache["k_scale"][page]), 1e-8)
        vs = jnp.maximum(jnp.where(fresh, cache["slot_v_scale"],
                                   cache["v_scale"][page]), 1e-8)
        kf = k[:, 0].astype(jnp.float32)
        vf = v[:, 0].astype(jnp.float32)
        kq = jnp.clip(jnp.round(kf / ks[:, :, None]),
                      -127, 127).astype(jnp.int8)
        vq = jnp.clip(jnp.round(vf / vs[:, :, None]),
                      -127, 127).astype(jnp.int8)
        k_pages = cache["k"].at[page, off].set(kq.reshape(B, -1))
        v_pages = cache["v"].at[page, off].set(vq.reshape(B, -1))
        k_sc = cache["k_scale"].at[page].set(ks)
        v_sc = cache["v_scale"].at[page].set(vs)
        k_max = jnp.maximum(cache["k_max"], jnp.max(jnp.abs(kf), axis=-1))
        v_max = jnp.maximum(cache["v_max"], jnp.max(jnp.abs(vf), axis=-1))
        from repro.kernels import ops
        o = ops.paged_decode_attention(q[:, 0], k_pages, v_pages, k_sc, v_sc,
                                       cache["page_table"], idx + 1,
                                       window=cfg.sliding_window)
        out = out_project(p, o.astype(x.dtype)[:, None], x.dtype)
        return out, {"k": k_pages, "v": v_pages, "k_scale": k_sc,
                     "v_scale": v_sc, "k_max": k_max, "v_max": v_max,
                     "len": idx + 1}
    if "k_scale" in cache:
        from repro.kernels import ops
        # scales are per (B, KV), fixed at prefill; epsilon-guard free slots
        # whose scales were never written (their rows are masked out anyway)
        ks = jnp.maximum(cache["k_scale"], 1e-8)
        vs = jnp.maximum(cache["v_scale"], 1e-8)
        kq = jnp.clip(jnp.round(k[:, 0].astype(jnp.float32) / ks[:, :, None]),
                      -127, 127).astype(jnp.int8)
        vq = jnp.clip(jnp.round(v[:, 0].astype(jnp.float32) / vs[:, :, None]),
                      -127, 127).astype(jnp.int8)
        k_cache = cache["k"].at[bidx, idx].set(kq)
        v_cache = cache["v"].at[bidx, idx].set(vq)
        o = ops.decode_attention_int8(q[:, 0], k_cache, v_cache, ks, vs,
                                      idx + 1, window=cfg.sliding_window)
        o = o.astype(x.dtype)
    else:
        k_cache = cache["k"].at[bidx, idx].set(k[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, idx].set(v[:, 0].astype(cache["v"].dtype))
        o = decode_attention(q[:, 0], k_cache, v_cache, idx + 1,
                             window=cfg.sliding_window)
    out = out_project(p, o[:, None], x.dtype)
    return out, {"k": k_cache, "v": v_cache, "len": idx + 1}


def self_attention_verify(p, x, cache, cfg, shard, *, pos=None, pos3=None,
                          lora=None, adapter_idx=None, lora_impl="gather",
                          lora_seg=None):
    """Speculative verify window: T = k+1 positions through the paged pool.

    x: (B, T, d) — position 0 embeds the slot's last sampled token (what a
    plain decode step would feed), positions 1..k the drafted continuation.
    Only the paged int8 pool is supported (speculation is gated to
    ``paged=True`` in the engine).

    Every window position's K/V is written into the slot's decode-private
    pages with EXACTLY the scale a sequential walk of T single-token steps
    would pick: a position reuses the pre-window page scale iff it lands in
    the page already holding token ``len - 1`` (only the window's first page
    can predate the window — positions are strictly increasing), otherwise
    it is the first write to a fresh page and quantizes with the slot's
    running scale, which the sequential walk stamps at ``off == 0`` and
    reuses for the rest of that page. Attention then reads each position j
    against keys ``0..len+j`` via ``ops.paged_verify_attention`` —
    bit-identical arithmetic to j+1 successive single-token steps.

    The returned cache advances by the FULL window (``len += T``) and
    carries per-position running-max stacks ``k_cmax``/``v_cmax``
    (B, T, KV) so the engine's acceptance pass can roll ``len`` /
    ``k_max`` / ``v_max`` back to each slot's commit point in-graph.
    Rejected positions' codes and fresh-page scale stamps sit past the
    rolled-back length, where the next dispatch's ``off == 0`` write
    re-stamps and overwrites them — rollback is a length/tracker reset,
    never a page free.
    """
    assert "page_table" in cache, "speculative verify requires the paged pool"
    q, k, v = qkv_project(p, x, cfg, pos=pos, pos3=pos3, lora=lora,
                          adapter_idx=adapter_idx, lora_impl=lora_impl,
                          lora_seg=lora_seg)
    B, T = x.shape[:2]
    idx = cache["len"]                                    # (B,)
    ps = cache["k"].shape[1]
    pos_abs = idx[:, None] + jnp.arange(T)[None]          # (B, T)
    page = jnp.take_along_axis(cache["page_table"], pos_abs // ps, axis=1)
    off = pos_abs % ps
    in_old = (pos_abs // ps) == ((idx - 1) // ps)[:, None]
    ks = jnp.maximum(jnp.where(in_old[..., None], cache["k_scale"][page],
                               cache["slot_k_scale"][:, None]), 1e-8)
    vs = jnp.maximum(jnp.where(in_old[..., None], cache["v_scale"][page],
                               cache["slot_v_scale"][:, None]), 1e-8)
    kf = k.astype(jnp.float32)                            # (B, T, KV, hd)
    vf = v.astype(jnp.float32)
    kq = jnp.clip(jnp.round(kf / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vf / vs[..., None]), -127, 127).astype(jnp.int8)
    pf, of = page.reshape(-1), off.reshape(-1)
    k_pages = cache["k"].at[pf, of].set(kq.reshape(B * T, -1))
    v_pages = cache["v"].at[pf, of].set(vq.reshape(B * T, -1))
    # duplicate page indices across a row's positions carry identical scale
    # values (same page => same in_old branch), so last-write-wins is exact
    k_sc = cache["k_scale"].at[pf].set(ks.reshape(B * T, -1))
    v_sc = cache["v_scale"].at[pf].set(vs.reshape(B * T, -1))
    k_cmax = jnp.maximum(jax.lax.cummax(jnp.max(jnp.abs(kf), axis=-1), axis=1),
                         cache["k_max"][:, None])
    v_cmax = jnp.maximum(jax.lax.cummax(jnp.max(jnp.abs(vf), axis=-1), axis=1),
                         cache["v_max"][:, None])
    from repro.kernels import ops
    o = ops.paged_verify_attention(q, k_pages, v_pages, k_sc, v_sc,
                                   cache["page_table"], idx,
                                   window=cfg.sliding_window)
    out = out_project(p, o.astype(x.dtype), x.dtype)
    return out, {"k": k_pages, "v": v_pages, "k_scale": k_sc, "v_scale": v_sc,
                 "k_max": k_cmax[:, -1], "v_max": v_cmax[:, -1],
                 "k_cmax": k_cmax, "v_cmax": v_cmax, "len": idx + T}
