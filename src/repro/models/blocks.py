"""Config-driven block dispatcher.

A model is ``num_periods`` repetitions of a *period* — a fixed sequence of
sublayers (attention / mamba / sLSTM / mLSTM, each optionally followed by a
dense-FFN or MoE sublayer). Parameters and caches carry a leading
``num_periods`` axis and are consumed by ``lax.scan`` in ``repro.models.lm`` —
this keeps the HLO one-period-sized for 80-layer configs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp

from repro.configs.base import ATTN, MAMBA, MLSTM, SLSTM, ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import xlstm as xl
from repro.models.common import ParamSpec
from repro.models.layers import rmsnorm, rmsnorm_spec
from repro.models.mlp import mlp, mlp_spec
from repro.models.moe import moe_ffn, moe_spec


@dataclasses.dataclass(frozen=True)
class SubLayer:
    kind: str
    has_moe: bool
    has_ffn: bool
    has_cross: bool = False


def period_len(cfg: ModelConfig) -> int:
    base = len(cfg.block_pattern) if cfg.block_pattern else 1
    if cfg.uses_moe:
        base = math.lcm(base, cfg.moe_every)
    assert cfg.num_layers % base == 0, (cfg.name, cfg.num_layers, base)
    return base


def period_layout(cfg: ModelConfig, cross: bool = False) -> list[SubLayer]:
    plen = period_len(cfg)
    blocks = cfg.blocks
    out = []
    for pos in range(plen):
        kind = blocks[pos]
        has_ffn = cfg.d_ff > 0 and kind not in (MLSTM, SLSTM)
        has_moe = has_ffn and cfg._layer_has_moe(pos)
        out.append(SubLayer(kind, has_moe, has_ffn, cross))
    return out


# ---------------- specs ----------------

def sublayer_spec(cfg: ModelConfig, lay: SubLayer) -> dict:
    d = cfg.d_model
    spec: dict = {"ln1": rmsnorm_spec(d)}
    if lay.kind == ATTN:
        spec["attn"] = attn.attn_spec(cfg)
    elif lay.kind == MAMBA:
        spec["mamba"] = mam.mamba_spec(cfg)
    elif lay.kind == MLSTM:
        spec["mlstm"] = xl.mlstm_spec(cfg)
    elif lay.kind == SLSTM:
        spec["slstm"] = xl.slstm_spec(cfg)
    if lay.has_cross:
        spec["ln_x"] = rmsnorm_spec(d)
        spec["cross"] = attn.attn_spec(cfg, cross=True)
    if lay.has_ffn:
        spec["ln2"] = rmsnorm_spec(d)
        spec["ffn"] = moe_spec(cfg) if lay.has_moe else mlp_spec(cfg)
    return spec


def sublayer_cache_spec(cfg: ModelConfig, lay: SubLayer, batch: int, s_max: int,
                        enc_len: int = 0, kv_quant: bool = False,
                        paged: bool = False, page_size: int = 16,
                        num_pages: int = 0) -> Optional[dict]:
    """Decode-time cache carried per sublayer (logical axes included).

    ``kv_quant``: store self-attention K/V as int8 with per-(batch, kv-head)
    symmetric scales (persistent serving pools — halves cache traffic; scales
    are written at prefill admission). Cross-attention K/V stay bf16.

    ``paged``: the paged serving pool layout (``core.decode_engine`` with
    ``paged=True``) — instead of one dense (batch, s_max) region per slot,
    self-attention K/V live in a global arena of ``num_pages`` fixed-size
    pages, ``(num_pages, page_size, kv * hd)`` int8 (one row per token, the
    KV heads side by side: the paged decode kernel reads a page as one
    contiguous block, and the row width keeps the TPU's default layout
    row-major where ``hd`` alone is under 128 lanes), shared by all slots (copy-on-write prefix sharing maps one page
    into several tables), addressed through a per-slot ``page_table``
    (int32 arena page ids; entries past a stream's length stay 0, a valid —
    masked — index). Scales are per (page, kv-head) — each page is
    quantized over its OWN content at admission, which is what makes a
    shared prefix page bit-identical regardless of which stream wrote it.
    ``slot_k_scale`` / ``slot_v_scale`` keep each slot's admission-time
    running scales so decode-era appends quantize into a consistent range
    and stamp it onto fresh pages; ``k_max`` / ``v_max`` track the slot's
    decode-era magnitude maxima for the engine's proactive scale refresh.
    ``s_max`` bounds pages per slot (the page-table width), NOT reserved
    memory: a stream only ever holds the pages its tokens occupy. int8-only
    (the arena layout exists to halve streamed bytes; a bf16 arena would
    just be a slower dense pool).
    """
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    dt = jnp.bfloat16
    kv_dt = jnp.int8 if kv_quant else dt
    if paged and lay.kind == ATTN:
        assert kv_quant and num_pages > 0, \
            "paged pools are int8 self-attention only"
        mp = -(-s_max // page_size)                 # page-table width
        spec = {
            "k": ParamSpec((num_pages, page_size, kv * hd),
                           (None, None, "kv_heads"),
                           init="zeros", dtype=kv_dt),
            "v": ParamSpec((num_pages, page_size, kv * hd),
                           (None, None, "kv_heads"),
                           init="zeros", dtype=kv_dt),
            "k_scale": ParamSpec((num_pages, kv), (None, "kv_heads"),
                                 init="zeros", dtype=jnp.float32),
            "v_scale": ParamSpec((num_pages, kv), (None, "kv_heads"),
                                 init="zeros", dtype=jnp.float32),
            "slot_k_scale": ParamSpec((batch, kv), ("batch", "kv_heads"),
                                      init="zeros", dtype=jnp.float32),
            "slot_v_scale": ParamSpec((batch, kv), ("batch", "kv_heads"),
                                      init="zeros", dtype=jnp.float32),
            "k_max": ParamSpec((batch, kv), ("batch", "kv_heads"),
                               init="zeros", dtype=jnp.float32),
            "v_max": ParamSpec((batch, kv), ("batch", "kv_heads"),
                               init="zeros", dtype=jnp.float32),
            "page_table": ParamSpec((batch, mp), ("batch", None),
                                    init="zeros", dtype=jnp.int32),
            "len": ParamSpec((batch,), ("batch",), init="zeros",
                             dtype=jnp.int32),
        }
        if lay.has_cross:
            # encoder–decoder: cross K/V are per-SLOT pooled state (the
            # encoder output does not grow with decode) — dense bf16
            # sidecars beside the paged self-attention arena
            spec["ck"] = ParamSpec((batch, enc_len, kv, hd),
                                   ("batch", "cache_seq", "kv_heads", None),
                                   init="zeros", dtype=dt)
            spec["cv"] = ParamSpec((batch, enc_len, kv, hd),
                                   ("batch", "cache_seq", "kv_heads", None),
                                   init="zeros", dtype=dt)
        return spec
    if lay.kind == ATTN:
        spec = {
            "k": ParamSpec((batch, s_max, kv, hd), ("batch", "cache_seq", "kv_heads", None),
                           init="zeros", dtype=kv_dt),
            "v": ParamSpec((batch, s_max, kv, hd), ("batch", "cache_seq", "kv_heads", None),
                           init="zeros", dtype=kv_dt),
            "len": ParamSpec((batch,), ("batch",), init="zeros", dtype=jnp.int32),
        }
        if kv_quant:
            spec["k_scale"] = ParamSpec((batch, kv), ("batch", "kv_heads"),
                                        init="zeros", dtype=jnp.float32)
            spec["v_scale"] = ParamSpec((batch, kv), ("batch", "kv_heads"),
                                        init="zeros", dtype=jnp.float32)
        if lay.has_cross:
            spec["ck"] = ParamSpec((batch, enc_len, kv, hd),
                                   ("batch", "cache_seq", "kv_heads", None),
                                   init="zeros", dtype=dt)
            spec["cv"] = ParamSpec((batch, enc_len, kv, hd),
                                   ("batch", "cache_seq", "kv_heads", None),
                                   init="zeros", dtype=dt)
        return spec
    di = cfg.mamba_expand * cfg.d_model
    if lay.kind == MAMBA:
        return {
            "conv": ParamSpec((batch, cfg.mamba_d_conv - 1, di), ("batch", None, "mlp"),
                              init="zeros", dtype=dt),
            "ssm": ParamSpec((batch, di, cfg.mamba_d_state), ("batch", "mlp", None),
                             init="zeros"),
        }
    dix = int(cfg.xlstm_proj_factor * cfg.d_model)
    h = cfg.num_heads
    if lay.kind == MLSTM:
        return {
            "C": ParamSpec((batch, h, dix // h, dix // h), ("batch", "heads", None, None),
                           init="zeros"),
            "n": ParamSpec((batch, h, dix // h), ("batch", "heads", None), init="zeros"),
            "m": ParamSpec((batch, h), ("batch", "heads"), init="zeros"),
        }
    if lay.kind == SLSTM:
        return {k: ParamSpec((batch, dix), ("batch", "mlp"), init="zeros")
                for k in ("c", "n", "h", "m")}
    return None


# ---------------- apply ----------------

def _ffn_apply(p, x, cfg, lay, shard, seq_lens=None):
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if lay.has_moe:
        # var-len prefill: pads must not claim expert capacity — a real
        # token's routing is invariant to its admission bucket's padding
        valid = None if seq_lens is None else \
            (jnp.arange(x.shape[1])[None] < seq_lens[:, None])
        out, aux = moe_ffn(p["ffn"], h, k=cfg.experts_per_token,
                           dispatch=cfg.moe_dispatch, shard=shard,
                           valid=valid)
        return x + out, aux
    return x + mlp(p["ffn"], h, shard), 0.0


def sublayer_apply(p, x, cfg: ModelConfig, lay: SubLayer, shard, *,
                   mode: str, cache=None, pos=None, pos3=None, causal=True,
                   enc_out=None, lora=None, adapter_idx=None,
                   lora_impl: str = "gather", lora_seg=None, seq_lens=None,
                   prefix=None, prefix_len=None):
    """Apply one sublayer. mode: 'full' (train/prefill) or 'decode'.

    Returns (x, cache', aux_loss). cache' is None unless a cache was provided
    (prefill fills it; decode updates it).

    ``seq_lens``: (B,) per-row true lengths for right-padded variable-length
    prefill — pad keys are masked out of attention, pad K/V are zeroed before
    the cache write (so int8 admission scales see only real tokens), and the
    cache ``len`` is set per row instead of to the padded S.

    ``prefix``/``prefix_len``: chunked shared-prefix prefill — a dict(k, v)
    of precomputed (B, Sp, KV, hd) prefix K/V this sublayer's queries attend
    to IN FRONT of their own keys (dequantized shared pages; see
    ``attention.self_attention``). The cache fill below stores only the
    tail's K/V — the prefix already lives in the paged arena.
    """
    aux = 0.0
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)

    if lay.kind == ATTN:
        if mode == "decode":
            if pos is None:
                pos = cache["len"][:, None]                       # rope position
            if pos3 is None and cfg.mrope_sections is not None:
                pos3 = jnp.repeat(pos[..., None], 3, axis=-1)     # text: t=h=w
            out, attn_cache = attn.self_attention_decode(
                p["attn"], h, cache, cfg, shard, pos=pos, pos3=pos3,
                lora=lora, adapter_idx=adapter_idx, lora_impl=lora_impl,
                lora_seg=lora_seg)
            new_cache = dict(cache, **attn_cache)
        elif mode == "verify":
            # speculative verify window: T positions at absolute rope
            # positions len..len+T-1, through the paged pool
            if pos is None:
                pos = cache["len"][:, None] + jnp.arange(x.shape[1])[None]
            if pos3 is None and cfg.mrope_sections is not None:
                pos3 = jnp.repeat(pos[..., None], 3, axis=-1)     # text: t=h=w
            out, attn_cache = attn.self_attention_verify(
                p["attn"], h, cache, cfg, shard, pos=pos, pos3=pos3,
                lora=lora, adapter_idx=adapter_idx, lora_impl=lora_impl,
                lora_seg=lora_seg)
            new_cache = dict(cache, **attn_cache)
        else:
            out, (k, v) = attn.self_attention(
                p["attn"], h, cfg, shard, causal=causal, pos=pos, pos3=pos3,
                lora=lora, adapter_idx=adapter_idx, lora_impl=lora_impl,
                lora_seg=lora_seg, seq_lens=seq_lens, prefix=prefix,
                prefix_len=prefix_len)
            new_cache = None
            if cache is not None:  # prefill: fill the cache
                S = x.shape[1]
                new_cache = dict(cache)
                if seq_lens is not None:
                    # zero the pad positions' K/V: decode masks them via the
                    # per-row len anyway, but the int8 admission scales below
                    # are computed over the whole S axis
                    valid = (jnp.arange(S)[None] < seq_lens[:, None])
                    k = k * valid[..., None, None].astype(k.dtype)
                    v = v * valid[..., None, None].astype(v.dtype)
                if "k_scale" in cache:
                    # int8 pool admission: quantize the prompt's K/V once and
                    # fix the per-(batch, kv-head) scales for the decode steps
                    from repro.kernels import ops
                    kq, vq, ks, vs = ops.quantize_kv(k, v)
                    new_cache["k"] = jnp.zeros_like(cache["k"]).at[:, :S].set(kq)
                    new_cache["v"] = jnp.zeros_like(cache["v"]).at[:, :S].set(vq)
                    new_cache["k_scale"] = ks
                    new_cache["v_scale"] = vs
                else:
                    new_cache["k"] = jnp.zeros_like(cache["k"]).at[:, :S].set(
                        k.astype(cache["k"].dtype))
                    new_cache["v"] = jnp.zeros_like(cache["v"]).at[:, :S].set(
                        v.astype(cache["v"].dtype))
                new_cache["len"] = jnp.full_like(cache["len"], S) \
                    if seq_lens is None \
                    else seq_lens.astype(cache["len"].dtype)
        x = x + out
        if lay.has_cross:
            hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
            if mode == "decode":
                ck, cv = cache["ck"], cache["cv"]
                o = attn.decode_attention(
                    jnp.einsum("bsd,dhk->bshk", hx, p["cross"]["wq"].astype(hx.dtype))[:, 0],
                    ck, cv, jnp.full((hx.shape[0],), ck.shape[1], jnp.int32))
                x = x + attn.out_project(p["cross"], o[:, None], hx.dtype)
            else:
                # train/prefill: project encoder output to cross K/V here
                ck = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"].astype(hx.dtype))
                cv = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"].astype(hx.dtype))
                if new_cache is not None:  # prefill: persist for decode
                    new_cache["ck"] = ck.astype(cache["ck"].dtype)
                    new_cache["cv"] = cv.astype(cache["cv"].dtype)
                x = x + attn.cross_attention(p["cross"], hx, (ck, cv), cfg, shard)
        if lay.has_ffn:
            x, aux = _ffn_apply(p, x, cfg, lay, shard,
                                seq_lens=None if mode == "decode" else seq_lens)
        return x, new_cache, aux

    if lay.kind == MAMBA:
        if mode == "decode":
            out, (conv, ssm) = mam.mamba_decode(p["mamba"], h, cfg, shard,
                                                cache["conv"], cache["ssm"])
            new_cache = {"conv": conv, "ssm": ssm}
        else:
            out, (conv, ssm) = mam.mamba_forward(p["mamba"], h, cfg, shard,
                                                 seq_lens=seq_lens)
            new_cache = {"conv": conv, "ssm": ssm} if cache is not None else None
        x = x + out
        if lay.has_ffn:
            x, aux = _ffn_apply(p, x, cfg, lay, shard,
                                seq_lens=None if mode == "decode" else seq_lens)
        return x, new_cache, aux

    if lay.kind == MLSTM:
        out, state = xl.mlstm_forward(
            p["mlstm"], h, cfg, shard,
            state=cache if mode == "decode" else None,
            seq_lens=None if mode == "decode" else seq_lens)
        return x + out, (state if cache is not None else None), aux

    if lay.kind == SLSTM:
        out, state = xl.slstm_forward(
            p["slstm"], h, cfg, shard,
            state=cache if mode == "decode" else None,
            seq_lens=None if mode == "decode" else seq_lens)
        return x + out, (state if cache is not None else None), aux

    raise ValueError(f"unknown block kind {lay.kind}")
