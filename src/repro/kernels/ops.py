"""Backend-dispatching jit wrappers for the Pallas kernels.

``backend="auto"`` uses the Pallas TPU kernels on TPU and the pure-jnp
oracles elsewhere (CPU tests check the kernels in the Pallas interpreter and
compile them for a described TPU). Layout adapters translate between the
model-internal (B, S, H, hd) convention and the head-major layout of the
dense kernels. The paged pool's arena needs none: the paged kernel reads it
as the pool stores it, ``(num_pages, page_size, KV * hd)`` int8 with the
trash page 0 (see ``kernels.paged_decode_attention``), and the XLA paths
gather pages from that layout first and reshape only the gathered block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.decode_attention_int8 import \
    decode_attention_int8 as _decode_int8_pallas
from repro.kernels.decode_attention_int8 import quantize_kv as _quantize_kv
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.paged_decode_attention import \
    paged_decode_attention as _paged_decode_pallas
from repro.kernels.segmented_lora import segmented_lora as _sgmv_pallas

# module-level default, overridable per call
BACKEND = "auto"


def _resolve(backend: Optional[str]) -> str:
    b = backend or BACKEND
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return b


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    backend: Optional[str] = None, interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H, hd)."""
    b = _resolve(backend)
    if b == "pallas":
        o = _flash_pallas(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal, window=window,
                          interpret=interpret)
        return o.transpose(0, 2, 1, 3)
    from repro.models.attention import flash_attention as jnp_flash
    return jnp_flash(q, k, v, causal=causal, window=window)


def decode_attention(q, k_cache, v_cache, lengths, *, window: Optional[int] = None,
                     backend: Optional[str] = None, interpret: bool = False):
    """q: (B, H, hd); caches: (B, S, KV, hd); lengths: (B,) -> (B, H, hd)."""
    b = _resolve(backend)
    if b == "pallas":
        return _decode_pallas(q, k_cache.transpose(0, 2, 1, 3),
                              v_cache.transpose(0, 2, 1, 3), lengths,
                              window=window, interpret=interpret)
    from repro.models.attention import decode_attention as jnp_decode
    return jnp_decode(q, k_cache, v_cache, lengths, window=window)


def quantize_kv(k, v):
    """Symmetric per-(batch, kv-head) int8 KV quantization, model layout.

    k, v: (B, S, KV, hd) float -> (k_q, v_q (B, S, KV, hd) int8,
    k_scale, v_scale (B, KV) f32). Thin layout adapter over
    ``kernels.decode_attention_int8.quantize_kv`` (head-major)."""
    kq, vq, ks, vs = _quantize_kv(k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3))
    return kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3), ks, vs


def decode_attention_int8(q, k_q, v_q, k_scale, v_scale, lengths, *,
                          window: Optional[int] = None,
                          backend: Optional[str] = None,
                          interpret: bool = False):
    """int8-KV decode attention, model layout.

    q: (B, H, hd); k_q/v_q: (B, S, KV, hd) int8; k_scale/v_scale: (B, KV);
    lengths: (B,) -> (B, H, hd). HBM only ever streams int8 on the Pallas
    path; the CPU oracle dequantizes then reuses the f32 decode reference."""
    b = _resolve(backend)
    kh = k_q.transpose(0, 2, 1, 3)
    vh = v_q.transpose(0, 2, 1, 3)
    if b == "pallas":
        return _decode_int8_pallas(q, kh, vh, k_scale, v_scale, lengths,
                                   window=window, interpret=interpret)
    return ref.decode_attention_int8_ref(q, kh, vh, k_scale, v_scale, lengths,
                                         window=window)


def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale, page_table,
                           lengths, *, window: Optional[int] = None,
                           backend: Optional[str] = None,
                           interpret: bool = False):
    """Paged int8-KV decode attention over the arena as the pool stores it.

    q: (B, H, hd); k_pages/v_pages: (num_pages, ps, KV * hd) int8 arena;
    k_scale/v_scale: (num_pages, KV) per-page scales; page_table:
    (B, max_pages) int32 — rows of DIFFERENT streams may reference the same
    physical page (copy-on-write prefix sharing maps shared prompt pages
    into several tables; reads only, so no kernel change); lengths: (B,)
    -> (B, H, hd).

    The Pallas path hands the arena to the kernel untouched: it DMAs whole
    pages through the scalar-prefetched table, only up to each stream's
    length, and a row whose first entry is the trash page 0 (a free slot)
    reads zeros. The CPU oracle gathers with jnp, whatever the rows hold,
    then reuses the f32 decode reference."""
    b = _resolve(backend)
    if b == "pallas":
        return _paged_decode_pallas(q, k_pages, v_pages, k_scale, v_scale,
                                    page_table, lengths, window=window,
                                    interpret=interpret)
    # XLA path: gather from the arena FIRST (the gathered (B, MP, ps, KV, hd)
    # block is per-request-sized), dequant with the per-page scales, then
    # transpose only the gathered block into the head-major layout the f32
    # decode reference wants — never the whole arena. This keeps the
    # per-step cost over the dense int8 path to one gather + one small
    # transpose (~10% at the serving shapes, see
    # BENCH_serving.json#paged.step_parity).
    B, MP = page_table.shape
    _, ps, _ = k_pages.shape
    KV, hd = k_scale.shape[1], q.shape[-1]

    def gathered(pages, scale):
        g = pages[page_table].reshape(B, MP, ps, KV, hd).astype(jnp.float32)
        g = g * scale[page_table][:, :, None, :, None]
        return g.transpose(0, 3, 1, 2, 4).reshape(B, KV, MP * ps, hd)

    return ref.decode_attention_ref(q, gathered(k_pages, k_scale),
                                    gathered(v_pages, v_scale), lengths,
                                    window=window)


def paged_verify_attention(q, k_pages, v_pages, k_scale, v_scale, page_table,
                           base_len, *, window: Optional[int] = None,
                           backend: Optional[str] = None,
                           interpret: bool = False):
    """Multi-position paged attention for the speculative verify window.

    q: (B, T, H, hd) — the T = k+1 window positions' queries; the arena,
    scales and table as ``paged_decode_attention`` takes them; window position
    j sits at absolute position ``base_len + j`` and therefore attends
    ``base_len + j + 1`` keys. All T positions' K/V must already be written
    into the arena: codes at positions a query must not see are gathered,
    dequantized and then MASKED out by the per-position length — exactly how
    the single-token path treats a fresh page's garbage tail — so position j
    reads bit-identically to a sequential decode step at length
    ``base_len + j + 1``. Returns (B, T, H, hd).

    The XLA path pays the per-request KV gather ONCE and shares it across
    all T window positions — at serving context lengths the gather
    dominates a decode step, so a verify window costs close to one step
    instead of T (this is what buys the speculative plane its speedup; see
    BENCH_serving.json#spec). Per-position masking reproduces the
    single-token math: position j's score row masks keys at or past
    ``base_len + j + 1`` with the same NEG_INF + softmax treatment the
    decode reference uses, so only matmul batching (an invariance the
    chunked-prefill plane already relies on) separates it from T unrolled
    single-token calls. The Pallas backend makes T single-token kernel calls,
    each reading the arena in place — correct everywhere, fused later."""
    T = q.shape[1]
    b = _resolve(backend)
    if b == "pallas":
        outs = [paged_decode_attention(q[:, j], k_pages, v_pages, k_scale,
                                       v_scale, page_table, base_len + j + 1,
                                       window=window, backend=backend,
                                       interpret=interpret)
                for j in range(T)]
        return jnp.stack(outs, axis=1)
    B, MP = page_table.shape
    _, ps, _ = k_pages.shape
    H, hd = q.shape[2:]
    KV = k_scale.shape[1]
    G = H // KV
    S = MP * ps

    def gathered(pages, scale):
        g = pages[page_table].reshape(B, MP, ps, KV, hd).astype(jnp.float32)
        g = g * scale[page_table][:, :, None, :, None]
        return g.transpose(0, 3, 1, 2, 4).reshape(B, KV, S, hd)

    kf = gathered(k_pages, k_scale)
    vf = gathered(v_pages, v_scale)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = q.reshape(B, T, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("btkgd,bksd->bkgts", qf, kf) * scale
    lens = base_len[:, None] + 1 + jnp.arange(T)[None]        # (B, T)
    pos = jnp.arange(S)
    mask = pos[None, None] < lens[..., None]                  # (B, T, S)
    if window is not None:
        mask &= pos[None, None] >= (lens[..., None] - window)
    s = jnp.where(mask[:, None, None], s, ref.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bksd->btkgd", p, vf)
    return o.reshape(B, T, H, hd).astype(q.dtype)


def gather_prefix_kv(k_pages, v_pages, k_scale, v_scale, page_table):
    """Dequantized prefix K/V gather, model layout (chunked prefill).

    k_pages/v_pages: (num_pages, ps, KV * hd) int8 arena; k_scale/v_scale:
    (num_pages, KV) per-page scales; page_table: (B, P) int32 prefix pages
    (positions past a row's true prefix length may point at the trash page —
    the attention mask is responsible for hiding them). Returns float32
    (k, v), each (B, P * ps, KV, hd), ready to feed
    ``models.attention.flash_attention(prefix_k=..., prefix_v=...)``.

    Pure-jnp on every backend: the gathered block is per-request-sized (a
    handful of prefix pages), so there is nothing for a Pallas kernel to win
    here — the arena is never transposed wholesale."""
    B, P = page_table.shape
    _, ps, W = k_pages.shape
    KV = k_scale.shape[1]
    hd = W // KV

    def gather(pages, scale):
        g = pages[page_table].reshape(B, P, ps, KV, hd).astype(jnp.float32)
        g = g * scale[page_table][:, :, None, :, None]
        return g.reshape(B, P * ps, KV, hd)

    return gather(k_pages, k_scale), gather(v_pages, v_scale)


def segmented_lora(x, block_adapter, a_w, b_w, *, block_t: int = 128,
                   backend: Optional[str] = None, interpret: bool = False):
    """x: (T, d) adapter-sorted; b_w: (NA, r, out) -> LoRA delta (T, out)."""
    b = _resolve(backend)
    if b == "pallas":
        return _sgmv_pallas(x, block_adapter, a_w, b_w, block_t=block_t,
                            interpret=interpret)
    return ref.segmented_lora_ref(x, block_adapter, a_w, b_w, block_t)
