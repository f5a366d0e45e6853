"""Operations and bytes of the served work of a dense transformer stack,
computed from shapes: the ``ops`` module of such a configuration
(``bench/spec.py`` gives the contract).

``m`` is a configuration's ``model`` dict (``configs/<config>.json``) and
``rank`` its LoRA rank. Counts are the matrix multiplications the model
needs (2 operations per multiply-add) for the tokens a request really
carries, not for the padding a bucket adds: what a user's request costs,
so that a share of the peak does not grow with waste.
"""
from __future__ import annotations


def _dims(m: dict):
    d, h = m["d_model"], m["num_heads"]
    kv = m.get("num_kv_heads", h)
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd, m["d_ff"], m["num_layers"]


def dense_per_token(m: dict, rank: int) -> float:
    """Operations per token outside attention's score and value products:
    projections, feed-forward and the q/v LoRA deltas, all layers."""
    d, h, kv, hd, f, L = _dims(m)
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    ffn = 3 * 2 * d * f
    lora = 2 * (d * rank + rank * h * hd) + 2 * (d * rank + rank * kv * hd)
    return L * (proj + ffn + lora)


def attn_pairs_ops(m: dict, pairs: float) -> float:
    """Score and value products over ``pairs`` (query, key) pairs, all
    layers and heads."""
    d, h, kv, hd, f, L = _dims(m)
    return L * 4 * h * hd * pairs


def head_ops(m: dict) -> float:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_ops(m: dict, rank: int, prompt: int) -> float:
    """Admission of a ``prompt``-token request: causal attention and the
    vocabulary head at the last position only."""
    pairs = prompt * (prompt + 1) / 2
    return prompt * dense_per_token(m, rank) + attn_pairs_ops(m, pairs) \
        + head_ops(m)


def decode_ops(m: dict, rank: int, prompt: int, tokens: int) -> float:
    """The ``tokens - 1`` decode steps after the first token (which the
    admission made); step ``i`` attends ``prompt + i`` keys."""
    steps = max(tokens - 1, 0)
    pairs = steps * prompt + steps * (steps + 1) / 2
    return steps * (dense_per_token(m, rank) + head_ops(m)) \
        + attn_pairs_ops(m, pairs)


def pooled_ops(m: dict, rank: int, rows: int, head_out: int) -> float:
    """One pooled request: the backbone over ``rows`` rows (causal unless
    the model is a representation model) and the task's linear head over
    the pooled feature."""
    causal = not m.get("is_representation", False)
    pairs = rows * (rows + 1) / 2 if causal else rows * rows
    return rows * dense_per_token(m, rank) + attn_pairs_ops(m, pairs) \
        + 2 * m["d_model"] * head_out


def paged_attn_bytes(m: dict, lengths, *, page_size: int, slots: int,
                     max_pages: int) -> float:
    """Bytes one decode step's paged attention references, all layers: the
    int8 K and V pages that cover each live stream's ``lengths`` (keys
    attended, the new token included), the gathered per-page scales (one
    ``(2, KV, max_pages)`` float32 block per slot), and the bfloat16 query
    and output rows of every slot."""
    d, h, kv, hd, f, L = _dims(m)
    pages = sum(-(-int(n) // page_size) for n in lengths)
    kv_bytes = pages * 2 * kv * page_size * hd
    scale_bytes = slots * 2 * kv * max_pages * 4
    qo_bytes = slots * 2 * h * hd * 2
    return L * (kv_bytes + scale_bytes + qo_bytes)
