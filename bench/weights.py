"""Seeded random weights for a dense transformer configuration, made on the
device in one jitted call, in float32 (the type the program keeps its
parameters in): the ``weights`` module of such a configuration
(``bench/spec.py`` gives the contract).

The benchmark makes the weights itself, and the reference makes them again
from the same seed after the program is gone: the reference takes nothing
the program made. The tree is the program's parameter layout (a decoder or
encoder stack scanned over ``num_layers``):

    {"layers": [{"ln1": (L, d), "attn": {"wq": (L, d, H, hd),
                 "wk"/"wv": (L, d, KV, hd), "wo": (L, H, hd, d)},
                 "ln2": (L, d), "ffn": {"wi_gate"/"wi_up": (L, d, f),
                 "wo": (L, f, d)}}],
     "final_norm": (d,), "embed": (V, d), "head": (d, V)}

(an encoder has no ``embed`` and a ``(d, d)`` ``head``, which its feature
pass does not use). Matrices are N(0, 1/fan_in), the token embedding
N(0, 1), norms one. Each task's LoRA adapter is ``{"q"|"v": {"a": (L, 1,
d, r), "b": (L, 1, r, out)}}`` with A ~ N(0, 1/d) and B ~ N(0, 1/(4r)), so
its delta is about half the size of the projection it adds to; each pooled
task's head is a ``(d, classes)`` N(0, 1/d) matrix. The heads come from a
fixed seed, the same in every run: the program's executor compiles a head's
matrix into the head's programs, so heads drawn from ``--seed`` would
compile anew in every run's set-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from all 64 bits of ``seed``."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def dims(m: dict) -> dict:
    h = m["num_heads"]
    return {"d": m["d_model"], "h": h, "kv": m.get("num_kv_heads", h),
            "hd": m.get("head_dim") or m["d_model"] // h, "f": m["d_ff"],
            "L": m["num_layers"], "V": m.get("vocab_size", 0)}


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def _backbone(key, g):
    d, h, kv, hd, f, L, V = (g[k] for k in ("d", "h", "kv", "hd", "f", "L",
                                            "V"))
    ks = iter(jax.random.split(key, 16))
    layer = {
        "ln1": jnp.ones((L, d), jnp.float32),
        "attn": {"wq": _normal(next(ks), (L, d, h, hd), d),
                 "wk": _normal(next(ks), (L, d, kv, hd), d),
                 "wv": _normal(next(ks), (L, d, kv, hd), d),
                 "wo": _normal(next(ks), (L, h, hd, d), h * hd)},
        "ln2": jnp.ones((L, d), jnp.float32),
        "ffn": {"wi_gate": _normal(next(ks), (L, d, f), d),
                "wi_up": _normal(next(ks), (L, d, f), d),
                "wo": _normal(next(ks), (L, f, d), f)},
    }
    p = {"layers": [layer], "final_norm": jnp.ones((d,), jnp.float32)}
    if V > 0:
        p["embed"] = jax.random.normal(next(ks), (V, d), jnp.float32)
        p["head"] = _normal(next(ks), (d, V), d)
    else:
        p["head"] = _normal(next(ks), (d, d), d)
    return p


def _adapter(key, g, rank):
    d, h, kv, hd, L = g["d"], g["h"], g["kv"], g["hd"], g["L"]
    ka, kb, kc, kd = jax.random.split(key, 4)
    b_std = 0.5 / np.sqrt(rank)
    return [{"q": {"a": _normal(ka, (L, 1, d, rank), d),
                   "b": jax.random.normal(kb, (L, 1, rank, h * hd)) * b_std},
             "v": {"a": _normal(kc, (L, 1, d, rank), d),
                   "b": jax.random.normal(kd, (L, 1, rank, kv * hd))
                   * b_std}}]


HEAD_SEED = 0x4EAD


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _make(key, head_key, g_items, rank, n_adapters, head_shape):
    g = dict(g_items)
    kb, ka = jax.random.split(key)
    kh = head_key
    params = _backbone(kb, g)
    adapters = [_adapter(k, g, rank)
                for k in jax.random.split(ka, max(n_adapters, 1))][:n_adapters]
    n_heads, classes = head_shape
    heads = [_normal(k, (g["d"], classes), g["d"])
             for k in jax.random.split(kh, max(n_heads, 1))][:n_heads]
    return params, adapters, heads


def make(seed: int, model: dict, *, rank: int, adapters: int, heads: int,
         classes: int):
    """(params, [adapter tree per task], [head matrix per pooled task]),
    all on the default device, from one compiled program."""
    g = tuple(sorted(dims(model).items()))
    return _make(seed_key(seed), seed_key(HEAD_SEED), g, rank, adapters,
                 (heads, classes))
