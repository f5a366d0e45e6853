"""Plain float32 reference of the served transformer stacks.

Written from the equations, in straightforward ``jax.numpy``, with every
product at ``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs
as one bfloat16 pass). It imports nothing of the program: it reads the
weights that ``bench/weights.py`` makes from the seed, by name. It is the
``reference`` module of a dense transformer configuration (``bench/spec.py``
gives the contract): ``token_scores`` and ``pooled_answer`` take the
configuration's ``model`` dict first.

Each layer, for input x (rows, d):

    h  = rmsnorm(x) * ln1                   rmsnorm(x) = x / sqrt(mean(x^2) + eps)
    q  = h Wq + (h Aq) Bq,  k = h Wk,  v = h Wv + (h Av) Bv     (task LoRA)
    q, k = rope(q), rope(k)                 rotate halves [x1, x2] of each head
                                            by pos / theta^(2i/hd)
    x  = x + softmax(q k^T / sqrt(hd) + mask) v Wo   (causal mask for a decoder)
    h2 = rmsnorm(x) * ln2
    x  = x + (silu(h2 Wg) * (h2 Wu)) Wd

then ``rmsnorm(x) * final_norm``. A decoder's logits are that times
``head``; a pooled request's answer is the mean of it over the rows times
the task's head matrix.

Departures from the published models, shared with the program: RMSNorm in
place of stablelm-2's LayerNorm, no q/k/v bias, rotary embedding over the
whole head (stablelm-2 rotates a quarter); for moment-large, rotary
positions in place of T5's relative position buckets and SwiGLU in place of
its feed-forward. The configuration files list them.

``precision`` selects the control: ``"f32"`` is the reference; ``"int8"``
rounds every weight matrix to int8 with one symmetric scale per output
channel (a weight-only int8 serving path); ``"fp8"`` rounds weights and
activations of every product to float8 e4m3 with one scale per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-5


def _q8(w, axes):
    """Symmetric int8 fake quantization of ``w``, one scale per slice that
    keeps the output axes (reduces over ``axes``)."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _f8(x):
    """float8 e4m3 rounding with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, precision, w_axes):
    """``einsum(spec, x, w)`` with the control's rounding applied."""
    if precision == "int8":
        w = _q8(w, w_axes)
    elif precision == "fp8":
        x, w = _f8(x), _f8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def rmsnorm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def rope(x, pos, theta):
    """x: (S, H, hd); pos: (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs       # (S, 1, hd/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def stack(params, adapter, x, *, causal: bool, theta: float,
          precision: str = "f32"):
    """The layer stack over one sequence ``x`` (S, d) float32 under one
    task's ``adapter`` (its tree from ``bench/weights.py``). Returns the
    final-normed hidden states (S, d)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    lay = params["layers"][0]
    ad = adapter[0]
    mask = jnp.tril(jnp.ones((S, S), bool)) if causal else \
        jnp.ones((S, S), bool)

    def body(x, p):
        w, a = p
        at, ff = w["attn"], w["ffn"]
        h = rmsnorm(x, w["ln1"])
        q = _mm("sd,dhk->shk", h, at["wq"], precision, (0,))
        k = _mm("sd,dhk->shk", h, at["wk"], precision, (0,))
        v = _mm("sd,dhk->shk", h, at["wv"], precision, (0,))
        dq = jnp.einsum("sr,ro->so", jnp.einsum("sd,dr->sr", h, a["q"]["a"][0],
                                                precision=HI),
                        a["q"]["b"][0], precision=HI)
        dv = jnp.einsum("sr,ro->so", jnp.einsum("sd,dr->sr", h, a["v"]["a"][0],
                                                precision=HI),
                        a["v"]["b"][0], precision=HI)
        q = q + dq.reshape(q.shape)
        v = v + dv.reshape(v.shape)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        g = q.shape[1] // k.shape[1]                 # grouped-query heads
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        x = x + _mm("shk,hkd->sd", o, at["wo"], precision, (0, 1))
        h2 = rmsnorm(x, w["ln2"])
        u = jax.nn.silu(_mm("sd,df->sf", h2, ff["wi_gate"], precision, (0,))) \
            * _mm("sd,df->sf", h2, ff["wi_up"], precision, (0,))
        return x + _mm("sf,fd->sd", u, ff["wo"], precision, (0,)), None

    x, _ = jax.lax.scan(body, x, (lay, ad))
    return rmsnorm(x, params["final_norm"])


def _theta(model: dict) -> float:
    return float(model.get("rope_theta", 10000.0))


def token_scores(model: dict, params, adapter, tokens, cand, *,
                 precision: str = "f32"):
    """A decoder over ``tokens`` (S,) int32. For each position i returns
    (logit max - logit of ``cand[i]``, argmax): how far the candidate lies
    below the best token, and which token that is. ``model`` is the
    configuration's ``model`` dict."""
    return _token_scores(params, adapter, tokens, cand, theta=_theta(model),
                         precision=precision)


def pooled_answer(model: dict, params, adapter, head, rows, *,
                  precision: str = "f32"):
    """A pooled request: the stack over ``rows`` (R, d), the mean over the
    rows, the task's head. Causal unless the model is a representation
    model."""
    return _pooled_answer(params, adapter, head, rows,
                          causal=not model.get("is_representation", False),
                          theta=_theta(model), precision=precision)


@functools.partial(jax.jit, static_argnames=("theta", "precision"))
def _token_scores(params, adapter, tokens, cand, *, theta: float,
                  precision: str):
    x = params["embed"][tokens]
    h = stack(params, adapter, x, causal=True, theta=theta,
              precision=precision)
    logits = _mm("sd,dv->sv", h, params["head"], precision, (0,))
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, cand[:, None], axis=-1)[:, 0]
    return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("causal", "theta", "precision"))
def _pooled_answer(params, adapter, head, rows, *, causal: bool,
                   theta: float, precision: str):
    h = stack(params, adapter, rows, causal=causal, theta=theta,
              precision=precision)
    return jnp.einsum("d,dc->c", jnp.mean(h, axis=0), head, precision=HI)
