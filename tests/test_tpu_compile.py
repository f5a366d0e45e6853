"""The serving kernels compile for a TPU v5e at stablelm-1.6b widths.

Interpret mode never checks what the chip's compiler does (block alignment,
VMEM use), so each kernel of the paged decode path is lowered and compiled
here for one chip of a described ``v5e:2x2`` topology: no chip is needed
and nothing runs. Shapes are the chip smoke's: 8 decode slots, 32 MHA heads
of 64, pages of 16 tokens, LoRA rank 4 in blocks of 16 tokens; the paged
decode kernel also compiles at the chat cell's pool (16 slots, 801 pages,
81 pages a slot) and at a GQA width, with the arena left where it is.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention_int8 import (decode_attention_int8,
                                                 quantize_kv)
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.segmented_lora import padded_tokens, segmented_lora

B, H, KV, HD, D = 8, 32, 32, 64, 2048     # slots, heads, kv heads, head dim
PS, MP = 16, 10                           # page size, pages per slot
P = 1 + B * MP                            # arena pages, trash page included
S = 160                                   # dense int8 cache length
BT, RANK, NA = 16, 4, 4                   # LoRA block, rank, adapter slots


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("window", [None, 64])
def test_paged_decode_attention_compiles(spec, window):
    hlo = _compile(
        lambda *a: paged_decode_attention(*a, window=window),
        spec((B, H, HD), jnp.bfloat16), spec((P, PS, KV * HD), jnp.int8),
        spec((P, PS, KV * HD), jnp.int8), spec((P, KV), jnp.float32),
        spec((P, KV), jnp.float32), spec((B, MP), jnp.int32),
        spec((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


# (query heads, kv heads, head dim): stablelm-1.6b's MHA, and a GQA width
@pytest.mark.parametrize("heads", [(32, 32, 64), (32, 8, 128)],
                         ids=["mha_hd64", "gqa_kv8_hd128"])
def test_paged_decode_attention_compiles_at_cell_shapes(spec, heads):
    """The chat cell's pool: 16 slots, 801 pages of 16, 81 pages a slot. The
    compiled call is the kernel, under the name the roofline reader matches
    in the trace, and nothing copies, transposes or prefetches the arena."""
    h, kv, hd = heads
    slots, pages, width = 16, 801, 81
    hlo = _compile(
        lambda *a: ops.paged_decode_attention(*a, backend="pallas"),
        spec((slots, h, hd), jnp.bfloat16),
        spec((pages, PS, kv * hd), jnp.int8),
        spec((pages, PS, kv * hd), jnp.int8),
        spec((pages, kv), jnp.float32), spec((pages, kv), jnp.float32),
        spec((slots, width), jnp.int32), spec((slots,), jnp.int32))
    kernels = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                         hlo)
    assert kernels and all(k.startswith("paged_decode_attention")
                           for k in kernels), kernels
    arena = re.findall(r"= \(?s8\[801,[^=]* ([a-z\-]+)\(", hlo)
    assert arena and set(arena) == {"parameter"}, arena


@pytest.mark.parametrize("tokens", [B, 8 * 32])   # a decode step, a pooled batch
def test_segmented_lora_compiles(spec, tokens):
    T = padded_tokens(tokens, min(tokens, NA + 2), BT)
    hlo = _compile(
        lambda *a: segmented_lora(*a, block_t=BT),
        spec((T, D), jnp.bfloat16), spec((T // BT,), jnp.int32),
        spec((NA, D, RANK), jnp.float32), spec((NA, RANK, H * HD), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_decode_attention_int8_compiles(spec):
    hlo = _compile(
        decode_attention_int8,
        spec((B, H, HD), jnp.bfloat16), spec((B, KV, S, HD), jnp.int8),
        spec((B, KV, S, HD), jnp.int8), spec((B, KV), jnp.float32),
        spec((B, KV), jnp.float32), spec((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_quantize_kv_compiles(spec):
    _compile(quantize_kv, spec((B, KV, S, HD), jnp.bfloat16),
             spec((B, KV, S, HD), jnp.bfloat16))
