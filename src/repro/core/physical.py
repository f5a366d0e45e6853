"""Physical FM: a loaded backbone + adapter/head stores + bucketed jit cache.

The real-execution plane (CPU-scale configs). A PhysicalFM owns:
  * backbone params (pure pytree) for one ``ModelConfig``;
  * an adapter store — LoRA A/B stacks keyed by adapter id, padded to a
    common rank AND to a slot bucket (4/8/16/...) so adding a task within
    capacity reuses the compiled executable instead of recompiling;
  * a decoder-head store — per-task heads applied after the shared pass;
  * a cache of jitted executables keyed on (batch bucket, adapter slot
    bucket) so TPU-style static shapes never recompile in steady state.

``run_batch`` picks the LoRA serve path per co-batch (``lora_impl="auto"``,
the server default): the measured ``AUTO_LORA_TABLE`` crossover chooses
between the segmented (SGMV) path — the adapter-sorted co-batch is flattened
token-major, permuted into block-padded segments (metadata built ONCE per
batch on the host via ``kernels.segmented_lora.segment_metadata``), and the
q/v deltas dispatch through the Pallas kernel (ref oracle on CPU) — and the
per-request gather-einsum path, which wins where block padding fragments
(e.g. large batches spread over many adapters). Explicit
``lora_impl="gather"``/``"segmented"`` overrides pin one path
(train / dry-run / parity testing / benchmarks).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.profile import FMProfile, profile_backbone
from repro.kernels.segmented_lora import SegmentMetaCache, padded_tokens
from repro.models import lm

BUCKETS = (1, 2, 4, 8, 16, 32)
SLOT_BUCKETS = (4, 8, 16, 32, 64)

# lora_impl="auto" crossover table, measured per (batch bucket, adapter
# count) cell from BENCH_serving.json#pooled (CPU backend): the per-cell
# winner between the gather-einsum path and the segmented SGMV kernel.
# Neither dominates: segmented amortizes when many tokens share an adapter
# (e.g. batch 32 / 1 adapter: 8.6ms vs 18.2ms gather) but its block padding
# loses when a large co-batch fragments across adapters (batch 32 / 4
# adapters: 16.4ms vs 9.8ms gather). Re-measure and update when the kernel
# or the backend changes; explicit lora_impl= overrides skip the table.
NA_BUCKETS = (1, 2, 4, 8, 16)
AUTO_LORA_TABLE = {
    (1, 1): "segmented", (1, 2): "gather", (1, 4): "segmented",
    (1, 8): "gather", (1, 16): "gather",
    (2, 1): "gather", (2, 2): "segmented", (2, 4): "gather",
    (2, 8): "segmented", (2, 16): "segmented",
    (4, 1): "segmented", (4, 2): "segmented", (4, 4): "segmented",
    (4, 8): "segmented", (4, 16): "segmented",
    (8, 1): "gather", (8, 2): "gather", (8, 4): "segmented",
    (8, 8): "gather", (8, 16): "segmented",
    (16, 1): "segmented", (16, 2): "gather", (16, 4): "gather",
    (16, 8): "segmented", (16, 16): "segmented",
    (32, 1): "segmented", (32, 2): "gather", (32, 4): "gather",
    (32, 8): "gather", (32, 16): "gather",
}
# adapter-id sentinel for rows that are padding / free decode slots; beyond
# any real slot index AND any slot bucket, so both LoRA paths zero it out.
# Shared with DecodeEngine so pad rows and free slots segment identically.
PAD_SENTINEL = 10**6


def bucket_for(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


def slot_bucket_for(n: int) -> int:
    for b in SLOT_BUCKETS:
        if n <= b:
            return b
    return SLOT_BUCKETS[-1]


class AdapterStore:
    """Backbone LoRA adapters of one physical FM, stacked for co-batching.

    Each entry is a full per-layer LoRA pytree (``models.lora`` layout, NA=1);
    ``stacked()`` maintains one NA=capacity() stack consumed by
    ``lm.forward(lora=..., adapter_idx=...)``. The stack is padded with
    zero-weight adapters up to the slot bucket, so (a) its shape — and hence
    the jitted executable — is stable while tasks come and go within
    capacity, and (b) the "no adapter" sentinel can never alias a real
    adapter slot: ``index()`` returns ``capacity()``, which both execution
    paths treat as "zero delta", and any stale in-between index lands on a
    zero-B pad slot whose delta is exactly zero anyway.

    The stack is cached incrementally: adding an adapter writes it into the
    next free pad slot of the existing stack (no re-concatenation); only
    removal or a capacity change invalidates the cache.
    """

    def __init__(self, cfg, rank: int = 16):
        from repro.models import lora as lora_mod
        self.cfg = cfg
        self.rank = rank
        self._mod = lora_mod
        self.ids: list[str] = []
        self._trees: list = []
        self._stacked = None
        self._stacked_n = 0        # how many real adapters the cache holds
        self._stacked_cap = 0      # slot capacity the cache was built for

    def __len__(self):
        return len(self.ids)

    def capacity(self) -> int:
        """Current slot-bucket capacity of the stacked representation."""
        return slot_bucket_for(max(1, len(self.ids)))

    def add(self, adapter_id: str, tree):
        if len(self.ids) >= SLOT_BUCKETS[-1]:
            # beyond the top bucket the capacity() sentinel would alias a
            # real slot and incremental writes would clamp out of bounds
            raise ValueError(
                f"adapter slots exhausted ({SLOT_BUCKETS[-1]}) on this FM; "
                "deploy another physical FM instance for more tasks")
        self.ids.append(adapter_id)
        self._trees.append(tree)
        if self._stacked is not None and self._stacked_cap != self.capacity():
            self._stacked = None   # crossed a slot bucket: full rebuild

    def new(self, adapter_id: str, seed: int = 0):
        tree = self._mod.init_single_adapter(
            jax.random.PRNGKey(seed), self.cfg, self.rank)
        self.add(adapter_id, tree)
        return tree

    def remove(self, adapter_id: str):
        """Idempotent: the server frees adapters on unbind, so callers that
        also remove explicitly (tests, manual lifecycle) must not fail."""
        if adapter_id not in self.ids:
            return
        i = self.ids.index(adapter_id)
        del self.ids[i], self._trees[i]
        self._stacked = None       # slots shift: precise full invalidation

    def index(self, adapter_id: Optional[str]) -> int:
        """Sentinel == capacity() (the stack's NA) means 'no adapter'."""
        if adapter_id in self.ids:
            return self.ids.index(adapter_id)
        return self.capacity()

    def _zero_tree(self):
        template = self._trees[0] if self._trees else \
            self._mod.init_single_adapter(jax.random.PRNGKey(0), self.cfg,
                                          self.rank)
        return jax.tree.map(jnp.zeros_like, template)

    def stacked(self):
        cap = self.capacity()
        n = len(self.ids)
        if self._stacked is not None and self._stacked_cap == cap:
            if self._stacked_n < n:
                # incremental: write the new adapters into their pad slots
                st = self._stacked
                for j in range(self._stacked_n, n):
                    tree = self._trees[j]
                    st = jax.tree.map(
                        lambda s, t: s.at[:, j].set(t[:, 0].astype(s.dtype)),
                        st, tree)
                self._stacked = st
                self._stacked_n = n
            return self._stacked
        zero = self._zero_tree()
        trees = self._trees + [zero] * (cap - n)
        self._stacked = self._mod.stack_adapters(trees) if len(trees) > 1 \
            else trees[0]
        self._stacked_n, self._stacked_cap = n, cap
        return self._stacked


class PhysicalFM:
    """One deployed backbone instance."""

    def __init__(self, cfg: ModelConfig, *, seed: int = 0, lora_rank: int = 16,
                 input_len: int = 32, lora_impl: str = "auto",
                 seg_block_t: int = 16):
        self.cfg = cfg
        self.input_len = input_len
        self.lora_impl = lora_impl
        self.seg_block_t = seg_block_t
        t0 = time.perf_counter()
        self.params = lm.init_model(jax.random.PRNGKey(seed), cfg)
        self.adapters = AdapterStore(cfg, lora_rank)
        self.heads: dict[str, Callable] = {}        # task_id -> head fn
        self._jit_cache: dict[tuple[int, int], Callable] = {}
        self.seg_meta_cache = SegmentMetaCache()    # per-composition host sort
        self.load_time_s = time.perf_counter() - t0
        self.profile: Optional[FMProfile] = None

    # ---- stores ----
    def attach_head(self, task_id: str, head_fn: Callable):
        self.heads[task_id] = head_fn

    def detach_task(self, task_id: str):
        self.heads.pop(task_id, None)

    # ---- execution ----
    def compile_count(self) -> int:
        """Total jitted executables across all bucket keys (steady-state
        serving must not grow this when tasks are added within capacity)."""
        return sum(f._cache_size() for f in self._jit_cache.values())

    def resolve_lora_impl(self, rows: int, num_adapters: Optional[int] = None
                          ) -> str:
        """The LoRA execution path for a ``rows``-request co-batch.

        ``lora_impl="auto"`` consults ``AUTO_LORA_TABLE`` at (batch bucket,
        adapter-count bucket); explicit "gather"/"segmented" pass through.
        ``num_adapters`` defaults to the store's registered count — callers
        with a bucketed jit key (the decode engine) pass their slot bucket
        instead so the resolution can't flip within a compiled key."""
        if self.lora_impl != "auto":
            return self.lora_impl
        na = len(self.adapters) if num_adapters is None else num_adapters
        nb = next((b for b in NA_BUCKETS if max(1, na) <= b), NA_BUCKETS[-1])
        return AUTO_LORA_TABLE[(bucket_for(rows), nb)]

    def _features_fn(self, bucket: int, slots: int, impl: str):
        """Shared backbone forward with per-request backbone LoRA deltas,
        jitted per (batch bucket, adapter slot bucket, lora impl)."""
        key = (bucket, slots, impl)
        if key not in self._jit_cache:
            cfg, bt = self.cfg, self.seg_block_t

            @jax.jit
            def pooled_features(params, embeds, lora_stack, adapter_idx, perm,
                                inv, blocks):
                seg = None
                if impl == "segmented":
                    seg = {"perm": perm, "inv": inv, "block_adapter": blocks,
                           "block_t": bt}
                if cfg.is_encoder_decoder:
                    # audio-style backbone: stub frames go to the encoder; the
                    # decoder runs over a BOS-only token stream
                    toks = jnp.zeros(embeds.shape[:2], jnp.int32)
                    feats, _, _ = lm.forward(params, cfg, tokens=toks,
                                             enc_embeds=embeds, lora=lora_stack,
                                             adapter_idx=adapter_idx,
                                             lora_impl=impl, lora_seg=seg)
                else:
                    feats, _, _ = lm.forward(params, cfg, embeds=embeds,
                                             lora=lora_stack,
                                             adapter_idx=adapter_idx,
                                             lora_impl=impl, lora_seg=seg)
                return feats.mean(axis=1)                      # (B, d) pooled

            self._jit_cache[key] = pooled_features
        return self._jit_cache[key]

    def segment_meta(self, adapter_idx: np.ndarray, cap: int, seq_len: int):
        """Per-batch SGMV metadata (host side, built once per co-batch
        *composition* — ``seg_meta_cache`` memoizes repeats, so steady-state
        serving and every step of a decode co-batch skip the host sort).

        Shapes depend only on (batch bucket, slot bucket, seq_len, block_t)
        — all static per jit-cache key — so steady state never recompiles."""
        b = len(adapter_idx)
        bt = self.seg_block_t
        # worst case: every distinct adapter plus the two sentinels ("no
        # adapter" == cap and batch padding) opens a block-padded segment
        max_segs = min(b, cap + 2)
        tp = padded_tokens(b * seq_len, max_segs, bt)
        ids = np.repeat(np.asarray(adapter_idx, np.int32), seq_len) \
            if seq_len != 1 else np.asarray(adapter_idx, np.int32)
        return self.seg_meta_cache.get(ids, cap, bt, tp)

    def run_batch_device(self, embeds, adapter_idx: np.ndarray):
        """Device-resident serve forward: like ``run_batch`` but returns the
        pooled features as a jax array (no host pull) so per-task heads can
        run on-device (see ``Executor``). The array keeps its batch-bucket
        padding: rows ``[0, n)`` are the batch, the rest are pad rows.
        Trimming on the device would be an eager program per (bucket, n)
        pair, compiled the first time serving forms that size."""
        n = embeds.shape[0]
        if n > BUCKETS[-1]:            # oversize co-batch: serve in chunks
            c = BUCKETS[-1]            # (all full but the last: row i stays i)
            return jnp.concatenate(
                [self.run_batch_device(embeds[i:i + c], adapter_idx[i:i + c])
                 for i in range(0, n, c)])
        b = bucket_for(n)
        pad = b - n
        if pad:
            embeds = np.concatenate([embeds, np.zeros((pad,) + embeds.shape[1:],
                                                      embeds.dtype)])
            adapter_idx = np.concatenate(
                [adapter_idx, np.full((pad,), PAD_SENTINEL, np.int32)])
        stack = self.adapters.stacked()
        cap = self.adapters.capacity()
        impl = self.resolve_lora_impl(b)
        if impl == "segmented":
            perm, inv, blocks = self.segment_meta(
                np.asarray(adapter_idx), cap, embeds.shape[1])
        else:   # gather path never reads the metadata; pass static dummies
            perm = inv = blocks = np.zeros((1,), np.int32)
        out = self._features_fn(b, cap, impl)(
            self.params, jnp.asarray(embeds), stack,
            jnp.asarray(adapter_idx, jnp.int32), jnp.asarray(perm),
            jnp.asarray(inv), jnp.asarray(blocks))
        return out

    def run_batch(self, embeds: np.ndarray, adapter_idx: np.ndarray):
        """embeds: (n, S, d); adapter_idx: (n,). Returns (n, d) features.
        Pads to the next batch bucket (and the adapter stack to its slot
        bucket) so steady-state serving never recompiles."""
        n = embeds.shape[0]
        return np.asarray(self.run_batch_device(embeds, adapter_idx))[:n]

    def calibrate(self, sizes=(1, 2, 4, 8, 16)) -> FMProfile:
        d = self.cfg.d_model
        rng = np.random.RandomState(0)

        def run(b):
            e = rng.randn(b, self.input_len, d).astype(np.float32)
            self.run_batch(e, np.zeros((b,), np.int32))

        self.profile = profile_backbone(run, sizes=sizes, name=self.cfg.name)
        self.profile.load_time_s = self.load_time_s
        self.profile.memory_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.params))
        return self.profile
