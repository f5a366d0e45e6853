"""Continuous-batching decode engine: segmented-LoRA token serving over a
persistent int8 KV pool — dense slot-contiguous or block-paged.

Autoregressive serving is where FMplex's co-location wins compound: every
decode step re-uses the shared backbone across all co-resident tasks, so the
per-step cost of multi-task isolation must be ~zero. The engine owns:

  * a **slot pool** — a fixed, bucketed number of decode slots backed by one
    persistent KV cache allocated ONCE. Two layouts:

      - *dense* (``paged=False``): ``lm.init_cache(kv_quant=True)`` — one
        contiguous ``(num_slots, s_max)`` int8 region per slot with
        per-(slot, kv-head) scales fixed at prefill admission
        (``kernels.decode_attention_int8``). Every stream RESERVES its
        worst-case length, so the slot count — not memory — caps colocation.
      - *paged* (``paged=True``): one global arena of ``total_pages``
        fixed-size pages (int8 K/V rows of ``kv * head_dim`` + per-(page,
        kv-head) scales, ``page_size`` tokens each) shared by every slot,
        addressed through a device-resident per-slot page table. The Pallas
        kernel DMAs a stream's whole pages through the table, only up to its
        length (``kernels.paged_decode_attention``; jnp gather oracle on
        CPU). Page 0 is the reserved trash page: free slots keep stepping
        (static shapes) and their garbage writes land there, never in a live
        stream's pages; the kernel reads a row whose first entry is page 0
        as empty.

    **Cache-manager plane (``core.cache_manager``) — the cache contract is
    per-SUBLAYER, not per-engine.** ``CachePlan.for_config`` walks the
    stack's period layout and declares, for every sublayer, where its
    serving state lives:

      - *attention*: growing K/V — the paged int8 arena (or the dense int8
        region) described above;
      - *recurrent* (mamba conv+SSM, mLSTM/sLSTM state): FIXED-SIZE per-slot
        state tensors riding in the same pool list (batch axis == slot),
        written at admission by the same scatter and advanced in place by
        the decode scan — nothing grows, nothing pages;
      - *encoder-decoder cross-attention*: per-slot encoder-output K/V
        sidecars (``ck``/``cv``, ``enc_len`` frames each), computed once at
        admission from the join's ``enc_feats`` and read-only thereafter.

    One lifecycle composes them: admit / decode / retire / preempt / cancel
    / quarantine / snapshot all route through the same slot machinery, with
    a ``StateSlotPool`` tracking the fixed-size side (strict alloc at
    admission, free on every exit path, occupancy + deferral gauges beside
    the page gauges; ``can_admit`` counts state slots for hybrid stacks,
    not just pages). Admission prefill is variable-length for EVERY stack:
    the recurrent scans are length-aware (``dt`` zeroed / state carried
    through right-pad positions), so hybrids share the bucketed right-pad
    path and its zero-recompile guarantees.

    Capability negotiation is explicit — planes whose mechanics are
    attention-only DEMOTE cleanly instead of crashing mid-serve:

      - prefix sharing + chunked prefill: shared pages capture attention KV
        only; recurrent state at the prefix boundary is stream-private, so
        hybrid joins admit plain (per-stream pages, full prefill);
      - speculative decode (``spec_k``): rollback is a length/tracker reset
        on paged KV; recurrent state cannot rewind past rejected drafts —
        ``spec_k > 0`` demotes to plain decode with a warning;
      - spill-resume: the stream spill captures pages + trackers only, so
        hybrid preemption uses the lossless fold-and-re-prefill path (which
        recomputes recurrent state exactly); snapshot/restore instead
        captures the dense state wholesale (``capture_dense_state``).

    **Paged page lifecycle — refcounted ownership + copy-on-write prefix
    sharing.** Every usable page carries a reference count; a page is owned
    by the free list exactly when its refcount is zero, and by one or more
    page-table mappings otherwise. The lifecycle:

      * *allocate* (``_take_pages``): pop from the free list, refcount 1.
      * *share* (``_share_pages``): a joining stream whose prompt starts
        with a prefix another stream already admitted MAPS that stream's
        pages into its own page table instead of copying them — the prefix
        registry (indexed by a chained sha256 digest over the adapter
        identity and the leading token bytes, one entry per full page of a
        registered prompt) resolves the
        longest page-aligned shared prefix, and each mapped page's refcount
        increments. Only pages wholly covered by prompt tokens are ever
        registered, and decode writes only ever land at positions at or
        beyond the stream's true prompt length — so shared pages are
        IMMUTABLE and the read path (the paged attention kernel) needs no
        change. The first divergent or partial page is the copy-on-write
        boundary: shared positions before it are mapped, everything from it
        on (including the partial boundary page itself, recomputed into a
        PRIVATE copy) lands in freshly allocated pages.
      * *release* (``_release_pages``; retire / preempt / bucket-trim all
        route through it): decrement, and only a refcount that reaches zero
        returns the page to the free list (and drops its registry entry).
        Preempting or retiring one sharer therefore never invalidates
        another sharer's mapped pages.

    Admission quantizes the prompt's K/V **per (page, kv-head)**: a page's
    scale is a pure function of the tokens it covers, so a shared page's
    int8 codes and scales are bit-identical to what the joining stream's
    own prefill would have written — sharing is exact, not approximate, and
    a sharer's token stream matches the unshared engine token for token.
    One exception keeps decode sane: the prompt/decode BOUNDARY page (the
    partial page decode keeps appending into — never shared, sharing stops
    at the last full page) is stamped at the slot-wide admission scale, so
    a few small-magnitude prompt tokens in it cannot clip the stream's
    normal-range decode K/V. Decode appends quantize into the slot's
    admission-era running scale for the first token of each fresh page
    (stamping it as the page scale) and into the page's stamped scale
    thereafter, so a recycled page's stale scale can never leak into a new
    owner.

    **Chunked shared-prefix admission (two-phase: map, then tail-compute).**
    A prefix hit saves COMPUTE as well as memory: when ``chunked_prefill``
    is on (the default) an admission whose prompt maps >= 1 registered or
    spill-restorable page runs the prefill ONLY over its private tail. The
    *map phase* increments the shared pages' refcounts (restoring spilled
    ones H2D first); the *tail-compute phase* feeds the tail tokens through
    ``_tail_prefill_fn`` with the mapped pages' int8 content dequantized
    per page (``kernels.ops.gather_prefix_kv``) riding in front of the
    tail's own fresh K/V inside every attention sublayer — absolute RoPE
    positions, causality and pad masking all offset by the prefix length.
    Tail lengths bucket separately (powers of two of the page size, a
    static jit key) so sharer churn with any mix of tail lengths stays
    zero-recompile; the prefix page vector, prefix length and true tail
    length are traced operands. The tail-page scatter quantizes the tail
    from its FLOAT cache exactly like the full path, and folds the mapped
    pages' stamped scales into the slot-wide running scale — bit-identical
    to the slot scale a full prefill would have computed. A quarantined
    tail rolls the map phase back (refcounts drop, nothing registered, the
    spill entries survive). The full prefill remains the fallback whenever
    nothing is shareable or free pages cannot cover restores + tail bucket,
    and is always correct. ``tail_tokens_computed``/``prefill_tokens_saved``
    count the split; ``admitted_log`` carries per-admission tail tokens so
    fair-share schedulers charge the work actually done.

    Admission prefill scatters the prompt's private tail into freshly
    allocated pages, decode appends a page on demand (the host allocator
    tops slots up to ``len + chunk`` tokens before each chunk), and retire
    releases — so concurrency is bounded by TOTAL *deduplicated* TOKENS IN
    FLIGHT: co-resident streams carrying the same system prompt pay for it
    once, not once per stream; and prefix-hit TTFT drops with the tail
    fraction (see ``BENCH_serving.json#prefix.ttft``).

  * **admission prefill** — a joining request's prompt runs a single jitted
    prefill (LoRA applied, K/V quantized in-graph) and is scattered into its
    slot (dense: one ``dynamic_update_slice`` per cache leaf; paged: a page
    scatter into the allocated page ids, shared positions pointed at the
    trash page). Admission is **variable-length**: prompts are right-padded
    to the smallest of 2-3 *prompt-length buckets* (a static jit-cache
    key), while the TRUE length rides along as a traced operand — pad keys
    are masked out of attention, the cache ``len`` is per-row exact, and
    the first token comes from the last REAL prompt position. On a full
    pool, a paged ``join`` **defers** (pending queue drained as slots and
    pages free up) instead of raising — a burst of admissions beyond
    capacity queues and drains across chunks; the dense layout keeps the
    historical raise. The pending queue drains mostly-FIFO with a bounded
    lookahead (``pending_lookahead``): a small prompt may admit past a
    large head that free pages cannot yet cover, but only
    ``hol_skip_cap`` times in a row — then the head regains strict
    priority, so skip-ahead cannot starve it.

  * **chunked decode** — ``step_chunk`` advances ALL occupied slots ``chunk``
    tokens under one jitted ``lax.scan`` (device-resident sampling: one
    dispatch and one host sync per chunk, not per token), greedy by default
    with per-slot PRNG key state for temperature/top-k sampling. If the free
    list cannot cover a live stream's next chunk, the youngest live stream is
    **preempted**: its pages return to the pool and it re-queues with its
    generated prefix folded into the prompt (re-admission also refreshes its
    int8 scales). Memory-aware loop admission (``ServeLoop``) keeps a chunk
    of decode headroom per admit precisely so this path stays rare.

  * **cached SGMV metadata** — segment metadata for the S=1 token co-batch is
    built once per batch *composition* and reused every step; steady-state
    decode performs zero host-side sorts and zero recompiles: jits stay keyed
    on (slot bucket, adapter slot bucket, chunk) and
    (adapter slot bucket, prompt bucket) — page tables, true lengths and page
    ids are all TRACED operands, so join/leave churn and page allocation
    never retrace. The LoRA path per jit key follows
    ``PhysicalFM.resolve_lora_impl`` (gather vs segmented crossover;
    ``lora_impl="auto"`` is the server default).

int8 KV scale drift: dense-pool quantization scales are fixed ONCE at prefill
admission; decode-era K/V whose magnitude outgrows the prompt-era range are
clipped to ±127·scale and the dense engine never rescales a live slot. The
divergence this introduces is bounded and grows slowly with decode length:
empirically (``tests/test_decode_engine.py::test_int8_scale_drift_bounded``)
a decode tail 3× longer than the prompt whose K/V magnitude drifts to 3× the
admission-scale range keeps attention-output relative divergence under ~0.8
(vs ~0.06 with no drift), and at the model level a decode 4× the prompt
length keeps logit relative divergence under 0.5. The PAGED pool refreshes
**proactively**: the decode step tracks each slot's running decode-era
|K|/|V| maxima in the pool (``k_max``/``v_max``, traced — no extra
compiles), and when a slot's observed maximum exceeds
``scale_refresh`` × its admission range the engine re-quantizes the slot's
current tail page in place (codes rescaled old→new scale, both per-page and
slot running scales bumped, ``scale_refreshes`` counted) so SUBSEQUENT
tokens quantize into the drifted range instead of clipping against the
prompt-era one. The refresh bounds the FUTURE, not the past: codes clipped
before the drift first crossed the threshold stay clipped (int8 cannot be
un-clipped), so a drifting stream converges to the refreshed-layout bound
(~no-drift tolerance, see ``test_int8_scale_drift_bounded``) rather than
holding it from the first drifted token. Shared prefix pages are never
refresh targets (the tail page is always private). Dense decodes far beyond a ``max_new`` of a few
hundred tokens should either re-admit (prefill on the generated prefix
refreshes scales — the paged preemption path does exactly this) or use
``kv_quant=False``.

**Failure semantics.** Every stream leaves the engine through ``leave`` (the
single retire path: slot freed, pages refcount-released, registry entries
dropped when their last reference goes) with a terminal ``DecodeSlot.status``
(``core.request`` statuses); deferred joins that never reach a slot leave
through the ``rejected`` list instead. The exit paths:

  * ``ok`` — budget reached or EOS. Pages freed at retire; nothing refunded
    (the stream's chunks were real device work).
  * ``quarantined`` — the in-graph per-slot finite-logits flag (AND-reduced
    across the chunk inside the decode ``lax.scan``, synced with the chunk's
    tokens: zero extra D2H round trips, no new jit keys — the same pattern
    as the scale-drift flag) came back False, or the admission prefill's
    logits were non-finite. A poisoned stream (NaN'd adapter, Inf
    activations) retires at the END of its chunk; co-batched rows are
    per-slot independent (attention, LoRA and sampling are all row-local),
    so their token streams are bit-identical to a fault-free run. A
    quarantined ADMISSION never allocates pages, never writes the pool and
    never registers its prefix — NaN K/V cannot enter the COW registry.
  * ``deadline_cancelled`` — a live slot (or a preempted resume entry) ran
    past ``DecodeSlot.deadline``; marked done on chunk entry and retired
    through the normal sweep, partial tokens preserved.
  * ``deadline_shed`` — a deferred join expired in the pending queue before
    ever being admitted; no pages were held, nothing to free.
  * ``rejected_stranded`` — a stranded deferred join (its shared-prefix
    discount was released and it can never fit, see ``_viable_pending``)
    past its deadline, or force-shed by the serve loop's wedge recovery.
    Stranded entries WITHOUT a deadline still idle (a later re-registration
    can unstrand them); only a fully wedged engine raises.
  * ``cancelled`` — ``cancel(rid)`` unwound the stream wherever it lived:
    live slot (retired via ``leave``, pages freed), pending entry (popped,
    nothing held), or preempted resume (popped, pages already freed at
    preemption).

Admissions are recorded in ``admitted_log`` (drained by
``ServeLoop.take_admitted``-style callers) so schedulers can charge prompt
tokens when the prefill ACTUALLY runs — a request cancelled or shed while
deferred was never charged and cannot distort fair shares.

**Durability plane** (paged pool with ``spill_bytes > 0``): device-state
loss is allowed to cost time, never tokens. Three paths move KV state
across the device boundary, all digest-guarded:

  * *spill on eviction* — a preemption victim's pages, per-page scales,
    running drift trackers, last token and PRNG key are captured D2H into
    the bounded host arena (``core.spill.HostSpillArena``, LRU by bytes)
    before release; a registered prefix whose LAST sharer releases spills
    its pages the same way (keyed by the chained prefix digest) instead of
    evaporating. Over-budget entries are SKIPPED, not force-fit — the spill
    tier is an accelerator, losing it only costs a re-prefill.
  * *restore on re-entry* — a deferred resume whose spill entry survived
    restores by H2D page write-back (no re-prefill, exact token AND
    sampling parity: ``spill_resumes``/``resume_costs``); a joining prompt
    whose prefix chain lives only in the spill arena restores those pages
    and re-registers them (``spill_prefix_hits``). The pending gate sizes
    a spill-backed resume by its TRUE restored page count (spill-entry
    meta), not its admission bucket, so the restore and its re-prefill
    fallback are both viable at admission time.
  * *snapshot/restore* — ``snapshot()`` captures the engine's full logical
    state (used pages D2H, slots, pending, registry, counters, PRNG keys)
    with a sha256 digest per page; ``restore()`` rebuilds a FRESH arena
    from it, verifying every page digest. ``reuse_jits_from`` shares the
    dead engine's jit caches (executables are code, not device state) so
    an in-process device reset is recompile-free; ``checkpoint.ckpt``
    round-trips the snapshot through disk for cross-process restores.

The digest contract on every path: bytes re-enter the arena only after
their sha256 matches what was stamped at capture. A mismatch increments
``digest_failures``, drops the entry (spill) or the page's registry entry
plus the mapping streams via requeue (snapshot), and the affected stream
falls back to lossless re-prefill from host-side tokens — corrupted
durable state can never surface as wrong tokens.

**Speculative plane** (``spec_k > 0``, paged pool only): each chunk scan
step commits UP TO ``spec_k + 1`` tokens per slot instead of exactly one,
with exact greedy parity. The moving parts:

  * *drafter* — prompt-lookup n-gram matching over the slot's OWN history
    (prompt + generated tokens), held in a device-resident buffer that the
    scan carry appends committed tokens to, so later steps in the same
    chunk draft from tokens committed moments earlier. No draft model, no
    host round-trip mid-scan. A slot with no bigram match (or a free slot)
    proposes the out-of-vocab sentinel ``FILL = vocab_size``, which can
    never match — that step degrades to exactly today's one-token step.
  * *verify* — ONE batched forward (``lm.verify_step``, T = k + 1
    positions) through the existing paged cache scores the pending token
    plus all drafts; ``models.attention.self_attention_verify`` replicates
    the sequential per-token quantize/stamp walk bit-exactly (same scale
    selection a one-token step would make at each position) and stacks a
    positionwise running-max so rollback can gather any commit point.
  * *acceptance* — per-slot, inside the scan carry: the longest draft
    prefix matching the backbone's own (argmax) output is committed plus
    one corrected token (``m`` in [1, k+1]); a mixed co-batch never
    serializes on its slowest stream. Greedy output is BIT-IDENTICAL to
    the non-speculative engine (keys untouched); sampled mode commits
    exact per-step conditionals but advances the PRNG stream faster —
    documented approximate, not bit-reproducible against sequential.
  * *rollback* — speculative KV writes past the reject point are undone by
    resetting ``len`` and the drift trackers to the commit point's running
    values (pages past true_len are decode-private — never freed); the
    allocator provisions ``chunk * (k + 1)`` tokens of page headroom per
    live stream (``_headroom_tokens``) so the in-flight window always has
    pages.
  * *adaptivity* — ``_spec_dispatch_now`` demotes to the plain decode fn
    when the accept EMA drops below ``spec_disable_below`` tokens per
    slot-step (``spec_fallbacks``) and re-probes after
    ``spec_probe_every`` plain chunks. Probes are single-step (the
    chunk-1 spec executable from the warmed ladder — about one extra
    plain dispatch per probe instead of a verify-width chunk) and dry
    probes back the interval off exponentially (capped at 16x), so a
    zero-overlap adversarial trace pays a vanishing probe tax while a
    workload turning self-similar is still re-detected.
    ``warm_speculative`` precompiles the spec fn over the chunk ladder,
    so mode flips and deadline clamps never recompile in steady state.
    Jit keys: ("spec", slots, adapter capacity, chunk, k).
  * *parity discipline* — the device page table every non-speculative
    plane sees keeps the spec_k=0 width; the speculative headroom
    columns ride separately (``_spec_cols``) and are concatenated back
    in-graph only inside the spec executable. XLA specializes on input
    shapes, so this is what keeps every plain-plane executable — and
    therefore every committed int8 KV code — bit-identical to a
    spec_k=0 engine's.
  * *accounting* — ``draft_proposed`` / ``draft_accepted`` /
    ``spec_dispatches`` / ``spec_commits`` count the plane;
    ``take_decode_charges`` drains per-task COMMITTED token counts so
    fair-share scheduling bills real throughput, and
    ``spec_task_accept_rates`` exposes per-task accept-rate gauges.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.cache_manager import (CachePlan, StateSlotPool,
                                      capture_dense_state,
                                      restore_dense_state)
from repro.core.physical import PAD_SENTINEL, PhysicalFM, bucket_for
from repro.core.spill import EngineSnapshot, HostSpillArena
from repro.kernels import ops
from repro.models import lm

FREE = PAD_SENTINEL   # free-slot adapter sentinel (same as run_batch padding)
TRASH_PAGE = 0        # arena page absorbing free-slot garbage writes


def default_prompt_buckets(prompt_len: int) -> tuple[int, ...]:
    """2-3 admission buckets: quarter, half and full ``prompt_len`` (deduped,
    ascending). Small enough that every bucket's prefill executable warms
    quickly; coarse enough that steady state never recompiles."""
    return tuple(sorted({max(1, prompt_len // 4),
                         max(1, prompt_len // 2), prompt_len}))


def make_sampler(temperature: float, top_k: int):
    """Token sampler used inside the jitted prefill/decode graphs.

    ``sample(logits (B, V), keys (B, 2) uint32) -> (tokens (B,), keys')``.
    Greedy when ``temperature <= 0`` (keys pass through untouched); otherwise
    temperature-scaled categorical over the top-k logits, one PRNG key per
    row so co-batched streams sample independently."""
    if temperature <= 0:
        def sample(logits, keys):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
        return sample

    def sample(logits, keys):
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)   # (B, 2, 2)
        next_keys, use_keys = split[:, 0], split[:, 1]
        l = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jax.lax.top_k(l, top_k)[0][:, -1]
            l = jnp.where(l >= kth[:, None], l, -1e30)
        toks = jax.vmap(jax.random.categorical)(use_keys, l)
        return toks.astype(jnp.int32), next_keys
    return sample


@dataclasses.dataclass
class DecodeSlot:
    """One occupied decode stream."""
    rid: int
    task_id: str
    adapter_slot: int
    max_new: int
    eos_id: Optional[int]
    tokens: list          # generated token ids (first one from prefill)
    t_join: float
    t_first: float        # wall time of the first generated token (TTFT end)
    prompt_tokens: int = 0   # TRUE (post-truncation) admitted prompt length
    done: bool = False
    prompt: Optional[np.ndarray] = None   # admitted prompt (paged: requeue)
    adapter_id: Optional[str] = None
    deadline: float = float("inf")        # wall-clock cancel point (inf: none)
    status: str = "ok"                    # terminal status (core.request)
    enc_feats: Optional[np.ndarray] = None   # enc-dec: encoder input frames


@dataclasses.dataclass
class _PendingJoin:
    """A deferred admission (paged pool full) waiting in the FIFO queue."""
    task_id: str
    prompt: np.ndarray
    adapter_id: Optional[str]
    max_new_tokens: int
    rid: int
    eos_id: Optional[int]
    resume: Optional[DecodeSlot] = None   # preempted stream being re-admitted
    deadline: float = float("inf")
    status: str = "ok"                    # stamped when rejected terminally
    enc_feats: Optional[np.ndarray] = None   # enc-dec: encoder input frames


class DecodeEngine:
    """Slot-based continuous-batching token server bound to one PhysicalFM."""

    def __init__(self, fm: PhysicalFM, *, num_slots: int = 8,
                 prompt_len: Optional[int] = None, max_new: int = 32,
                 chunk: int = 4, kv_quant: bool = True,
                 eos_id: Optional[int] = None,
                 prompt_buckets: Optional[tuple] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, paged: bool = False,
                 page_size: int = 16, total_pages: Optional[int] = None,
                 prefix_sharing: bool = True, scale_refresh: float = 2.0,
                 pending_lookahead: int = 4, hol_skip_cap: int = 4,
                 spill_bytes: int = 0,
                 spill_arena: Optional[HostSpillArena] = None,
                 deadline_clamp: bool = True,
                 chunked_prefill: bool = True,
                 spec_k: int = 0, spec_force_fill: bool = False,
                 spec_disable_below: float = 1.25,
                 spec_probe_every: int = 16,
                 enc_len: Optional[int] = None):
        cfg = fm.cfg
        assert cfg.vocab_size > 0 and not cfg.is_representation, \
            "DecodeEngine serves generative decoder LMs (vocab head required)"
        self.fm = fm
        self.cfg = cfg
        self.num_slots = bucket_for(num_slots)
        self.prompt_len = prompt_len or fm.input_len
        # per-sublayer cache plan (core.cache_manager): which sublayers page
        # into the shared int8 arena, which carry fixed-size per-slot state
        # (recurrent conv/SSM/LSTM state, encoder-output cross K/V), and
        # which serving planes the stack supports. Capabilities negotiate —
        # unsupported planes demote cleanly instead of crashing mid-serve.
        self.plan = CachePlan.for_config(cfg, paged)
        if paged and not self.plan.paged:
            warnings.warn(
                "paged=True on a stack with no attention sublayers: the "
                "whole serving state is fixed-size per-slot state, nothing "
                "to page — running the dense slot pool", RuntimeWarning,
                stacklevel=2)
            paged = False
        # admission is variable-length for EVERY stack: attention masks pads
        # out of its K/V, and the recurrent scans are length-aware (dt zeroed
        # / state carried through right-pad positions — models.mamba,
        # models.xlstm), so hybrids share the bucketed prefill path
        self.var_len = True
        # enc-dec: per-slot encoder-output cross K/V rides in the pool as
        # fixed-size state (enc_len frames per slot, written at admission)
        self.enc_len = int(enc_len) if enc_len is not None else \
            (self.prompt_len if cfg.is_encoder_decoder else 0)
        if prompt_buckets is None:
            prompt_buckets = default_prompt_buckets(self.prompt_len)
        self.prompt_buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
        self.prompt_len = self.prompt_buckets[-1]   # largest bucket is the cap
        self.max_new = max_new
        self.chunk = chunk
        self.kv_quant = kv_quant
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._sample = make_sampler(self.temperature, self.top_k)
        # per-slot PRNG key state; threaded through the decode scan carry
        self._keys = jax.random.split(jax.random.PRNGKey(sample_seed),
                                      self.num_slots)
        self.s_max = self.prompt_len + max_new + 1
        self.spec_k = int(spec_k)
        if self.spec_k > 0 and not self.plan.speculative_ok and \
                (self.plan.has_recurrent or self.plan.has_encoder):
            warnings.warn(
                "spec_k > 0 demoted to plain decode: speculative rollback is "
                "a length/tracker reset on paged attention KV only — "
                "recurrent state cannot rewind past rejected drafts, and the "
                "verify forward has no encoder-decoder mode", RuntimeWarning,
                stacklevel=2)
            self.spec_k = 0
        if self.spec_k > 0 and not paged:
            raise ValueError("speculative decoding (spec_k > 0) requires "
                             "paged=True: speculative KV rollback relies on "
                             "decode-private pages past true_len")
        # speculative in-flight window: the device length can run up to one
        # chunk of (k+1)-token steps past a slot's nominal maximum before
        # the host-side done check truncates, so the per-slot page table
        # (and the arena sizing derived from it) must cover those targets
        spec_room = self.chunk * (self.spec_k + 1) if self.spec_k > 0 else 0
        self.paged = paged
        if paged:
            assert kv_quant, "the paged arena is int8-only (kv_quant=True)"
            self.page_size = page_size
            self.pages_per_slot = -(-(self.s_max + spec_room) // page_size)
            if total_pages is None:        # dense-equivalent memory + trash
                total_pages = 1 + self.num_slots * self.pages_per_slot
            assert total_pages >= 2, "need at least one usable page"
            self.total_pages = total_pages
            self.pool = lm.init_cache(cfg, self.num_slots,
                                      self.s_max + spec_room,
                                      kv_quant=True, paged=True,
                                      page_size=page_size,
                                      num_pages=total_pages,
                                      enc_len=self.enc_len or None)
            # bit-exact parity contract: the DEVICE page table every
            # non-speculative plane sees keeps the spec_k=0 width. XLA
            # specializes executables on input shapes, so a table widened
            # by the speculative headroom would recompile the plain
            # decode/prefill/tail planes into reduction orders that differ
            # from a spec_k=0 engine's — float drift that occasionally
            # flips an int8 quantization tie and, many dispatches later, a
            # greedy argmax. The headroom columns ride separately in
            # ``_spec_cols`` and only the speculative dispatch (its own
            # executable regardless) concatenates them back in-graph.
            self._plain_pages = -(-self.s_max // page_size)
            for sub in self.pool:
                if isinstance(sub, dict) and "page_table" in sub:
                    sub["page_table"] = \
                        sub["page_table"][..., :self._plain_pages]
            self._spec_cols: list = []
            # host-side allocator state; the device page table is synced
            # from _ptab before any decode dispatch that follows a change
            self._free_pages = list(range(total_pages - 1, TRASH_PAGE, -1))
            self._ptab = np.zeros((self.num_slots, self.pages_per_slot),
                                  np.int32)
            self._held = np.zeros((self.num_slots,), np.int64)
            self._lens = np.zeros((self.num_slots,), np.int64)
            self._ptab_dirty = True
            self.pending: collections.deque[_PendingJoin] = collections.deque()
            self.deferrals = 0
            self.preemptions = 0
            # refcounted ownership + COW prefix sharing (module docstring).
            # Capability-gated: shared pages capture attention KV only, and
            # a recurrent sublayer's state at the shared-prefix boundary is
            # stream-private — on hybrid / enc-dec stacks sharing demotes
            # silently to plain (per-stream) admission.
            self.prefix_sharing = bool(prefix_sharing) and \
                self.plan.prefix_sharing_ok
            self._page_refs = np.zeros((total_pages,), np.int32)
            self._prefix_registry: dict[tuple, int] = {}   # key -> page id
            self._page_key: dict[int, tuple] = {}          # page id -> key
            self.prefix_hits = 0            # joins that mapped >= 1 page
            self.shared_pages_mapped = 0    # cumulative pages mapped, not copied
            # chunked shared-prefix prefill (module docstring): a join whose
            # prompt maps >= 1 registered (or spilled) page prefills ONLY its
            # private tail. Tail lengths bucket separately from prompt
            # lengths (powers of two of the page size) so sharer churn stays
            # zero-recompile. Registered prefix pages keep a host-side FLOAT
            # sidecar (the float prefill K/V the page was quantized from) so
            # the tail attends the SAME values a full prefill would have —
            # exact token parity; a page whose sidecar is gone (post-reset
            # restore, spill-resume re-registration) is attended dequantized
            # from its int8 arena content instead, trading ~0.4% K/V error
            # for keeping the TTFT win.
            self.chunked_prefill = bool(chunked_prefill) and self.prefix_sharing
            self._page_float: dict[int, list] = {}   # page id -> float K/V
            # assembled float-prefix operands memoized per mapped page-id
            # tuple: sharers of one prefix reuse ONE host assembly + H2D
            # upload; entries die with any constituent page (_release_pages)
            self._prefix_fp_cache: dict[tuple, list] = {}
            self._prefix_width = self._pages_for(self.prompt_len)
            tb = {min(page_size, self.prompt_len)}
            b = page_size
            while b < self.prompt_len:
                b *= 2
                tb.add(min(b, self.prompt_len))
            self.tail_buckets = tuple(sorted(tb))
            # proactive int8 scale refresh (module docstring, drift section)
            self.scale_refresh = float(scale_refresh)
            self.scale_refreshes = 0
            self._jit_rescale = None
            # bounded pending-queue lookahead (head-of-line fix)
            self.pending_lookahead = max(1, int(pending_lookahead))
            self.hol_skip_cap = max(1, int(hol_skip_cap))
            self._hol_skips = 0
            self.hol_bypasses = 0
            # host-RAM spill tier (module docstring, durability section):
            # preemption victims and last-sharer prefix evictions spill D2H
            # instead of being destroyed; resume/re-join restore by H2D copy
            self.spill = spill_arena if spill_arena is not None else (
                HostSpillArena(spill_bytes) if spill_bytes > 0 else None)
            if self.spill is not None and not self.plan.spill_resume_ok:
                warnings.warn(
                    "spill tier demoted: the stream spill captures pages + "
                    "quantization trackers only, not per-slot dense state "
                    "(recurrent / encoder) — preemption falls back to the "
                    "lossless fold-and-re-prefill path", RuntimeWarning,
                    stacklevel=2)
                self.spill = None
        else:
            self.spill = None
            self.chunked_prefill = False    # needs the paged arena
            # the persistent pool: allocated once, updated in place (donated)
            self.pool = lm.init_cache(cfg, self.num_slots, self.s_max,
                                      kv_quant=kv_quant,
                                      enc_len=self.enc_len or None)
            self.pending = collections.deque()
        # fixed-size per-slot state lifecycle (core.cache_manager): one state
        # slot per live stream, allocated at admission, freed on every exit
        # path (retire / preempt / cancel / quarantine). The tensors live in
        # self.pool (batch axis == slot); this tracks lifecycle + gauges and
        # feeds the hybrid admission gate.
        self.state_pool = StateSlotPool(self.num_slots) \
            if self.plan.needs_state_slots else None
        self._tokens = jnp.zeros((self.num_slots,), jnp.int32)  # last token/slot
        self.slots: list[Optional[DecodeSlot]] = [None] * self.num_slots
        self._slot_adapters = np.full((self.num_slots,), FREE, np.int32)
        self._jit_prefill: dict[tuple, Callable] = {}
        self._jit_decode: dict[tuple, Callable] = {}
        self._jit_write: dict = {}      # dense: {None: fn}; paged: {npages: fn}
        self._seg_key = None        # composition signature of cached metadata
        self._seg_dev = None        # device-uploaded (perm, inv, blocks)
        self.steps = 0              # decode steps executed (all slots)
        # sum over chunks of live streams x steps: live_slot_steps /
        # (steps x num_slots) is the share of slot-steps that decoded a
        # stream rather than an empty slot
        self.live_slot_steps = 0
        self.last_chunk_s = 0.0
        # failure-semantics state (module docstring, failure section)
        self.rejected: list[_PendingJoin] = []   # terminally rejected joins
        # (rid, task, true_prompt_len, tail_tokens): tail_tokens is what the
        # prefill ACTUALLY computed — schedulers charge it, not true_len
        self.admitted_log: list[tuple[int, str, int, int]] = []
        self.admissions = 0          # streams admitted into slots (ever)
        self.tail_tokens_computed = 0   # prompt tokens actually prefilled
        self.prefill_tokens_saved = 0   # prompt tokens skipped (prefix mapped)
        self.quarantines = 0         # streams retired on non-finite logits
        self.deadline_cancels = 0    # mid-flight (slot/resume) expirations
        self.deadline_sheds = 0      # pending entries expired unadmitted
        self.stranded_rejections = 0  # stranded entries terminally rejected
        self.cancels = 0             # client cancel() unwinds
        # durability-layer state (spill tier + snapshot/restore)
        self.spilled_pages = 0       # pages captured D2H into the host arena
        self.restored_pages = 0      # pages restored H2D from the host arena
        self.digest_failures = 0     # corrupted spill/snapshot pages detected
        self.spill_resumes = 0       # preempted streams resumed without prefill
        self.spill_prefix_hits = 0   # joins that restored >= 1 spilled prefix page
        self.resume_costs: list[tuple[str, float]] = []  # ("spill"|"reprefill", s)
        self._jit_gather = None       # padded fixed-width D2H page capture
        self._jit_page_restore = None  # padded H2D page write-back
        self._jit_slot_restore = None  # per-slot scale/len write-back
        # deadline overrun clamp: EMA of per-token decode seconds, used to
        # shrink the next chunk to a ladder size when a live deadline is
        # nearer than a full chunk (satellite; see step_chunk)
        self.deadline_clamp = bool(deadline_clamp)
        self._step_ema = 0.0
        self.deadline_clamps = 0     # chunks shortened by the clamp
        # self-speculative decode plane (module docstring, speculation
        # section): device-resident n-gram drafter + one batched verify
        # forward per scan step; paged-only (rollback is a length/tracker
        # reset over decode-private pages). spec_k itself parses above,
        # before the arena sizing it feeds.
        self.spec_force_fill = bool(spec_force_fill)
        self.spec_disable_below = float(spec_disable_below)
        self.spec_probe_every = max(1, int(spec_probe_every))
        # history buffer bound: prompt + generated tokens never exceed
        # s_max, plus one dispatch's worst-case in-flight growth (chunk
        # scan steps x up to k+1 commits each)
        self._spec_hist_len = self.s_max + self.chunk * (self.spec_k + 1)
        self._spec_seg_key = None    # composition signature (spec metadata)
        self._spec_seg_dev = None
        self._spec_accept_ema = 0.0  # committed tokens per slot-step (EMA)
        self._spec_cool = 0          # plain dispatches since the last probe
        # re-probe cadence with exponential backoff: a dry probe (nothing
        # accepted) doubles the interval up to 16x the base, so a
        # sustained zero-overlap workload pays an asymptotically vanishing
        # probe tax while a workload that turns self-similar again is
        # still re-detected within a bounded number of dispatches
        self._spec_probe_interval = self.spec_probe_every
        self._spec_probe = False     # current spec dispatch is a probe
        self._spec_task_stats: dict = {}   # task -> [proposed, accepted]
        self._decode_charges: collections.Counter = collections.Counter()
        self.draft_proposed = 0      # draft tokens sent to verification
        self.draft_accepted = 0      # draft tokens committed
        self.spec_dispatches = 0     # chunk dispatches through the spec fn
        self.spec_commits = 0        # tokens committed by spec dispatches
        self.spec_fallbacks = 0      # dispatches demoted to the plain fn

    # ---- occupancy ----
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def active_count(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def pending_count(self) -> int:
        return len(self.pending)

    def pending_rids(self) -> list[int]:
        return [p.rid for p in self.pending]

    def pending_task_ids(self) -> list[str]:
        return [p.task_id for p in self.pending]

    def compile_count(self) -> int:
        """Total jitted executables (prefill + decode + pool writes); steady
        state across request join/leave churn must not grow this."""
        fns = (list(self._jit_prefill.values()) +
               list(self._jit_decode.values()) +
               list(self._jit_write.values()))
        for name in ("_jit_rescale", "_jit_gather", "_jit_page_restore",
                     "_jit_slot_restore"):
            if getattr(self, name, None) is not None:
                fns.append(getattr(self, name))
        return sum(f._cache_size() for f in fns)

    # ---- page accounting (paged layout) ----
    def free_page_count(self) -> int:
        return len(self._free_pages) if self.paged else 0

    def used_page_count(self) -> int:
        if not self.paged:
            return 0
        return (self.total_pages - 1) - len(self._free_pages)

    def page_occupancy(self) -> float:
        """Fraction of usable (non-trash) pages held by streams."""
        if not self.paged:
            return 0.0
        return self.used_page_count() / max(self.total_pages - 1, 1)

    def _pages_for(self, tokens: int) -> int:
        return -(-max(tokens, 1) // self.page_size)

    def _headroom_tokens(self) -> int:
        """Decode headroom the allocator provisions per live stream per
        chunk: ``chunk`` tokens, or ``chunk * (spec_k + 1)`` when the
        speculative plane is configured — each scan step may commit up to
        ``k + 1`` tokens, so page topping / admission gates budget the
        worst case.  Static on ``spec_k`` (never the adaptive spec/plain
        demotion state): dispatch mode can flip between chunks, and the
        provisioning must hold either way."""
        return self.chunk * (self.spec_k + 1 if self.spec_k > 0 else 1)

    def shared_page_count(self) -> int:
        """Physical pages currently mapped by more than one stream."""
        return int((self._page_refs > 1).sum()) if self.paged else 0

    def dedup_saved_pages(self) -> int:
        """Pages prefix sharing is saving RIGHT NOW: logical mappings minus
        physical pages (Σ max(refcount - 1, 0))."""
        if not self.paged:
            return 0
        return int(np.maximum(self._page_refs - 1, 0).sum())

    def logical_page_count(self) -> int:
        """Total page-table mappings across live slots — what the streams
        would hold physically without prefix sharing."""
        return int(self._held.sum()) if self.paged else 0

    def _imminent_page_need(self) -> int:
        """Pages the LIVE streams will allocate for their next chunk — the
        watermark an admission must clear on top of its own need, so letting
        one more stream in doesn't immediately preempt a running one."""
        need = 0
        for i, s in enumerate(self.slots):
            if s is not None and not s.done:
                need += max(0, self._pages_for(self._lens[i]
                                               + self._headroom_tokens())
                            - self._held[i])
        return need

    def _admission_need(self, prompt_tokens: int, prompt=None,
                        adapter_id: Optional[str] = None) -> int:
        """Free pages an admission must find: the prompt's bucket worth of
        pages MINUS the pages its prefix would share (known only when the
        prompt content is provided), plus a chunk of decode headroom for the
        new stream and for every live one."""
        plen = self.bucket_for_prompt(min(max(prompt_tokens, 1),
                                          self.prompt_len))
        shared = len(self._match_prefix(adapter_id, prompt)) \
            if prompt is not None else 0
        return (self._pages_for(self._adm_s_max(plen)) - shared
                + self._pages_for(self._headroom_tokens())
                + self._imminent_page_need())

    def can_admit(self, prompt_tokens: Optional[int] = None, *,
                  prompt=None, adapter_id: Optional[str] = None) -> bool:
        """Would an admission of a ``prompt_tokens``-token prompt proceed
        right now? Dense: a free slot. Paged: a free slot, nothing already
        deferred ahead of it (FIFO), and free pages covering the prompt's
        admission bucket PLUS a chunk of decode headroom for this stream AND
        for every live one — the memory-aware gate ``ServeLoop`` consults
        before dispatching a prefill. Deliberately conservative by one chunk
        per live stream: over-admitting converts into preemptions, which
        redo prefill work and can truncate long streams.

        The paged gate REQUIRES the prompt length (or the prompt itself) —
        a silent 1-token default once let callers consult the memory gate
        with a wildly low estimate and over-admit. Passing ``prompt``
        (token ids) additionally lets the gate DISCOUNT the pages a shared
        prefix would map instead of allocate; ``adapter_id`` keys the
        prefix registry lookup (LoRA'd V differs per adapter)."""
        if prompt is not None:
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt_tokens is None:
                prompt_tokens = len(prompt)
        if self.paged and prompt_tokens is None:
            raise TypeError(
                "can_admit on a paged pool requires prompt_tokens (or "
                "prompt=): the memory gate cannot size an admission from "
                "a default 1-token estimate")
        if not self.free_slots():
            return False
        if self.state_pool is not None and self.state_pool.available() <= 0:
            # hybrid/enc-dec gate: admission needs a fixed-size state slot
            # alongside the decode slot (1:1 today, but counted separately
            # so the invariant — and the deferral gauge — is explicit)
            self.state_pool.note_deferral()
            return False
        if not self.paged:
            return True
        if self.pending:
            return False
        return len(self._free_pages) >= self._admission_need(
            prompt_tokens, prompt=prompt, adapter_id=adapter_id)

    # ---- refcounted page allocator + prefix registry (paged layout) ----
    def _take_pages(self, n: int) -> np.ndarray:
        assert len(self._free_pages) >= n
        pages = np.array([self._free_pages.pop() for _ in range(n)], np.int32)
        self._page_refs[pages] = 1
        return pages

    def _share_pages(self, pages):
        for p in pages:
            self._page_refs[int(p)] += 1

    def _release_pages(self, pages):
        """Drop one reference per page; pages whose refcount reaches zero
        return to the free list and fall out of the prefix registry. With a
        spill arena attached, registered pages losing their LAST sharer are
        captured D2H (keyed by their chained digest) before the id is
        recycled — the prefix survives the idle gap in host RAM. The
        capture happens before any later allocation can rewrite the page;
        within this call the device content is still intact."""
        spillable = []
        freed = set()
        for p in pages:
            p = int(p)
            r = self._page_refs[p] = self._page_refs[p] - 1
            assert r >= 0, f"double free of page {p}"
            if r == 0:
                self._free_pages.append(p)
                freed.add(p)
                key = self._page_key.pop(p, None)
                if key is not None and self._prefix_registry.get(key) == p:
                    del self._prefix_registry[key]
                    if self.spill is not None:
                        # the float sidecar rides into the spill blob
                        spillable.append((p, key))
                        continue
                self._page_float.pop(p, None)
        if freed and self._prefix_fp_cache:
            # a freed id may be recycled with new content: drop every
            # assembled-prefix operand that referenced it
            self._prefix_fp_cache = {
                k: v for k, v in self._prefix_fp_cache.items()
                if not freed.intersection(k)}
        if spillable:
            self._spill_prefix_pages(spillable)

    def _release_slot_pages(self, slot: int):
        self._release_pages(self._ptab[slot, :self._held[slot]])
        self._ptab[slot] = TRASH_PAGE
        self._held[slot] = 0
        self._lens[slot] = 0
        self._ptab_dirty = True

    def _prefix_keys(self, adapter_id: Optional[str],
                     prompt: np.ndarray) -> list[bytes]:
        """One registry key per full page of ``prompt``: a CHAINED sha256
        digest (key_j = H(key_{j-1} || page_j bytes), seeded with the
        adapter identity), so key material and hashing stay O(prompt
        bytes) total — not O(pages × prefix) — while a digest still
        commits to the ENTIRE prefix up to its page. 256-bit collisions
        are negligible against page-mapping corruption."""
        import hashlib
        h = hashlib.sha256(b"\x00" if adapter_id is None
                           else b"\x01" + adapter_id.encode())
        ps = self.page_size
        keys = []
        for j in range(len(prompt) // ps):
            h.update(prompt[j * ps:(j + 1) * ps].tobytes())
            keys.append(h.digest())
        return keys

    def _match_prefix(self, adapter_id: Optional[str], prompt) -> list[int]:
        """Arena page ids of the longest registered page-aligned prefix of
        ``prompt`` under ``adapter_id`` (LoRA changes V, so prefixes only
        match within one adapter). Empty when sharing is off."""
        if not (self.paged and self.prefix_sharing) or prompt is None:
            return []
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) > self.prompt_len:       # join() left-truncates
            prompt = prompt[-self.prompt_len:]
        shared = []
        for key in self._prefix_keys(adapter_id, prompt):
            p = self._prefix_registry.get(key)
            if p is None:
                break
            shared.append(p)
        return shared

    def _register_prefix(self, adapter_id: Optional[str], prompt: np.ndarray,
                         slot: int, true_len: int, cache=None,
                         cache_page0: int = 0):
        """Publish the slot's FULL prompt pages (the only immutable ones —
        decode never writes below ``true_len``) for future joins to map.
        An existing registration for the same prefix wins (first writer);
        the duplicate page stays private to this slot.

        ``cache`` (chunked prefill only): the admission's FLOAT prefill
        cache, whose page ``j - cache_page0`` holds the exact pre-quantized
        K/V of registered page ``j``. Winning registrations stash that slice
        host-side (``_page_float``) so future sharers' tails can attend the
        SAME float values a full prefill would have seen — exact token
        parity instead of the int8 arena's ~0.4% dequantization error."""
        if not self.prefix_sharing:
            return
        keys = self._prefix_keys(adapter_id, prompt[:true_len])
        stash = []
        for j, key in enumerate(keys):
            page = int(self._ptab[slot, j])
            if self._prefix_registry.setdefault(key, page) == page:
                self._page_key[page] = key
                if (self.chunked_prefill and cache is not None
                        and j >= cache_page0
                        and page not in self._page_float):
                    stash.append((j - cache_page0, page))
        if stash:
            # one D2H pull of the whole admission cache, then numpy page
            # slices: per-page device reads would sync once per page
            ps = self.page_size
            host = [{"k": np.asarray(csub["k"][:, 0]),
                     "v": np.asarray(csub["v"][:, 0])}
                    for csub, psub in zip(cache, self.pool)
                    if isinstance(psub, dict) and "page_table" in psub]
            for rel, page in stash:
                self._page_float[page] = [
                    {"k": sub["k"][:, rel * ps:(rel + 1) * ps].copy(),
                     "v": sub["v"][:, rel * ps:(rel + 1) * ps].copy()}
                    for sub in host]

    def _sync_page_table(self):
        """Push the host page table to every attention sublayer's device
        leaf. Values-only: the (num_slots, pages_per_slot) shape is static,
        so syncing never retraces."""
        if not self._ptab_dirty:
            return
        with tracing.span("fmplex.page_table_sync"):
            self._spec_cols = []
            for sub in self.pool:
                if isinstance(sub, dict) and "page_table" in sub:
                    nper = sub["page_table"].shape[0]
                    full = np.broadcast_to(self._ptab[None],
                                           (nper,) + self._ptab.shape)
                    sub["page_table"] = jnp.asarray(
                        full[..., :self._plain_pages])
                    # speculative headroom columns (empty at spec_k=0); the
                    # spec dispatch concatenates these behind the plain-
                    # width table in-graph — see the ctor's parity note
                    self._spec_cols.append(
                        jnp.asarray(full[..., self._plain_pages:]))
            self._ptab_dirty = False

    # ---- host-RAM spill tier (paged layout) ----
    def _paged_subs(self):
        return [sub for sub in self.pool
                if isinstance(sub, dict) and "page_table" in sub]

    def _gather_fn(self):
        """D2H capture of up to ``pages_per_slot`` pages plus one slot's
        running scales/drift trackers in ONE dispatch. The page-id vector is
        padded to the fixed width with the trash page, so the gather
        compiles exactly once — spill traffic never retraces."""
        if self._jit_gather is None:
            def spill_gather(pool, page_idx, slot):
                out = []
                for sub in pool:
                    if not (isinstance(sub, dict) and "page_table" in sub):
                        continue
                    out.append({
                        "k": sub["k"][:, page_idx],
                        "v": sub["v"][:, page_idx],
                        "k_scale": sub["k_scale"][:, page_idx],
                        "v_scale": sub["v_scale"][:, page_idx],
                        "slot_k_scale": sub["slot_k_scale"][:, slot],
                        "slot_v_scale": sub["slot_v_scale"][:, slot],
                        "k_max": sub["k_max"][:, slot],
                        "v_max": sub["v_max"][:, slot],
                    })
                return out
            self._jit_gather = jax.jit(spill_gather)
        return self._jit_gather

    def _page_restore_fn(self):
        """H2D write-back of up to ``pages_per_slot`` pages' int8 codes and
        per-page scales. Padded page ids point at the trash page (whose
        content is garbage by contract), so duplicate trash writes from the
        padding are harmless and the write compiles exactly once."""
        if self._jit_page_restore is None:
            def page_restore(pool, data, page_idx):
                out, i = [], 0
                for sub in pool:
                    if not (isinstance(sub, dict) and "page_table" in sub):
                        out.append(sub)
                        continue
                    d_, i = data[i], i + 1
                    d = dict(sub)
                    d["k"] = sub["k"].at[:, page_idx].set(d_["k"])
                    d["v"] = sub["v"].at[:, page_idx].set(d_["v"])
                    d["k_scale"] = sub["k_scale"].at[:, page_idx].set(
                        d_["k_scale"])
                    d["v_scale"] = sub["v_scale"].at[:, page_idx].set(
                        d_["v_scale"])
                    out.append(d)
                return out

            self._jit_page_restore = jax.jit(page_restore, donate_argnums=0)
        return self._jit_page_restore

    def _slot_restore_fn(self):
        """H2D write-back of one slot's running scales, drift trackers and
        true length — the second half of a spill resume."""
        if self._jit_slot_restore is None:
            def slot_restore(pool, state, slot, true_len):
                out, i = [], 0
                for sub in pool:
                    if not (isinstance(sub, dict) and "page_table" in sub):
                        out.append(sub)
                        continue
                    st, i = state[i], i + 1
                    d = dict(sub)
                    d["slot_k_scale"] = sub["slot_k_scale"].at[:, slot].set(
                        st["slot_k_scale"])
                    d["slot_v_scale"] = sub["slot_v_scale"].at[:, slot].set(
                        st["slot_v_scale"])
                    d["k_max"] = sub["k_max"].at[:, slot].set(st["k_max"])
                    d["v_max"] = sub["v_max"].at[:, slot].set(st["v_max"])
                    d["len"] = sub["len"].at[:, slot].set(true_len)
                    out.append(d)
                return out

            self._jit_slot_restore = jax.jit(slot_restore, donate_argnums=0)
        return self._jit_slot_restore

    def _capture_pages(self, pages: np.ndarray, slot: int) -> list:
        """Pull ``pages`` (and ``slot``'s running state) to host arrays:
        one padded gather dispatch, one host sync."""
        n = len(pages)
        idx = np.full((self.pages_per_slot,), TRASH_PAGE, np.int32)
        idx[:n] = pages
        dev = self._gather_fn()(self.pool, jnp.asarray(idx), jnp.int32(slot))
        host = []
        for sub in jax.device_get(dev):      # one transfer for the whole blob
            host.append({
                "k": np.asarray(sub["k"][:, :n]),
                "v": np.asarray(sub["v"][:, :n]),
                "k_scale": np.asarray(sub["k_scale"][:, :n]),
                "v_scale": np.asarray(sub["v_scale"][:, :n]),
                "slot_k_scale": np.asarray(sub["slot_k_scale"]),
                "slot_v_scale": np.asarray(sub["slot_v_scale"]),
                "k_max": np.asarray(sub["k_max"]),
                "v_max": np.asarray(sub["v_max"]),
            })
        return host

    def _restore_pages(self, blob: list, pages: np.ndarray):
        """Write captured page content back into arena pages ``pages``
        (freshly allocated — possibly different ids than at capture)."""
        n = len(pages)
        W = self.pages_per_slot
        idx = np.full((W,), TRASH_PAGE, np.int32)
        idx[:n] = pages
        data = []
        for sub in blob:
            d = {}
            for k in ("k", "v", "k_scale", "v_scale"):
                a = np.asarray(sub[k])
                pad = np.zeros((a.shape[0], W) + a.shape[2:], a.dtype)
                pad[:, :n] = a[:, :n]
                d[k] = pad
            data.append(d)
        self.pool = self._page_restore_fn()(self.pool, data,
                                            jnp.asarray(idx))

    def _spill_stream(self, slot: int, s: DecodeSlot):
        """Capture a preemption victim's full KV state D2H before its pages
        are released: pages + scales + drift trackers + last token + PRNG
        key. Resume restores all of it — no re-prefill, no re-quantization,
        exact token AND sampling parity with a never-preempted run."""
        n = int(self._held[slot])
        if n == 0:
            return
        pages = self._ptab[slot, :n]
        blob = self._capture_pages(pages, slot)
        meta = {
            "n_pages": n,
            "true_len": int(self._lens[slot]),
            "last_token": int(np.asarray(self._tokens[slot])),
            "key": np.asarray(self._keys[slot]),
        }
        if self.spill.put(("stream", s.rid), blob, meta):
            self.spilled_pages += n

    def _drop_stream_spill(self, rid: int):
        if self.spill is not None:
            self.spill.pop(("stream", rid))

    def _try_spill_resume(self, req: _PendingJoin) -> Optional[int]:
        """Resume a preempted stream from its host-RAM spill: allocate fresh
        pages, H2D-restore its int8 codes/scales/trackers/PRNG key, rebuild
        the page table and re-register its prefix — skipping the re-prefill
        entirely. Returns the slot, or None to fall back to re-prefill
        (spill missing/evicted, digest mismatch, or not enough free pages
        for the exact restored length)."""
        entry = self.spill.get(("stream", req.rid))
        if entry is None:
            return None
        if not entry.verify():
            self.spill.pop(("stream", req.rid))
            self.digest_failures += 1
            return None
        n = int(entry.meta["n_pages"])
        if len(self._free_pages) < n or not self.free_slots():
            return None
        t0 = time.perf_counter()
        self.spill.pop(("stream", req.rid))
        s = req.resume
        slot = self.free_slots()[0]
        pages = self._take_pages(n)
        true_len = int(entry.meta["true_len"])
        self._restore_pages(entry.blob, pages)
        state = [{k: sub[k] for k in ("slot_k_scale", "slot_v_scale",
                                      "k_max", "v_max")}
                 for sub in entry.blob]
        self.pool = self._slot_restore_fn()(self.pool, state,
                                            jnp.int32(slot),
                                            jnp.int32(true_len))
        self._ptab[slot, :n] = pages
        self._held[slot] = n
        self._lens[slot] = true_len
        self._ptab_dirty = True
        self._tokens = self._tokens.at[slot].set(
            jnp.int32(int(entry.meta["last_token"])))
        self._keys = self._keys.at[slot].set(
            jnp.asarray(entry.meta["key"]))
        self.slots[slot] = s
        aslot = self.fm.adapters.index(req.adapter_id)
        self._slot_adapters[slot] = aslot
        self._seg_key = None
        # restored prompt pages sit at their original page-table positions,
        # so re-registration republishes the prefix for future sharers
        if s.prompt is not None:
            self._register_prefix(s.adapter_id, np.asarray(s.prompt),
                                  slot, s.prompt_tokens)
        self.admissions += 1      # progress signal (watchdog); not re-logged
        self.spill_resumes += 1
        self.restored_pages += n
        self.resume_costs.append(("spill", time.perf_counter() - t0))
        return slot

    def _spill_prefix_pages(self, pairs: list):
        """Capture last-sharer prefix pages D2H as they leave the registry,
        keyed by their chained digest — a later join whose prompt chain
        reaches the digest restores them by DMA instead of recompute."""
        pages = np.array([p for p, _ in pairs], np.int32)
        blob = self._capture_pages(pages, 0)    # slot state ignored
        for j, (p, key) in enumerate(pairs):
            per_page = [{k: sub[k][:, j:j + 1]
                         for k in ("k", "v", "k_scale", "v_scale")}
                        for sub in blob]
            # the float sidecar spills WITH the page, so a restored prefix
            # keeps serving exact-parity chunked tails ("kf"/"vf" keys are
            # ignored by _restore_pages, which only writes the arena keys)
            fp = self._page_float.pop(p, None)
            if fp is not None:
                for sub, f in zip(per_page, fp):
                    sub["kf"] = f["k"]
                    sub["vf"] = f["v"]
            if self.spill.put(("prefix", key), per_page, {}):
                self.spilled_pages += 1

    def _match_spilled_prefix(self, adapter_id: Optional[str], prompt,
                              skip: int) -> list[tuple[bytes, object]]:
        """Continue a prompt's digest chain past the live registry into the
        spill arena: (key, entry) pairs for consecutive spilled full pages
        starting at page index ``skip``. A digest mismatch ends the chain
        (the corrupt entry is dropped and counted)."""
        if self.spill is None or not (self.paged and self.prefix_sharing) \
                or prompt is None:
            return []
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) > self.prompt_len:
            prompt = prompt[-self.prompt_len:]
        out = []
        for j, key in enumerate(self._prefix_keys(adapter_id, prompt)):
            if j < skip:
                continue
            entry = self.spill.get(("prefix", key))
            if entry is None:
                break
            if not entry.verify():
                self.spill.pop(("prefix", key))
                self.digest_failures += 1
                break
            out.append((key, entry))
        return out

    # ---- jitted planes ----
    def _impl(self, rows: int, cap: int) -> str:
        """LoRA path for a ``rows``-row co-batch. Resolved from the slot
        bucket (not the live adapter count) so the choice is stable within
        each compiled (rows, cap) jit key."""
        return self.fm.resolve_lora_impl(rows, num_adapters=cap)

    def _adm_s_max(self, plen: int) -> int:
        """Admission-prefill cache length for one prompt bucket: the paged
        scatter needs a whole number of pages; dense scatters into s_max."""
        if self.paged:
            return self._pages_for(plen) * self.page_size
        return self.s_max

    def _prefill_fn(self, cap: int, plen: int):
        """Admission prefill for one prompt-length bucket. The bucket length
        is a static jit key; the TRUE prompt length is a traced operand, so
        every length within the bucket reuses the executable."""
        key = (cap, plen)
        if key not in self._jit_prefill:
            cfg, bt = self.cfg, self.fm.seg_block_t
            impl = self._impl(1, cap)
            # paged admission keeps the prefill K/V in float: the page
            # scatter quantizes PER PAGE (a page's scale depends only on
            # the tokens it covers, so shared prefix pages are bit-exact
            # across streams); the dense scatter stores the in-graph
            # per-row quantization unchanged
            s_max, kvq, sample = self._adm_s_max(plen), \
                self.kv_quant and not self.paged, self._sample
            enc_len = self.enc_len

            @jax.jit
            def admission_prefill(params, tokens, true_len, enc_embeds,
                                  rng_key, lora_stack, adapter_idx, perm, inv,
                                  blocks):
                seg = None
                if impl == "segmented":
                    seg = {"perm": perm, "inv": inv, "block_adapter": blocks,
                           "block_t": bt}
                cache = lm.init_cache(cfg, 1, s_max, kv_quant=kvq,
                                      enc_len=enc_len or None)
                logits, cache = lm.prefill(
                    params, cfg, tokens=tokens, cache=cache, lora=lora_stack,
                    adapter_idx=adapter_idx, lora_impl=impl, lora_seg=seg,
                    seq_lens=true_len, enc_embeds=enc_embeds)
                first, rng_key = sample(logits, rng_key)
                # numeric-health flag rides the admission's existing host
                # sync: a non-finite prefill quarantines at admission, before
                # any page allocation or prefix registration
                return first, lm.finite_logits(logits), rng_key, cache

            self._jit_prefill[key] = admission_prefill
        return self._jit_prefill[key]

    def _tail_prefill_fn(self, cap: int, tlen: int, mode: str = "float"):
        """Chunked shared-prefix admission prefill for one TAIL bucket: run
        the model over only the prompt's private tail, with the tail's
        queries attending the already-mapped prefix pages in front of the
        tail's own fresh K/V. Two prefix sources, same attention plumbing:

          * ``mode="float"`` — the prefix K/V arrive as an explicit operand
            assembled host-side from the pages' float sidecars
            (``_page_float`` / spilled ``kf``/``vf``). These are the EXACT
            pre-quantization values a full prefill would have computed, so
            the tail's logits (and cache) are bit-identical to the full
            path's — exact token parity for sharer joins.
          * ``mode="pages"`` — the prefix is gathered from the int8 arena
            through the prefix page vector and dequantized per page
            (``ops.gather_prefix_kv``). Fallback for pages whose sidecar is
            gone (engine restored from a device-reset snapshot, prefix
            re-registered by a spill resume): keeps the TTFT win at ~0.4%
            K/V error.

        True prefix length and tail length are traced operands — which
        pages a sharer maps never retraces; only the tail BUCKET (and the
        mode) is a jit key."""
        key = ("tail", cap, tlen, mode)
        if key not in self._jit_prefill:
            cfg, bt = self.cfg, self.fm.seg_block_t
            impl = self._impl(1, cap)
            # like the full paged admission, the tail cache stays FLOAT: the
            # tail-page scatter quantizes per page afterwards
            s_max = self._pages_for(tlen) * self.page_size
            sample = self._sample
            # which pool entries are paged attention sublayers is static —
            # the float variant takes its prefix operand without the pool
            paged_mask = [isinstance(sub, dict) and "page_table" in sub
                          for sub in self.pool]

            def body(params, prefix, tokens, tail_len, prefix_len, rng_key,
                     lora_stack, adapter_idx, perm, inv, blocks):
                seg = None
                if impl == "segmented":
                    seg = {"perm": perm, "inv": inv, "block_adapter": blocks,
                           "block_t": bt}
                cache = lm.init_cache(cfg, 1, s_max, kv_quant=False)
                # absolute positions: RoPE must see the tail at its true
                # offset behind the prefix
                pos = prefix_len[:, None] + jnp.arange(tokens.shape[1])[None]
                logits, cache = lm.prefill(
                    params, cfg, tokens=tokens, cache=cache, lora=lora_stack,
                    adapter_idx=adapter_idx, lora_impl=impl, lora_seg=seg,
                    seq_lens=tail_len, pos=pos, prefix=prefix,
                    prefix_len=prefix_len)
                first, rng_key = sample(logits, rng_key)
                return first, lm.finite_logits(logits), rng_key, cache

            if mode == "float":
                @jax.jit
                def tail_prefill_float(params, prefix_fp, tokens, tail_len,
                                       prefix_len, rng_key, lora_stack,
                                       adapter_idx, perm, inv, blocks):
                    it = iter(prefix_fp)
                    prefix = [next(it) if paged else None
                              for paged in paged_mask]
                    return body(params, prefix, tokens, tail_len, prefix_len,
                                rng_key, lora_stack, adapter_idx, perm, inv,
                                blocks)
                run = tail_prefill_float
            else:
                @jax.jit
                def tail_prefill_pages(params, pool, tokens, tail_len,
                                       prefix_pages, prefix_len, rng_key,
                                       lora_stack, adapter_idx, perm, inv,
                                       blocks):
                    # dequantized prefix K/V per attention sublayer, gathered
                    # from the arena through the explicit prefix page vector
                    # (positions past prefix_len point at the trash page and
                    # are masked out of attention by the validity mask)
                    prefix = []
                    for sub in pool:
                        if isinstance(sub, dict) and "page_table" in sub:
                            gk, gv = jax.vmap(
                                lambda kp, vp, ks, vs: ops.gather_prefix_kv(
                                    kp, vp, ks, vs, prefix_pages[None]))(
                                sub["k"], sub["v"],
                                sub["k_scale"], sub["v_scale"])
                            prefix.append({"k": gk, "v": gv})
                        else:
                            prefix.append(None)
                    return body(params, prefix, tokens, tail_len, prefix_len,
                                rng_key, lora_stack, adapter_idx, perm, inv,
                                blocks)
                run = tail_prefill_pages

            self._jit_prefill[key] = run
        return self._jit_prefill[key]

    def _write_fn(self):
        """Dense admission scatter: one dynamic_update_slice per cache leaf
        along the slot (batch) axis."""
        if None not in self._jit_write:
            def slot_write(pool, cache, slot):
                # every cache leaf is (nper, batch, ...): scatter the one-row
                # prefill cache into the pool's slot along the batch axis
                return jax.tree.map(
                    lambda p, c: jax.lax.dynamic_update_slice_in_dim(
                        p, c.astype(p.dtype), slot, axis=1), pool, cache)

            self._jit_write[None] = jax.jit(slot_write, donate_argnums=0)
        return self._jit_write[None]

    def _paged_write_fn(self, npages: int):
        """Paged admission scatter for one prompt bucket (``npages`` pages):
        the one-row FLOAT prefill cache reshapes into pages, each page is
        quantized with its own per-(page, kv-head) scale (a pure function of
        the tokens the page covers — the property that makes shared prefix
        pages bit-exact across streams), and pages scatter into the arena at
        the allocated page ids. Shared prefix positions arrive pointed at
        the trash page, so their (identical) content is simply discarded.
        The slot's running scales are set to the prompt-wide maximum (the
        admission range decode appends quantize into), the drift trackers
        reset, and ``len`` is set to the TRUE prompt length. Page ids, slot
        and length are traced operands — allocation and sharing churn never
        retrace."""
        if npages not in self._jit_write:
            ps = self.page_size

            def page_write(pool, cache, slot, page_idx, true_len):
                out = []
                for psub, csub in zip(pool, cache):
                    if not (isinstance(psub, dict) and "page_table" in psub):
                        # fixed-size per-slot state (recurrent sublayers):
                        # the one-row prefill state scatters into the slot
                        # along the batch axis, same contract as the dense
                        # pool's _write_fn — no paging, no quantization
                        if isinstance(psub, dict):
                            out.append({
                                kk: jax.lax.dynamic_update_slice_in_dim(
                                    psub[kk], csub[kk].astype(psub[kk].dtype),
                                    slot, axis=1)
                                for kk in psub})
                        else:
                            out.append(psub)
                        continue
                    kf = csub["k"][:, 0].astype(jnp.float32)  # (nper,S,kv,hd)
                    nper, _, kv, hd = kf.shape
                    kf = kf.reshape(nper, npages, ps, kv, hd)
                    vf = csub["v"][:, 0].astype(jnp.float32).reshape(
                        nper, npages, ps, kv, hd)
                    kmax = jnp.max(jnp.abs(kf), axis=(2, 4))  # (nper,np,kv)
                    vmax = jnp.max(jnp.abs(vf), axis=(2, 4))
                    ks = kmax / 127.0       # 0 for empty (pad-only) pages
                    vs = vmax / 127.0
                    # slot scales = prompt-wide max: identical to the dense
                    # per-row quantization range (kernels.quantize_kv)
                    slot_ks = jnp.maximum(jnp.max(kmax, axis=1), 1e-8) / 127.0
                    slot_vs = jnp.maximum(jnp.max(vmax, axis=1), 1e-8) / 127.0
                    # the prompt/decode BOUNDARY page (the partial page
                    # decode will keep appending into) is stamped at the
                    # slot-wide scale, not its prompt-local one: a partial
                    # page holding a few small-magnitude prompt tokens must
                    # not clip the stream's normal-range decode K/V. Still a
                    # pure function of the prompt (slot scale is), and never
                    # a shared page (sharing stops at the last FULL page).
                    sel = (jnp.arange(npages) == true_len // ps)[None, :,
                                                                 None]
                    ks = jnp.where(sel, slot_ks[:, None, :], ks)
                    vs = jnp.where(sel, slot_vs[:, None, :], vs)
                    kq = jnp.clip(jnp.round(
                        kf / jnp.maximum(ks, 1e-12)[:, :, None, :, None]),
                        -127, 127).astype(psub["k"].dtype)
                    vq = jnp.clip(jnp.round(
                        vf / jnp.maximum(vs, 1e-12)[:, :, None, :, None]),
                        -127, 127).astype(psub["v"].dtype)
                    d = dict(psub)
                    # arena rows hold a token's KV heads side by side
                    d["k"] = psub["k"].at[:, page_idx].set(
                        kq.reshape(nper, npages, ps, kv * hd))
                    d["v"] = psub["v"].at[:, page_idx].set(
                        vq.reshape(nper, npages, ps, kv * hd))
                    d["k_scale"] = psub["k_scale"].at[:, page_idx].set(ks)
                    d["v_scale"] = psub["v_scale"].at[:, page_idx].set(vs)
                    d["slot_k_scale"] = psub["slot_k_scale"].at[:, slot].set(
                        slot_ks)
                    d["slot_v_scale"] = psub["slot_v_scale"].at[:, slot].set(
                        slot_vs)
                    d["k_max"] = psub["k_max"].at[:, slot].set(0.0)
                    d["v_max"] = psub["v_max"].at[:, slot].set(0.0)
                    d["len"] = psub["len"].at[:, slot].set(true_len)
                    for cc in ("ck", "cv"):
                        # enc-dec: fixed-size encoder-output K/V sidecars
                        # ride beside the paged arena, one row per slot
                        if cc in psub:
                            d[cc] = jax.lax.dynamic_update_slice_in_dim(
                                psub[cc], csub[cc].astype(psub[cc].dtype),
                                slot, axis=1)
                    out.append(d)
                return out

            self._jit_write[npages] = jax.jit(page_write, donate_argnums=0)
        return self._jit_write[npages]

    def _paged_tail_write_fn(self, npages: int):
        """Page scatter for a chunked (tail-only) admission: quantize the
        tail's float cache per page exactly like ``_paged_write_fn``, but

          * the prompt/decode boundary page index is a TRACED operand
            (``boundary = true_len // page_size - skip``, relative to the
            tail's first page — out of range when the prompt is
            page-aligned, exactly like the full path's), and
          * the slot-wide running scales fold in the mapped prefix pages'
            stamped scales. A registered full page's scale IS its own
            |K|max/127 (it is never the boundary page), and max-then-divide
            equals divide-then-max for a positive constant, so the combined
            slot scale is bit-identical to what a full prefill over the
            whole prompt would have computed.
        """
        key = ("tail", npages)
        if key not in self._jit_write:
            ps = self.page_size

            def tail_page_write(pool, cache, slot, page_idx, true_len,
                                boundary, prefix_pages, prefix_np):
                out = []
                W = prefix_pages.shape[0]
                pmask = (jnp.arange(W) < prefix_np)[None, :, None]
                for psub, csub in zip(pool, cache):
                    kf = csub["k"][:, 0].astype(jnp.float32)  # (nper,S,kv,hd)
                    nper, _, kv, hd = kf.shape
                    kf = kf.reshape(nper, npages, ps, kv, hd)
                    vf = csub["v"][:, 0].astype(jnp.float32).reshape(
                        nper, npages, ps, kv, hd)
                    kmax = jnp.max(jnp.abs(kf), axis=(2, 4))  # (nper,np,kv)
                    vmax = jnp.max(jnp.abs(vf), axis=(2, 4))
                    ks = kmax / 127.0
                    vs = vmax / 127.0
                    # prefix page scales (trash-padded entries masked to 0)
                    pks = jnp.where(pmask, psub["k_scale"][:, prefix_pages],
                                    0.0)
                    pvs = jnp.where(pmask, psub["v_scale"][:, prefix_pages],
                                    0.0)
                    slot_ks = jnp.maximum(
                        jnp.maximum(jnp.max(kmax, axis=1), 1e-8) / 127.0,
                        jnp.max(pks, axis=1))
                    slot_vs = jnp.maximum(
                        jnp.maximum(jnp.max(vmax, axis=1), 1e-8) / 127.0,
                        jnp.max(pvs, axis=1))
                    sel = (jnp.arange(npages) == boundary)[None, :, None]
                    ks = jnp.where(sel, slot_ks[:, None, :], ks)
                    vs = jnp.where(sel, slot_vs[:, None, :], vs)
                    kq = jnp.clip(jnp.round(
                        kf / jnp.maximum(ks, 1e-12)[:, :, None, :, None]),
                        -127, 127).astype(psub["k"].dtype)
                    vq = jnp.clip(jnp.round(
                        vf / jnp.maximum(vs, 1e-12)[:, :, None, :, None]),
                        -127, 127).astype(psub["v"].dtype)
                    d = dict(psub)
                    # arena rows hold a token's KV heads side by side
                    d["k"] = psub["k"].at[:, page_idx].set(
                        kq.reshape(nper, npages, ps, kv * hd))
                    d["v"] = psub["v"].at[:, page_idx].set(
                        vq.reshape(nper, npages, ps, kv * hd))
                    d["k_scale"] = psub["k_scale"].at[:, page_idx].set(ks)
                    d["v_scale"] = psub["v_scale"].at[:, page_idx].set(vs)
                    d["slot_k_scale"] = psub["slot_k_scale"].at[:, slot].set(
                        slot_ks)
                    d["slot_v_scale"] = psub["slot_v_scale"].at[:, slot].set(
                        slot_vs)
                    d["k_max"] = psub["k_max"].at[:, slot].set(0.0)
                    d["v_max"] = psub["v_max"].at[:, slot].set(0.0)
                    d["len"] = psub["len"].at[:, slot].set(true_len)
                    out.append(d)
                return out

            self._jit_write[key] = jax.jit(tail_page_write, donate_argnums=0)
        return self._jit_write[key]

    def _rescale_fn(self):
        """Proactive per-page scale refresh for ONE (slot, tail page): bump
        the page and slot scales to cover the slot's observed decode-era
        |K|/|V| maxima (with 10% headroom) and rewrite the page's int8 codes
        from the old scale into the new one. Slot and page are traced — the
        refresh compiles once, ever."""
        if self._jit_rescale is None:
            margin = 1.1 / 127.0

            def rescale(pool, slot, page):
                out = []
                for sub in pool:
                    if not (isinstance(sub, dict) and "k_max" in sub):
                        out.append(sub)     # fixed-size state: no scales
                        continue
                    km = sub["k_max"][:, slot] * margin       # (nper, kv)
                    vm = sub["v_max"][:, slot] * margin
                    old_ks = sub["k_scale"][:, page]
                    old_vs = sub["v_scale"][:, page]
                    new_ks = jnp.maximum(old_ks, km)
                    new_vs = jnp.maximum(old_vs, vm)
                    rk = jnp.where(new_ks > 0,
                                   old_ks / jnp.maximum(new_ks, 1e-12), 1.0)
                    rv = jnp.where(new_vs > 0,
                                   old_vs / jnp.maximum(new_vs, 1e-12), 1.0)
                    nper, _, ps, w = sub["k"].shape
                    kv = km.shape[1]

                    def requant(pages, r):     # (nper, ps, kv*hd) codes
                        x = pages.reshape(nper, ps, kv, w // kv)
                        x = jnp.round(x.astype(jnp.float32)
                                      * r[:, None, :, None])
                        return x.reshape(nper, ps, w).astype(pages.dtype)

                    d = dict(sub)
                    d["k"] = sub["k"].at[:, page].set(
                        requant(sub["k"][:, page], rk))
                    d["v"] = sub["v"].at[:, page].set(
                        requant(sub["v"][:, page], rv))
                    d["k_scale"] = sub["k_scale"].at[:, page].set(new_ks)
                    d["v_scale"] = sub["v_scale"].at[:, page].set(new_vs)
                    d["slot_k_scale"] = sub["slot_k_scale"].at[:, slot].set(
                        jnp.maximum(sub["slot_k_scale"][:, slot], km))
                    d["slot_v_scale"] = sub["slot_v_scale"].at[:, slot].set(
                        jnp.maximum(sub["slot_v_scale"][:, slot], vm))
                    out.append(d)
                return out

            self._jit_rescale = jax.jit(rescale, donate_argnums=0)
        return self._jit_rescale

    def _maybe_refresh_scales(self, over):
        """Refresh the (always private) tail page of every slot the chunk's
        in-graph drift check flagged: its decode-era |K|/|V| maxima exceeded
        ``scale_refresh`` × the admission range."""
        if not self.paged or self.scale_refresh <= 0 or not over.any():
            return
        for i in np.nonzero(over)[0]:
            s = self.slots[i]
            # only decode-era tokens drift, and only a slot that has decoded
            # past its prompt has a PRIVATE tail page to rewrite — shared
            # prefix pages are never refresh targets
            if s is None or s.done or self._lens[i] <= s.prompt_tokens:
                continue
            page = int(self._ptab[i, (self._lens[i] - 1) // self.page_size])
            self.pool = self._rescale_fn()(self.pool, jnp.int32(int(i)),
                                           jnp.int32(page))
            self.scale_refreshes += 1

    def _decode_fn(self, cap: int, chunk: int):
        key = (self.num_slots, cap, chunk)
        if key not in self._jit_decode:
            cfg, bt = self.cfg, self.fm.seg_block_t
            impl = self._impl(self.num_slots, cap)
            # drift detection rides the chunk: the over-threshold flag is
            # computed in-graph from the post-chunk trackers and synced
            # with the tokens — the steady-state path never does extra
            # host round-trips just to learn nothing drifted
            refresh_thr = self.scale_refresh * 127.0 \
                if self.paged and self.scale_refresh > 0 else None
            nslots = self.num_slots

            sample = self._sample

            def decode_chunk(params, pool, tokens, keys, lora_stack,
                             adapter_idx, perm, inv, blocks):
                seg = None
                if impl == "segmented":
                    seg = {"perm": perm, "inv": inv, "block_adapter": blocks,
                           "block_t": bt}

                def body(carry, _):
                    # the per-slot finite flag AND-accumulates through the
                    # scan carry (logits are per-step values — unlike the
                    # drift trackers they cannot be read back post-scan), so
                    # a single NaN step anywhere in the chunk quarantines the
                    # stream; it is a traced OUTPUT synced with the chunk's
                    # tokens — no extra D2H syncs, no new jit keys
                    pool, tok, keys, fin = carry
                    logits, pool = lm.decode_step(
                        params, cfg, tokens=tok, cache=pool, lora=lora_stack,
                        adapter_idx=adapter_idx, lora_impl=impl, lora_seg=seg)
                    fin = fin & lm.finite_logits(logits)
                    nxt, keys = sample(logits, keys)
                    return (pool, nxt, keys, fin), nxt

                fin0 = jnp.ones((nslots,), jnp.bool_)
                (pool, tok, keys, fin), out = jax.lax.scan(
                    body, (pool, tokens, keys, fin0), None, length=chunk)
                drift = jnp.zeros((nslots,), jnp.bool_)
                if refresh_thr is not None:
                    for sub in pool:
                        if isinstance(sub, dict) and "k_max" in sub:
                            o = (sub["k_max"] > refresh_thr * jnp.maximum(
                                    sub["slot_k_scale"], 1e-8)) | \
                                (sub["v_max"] > refresh_thr * jnp.maximum(
                                    sub["slot_v_scale"], 1e-8))
                            drift = drift | jnp.any(o, axis=(0, 2))
                return pool, tok, keys, out.T, drift, fin    # (slots, chunk)

            self._jit_decode[key] = jax.jit(decode_chunk, donate_argnums=1)
        return self._jit_decode[key]

    def _spec_decode_fn(self, cap: int, chunk: int):
        """Self-speculative chunk dispatch (module docstring, speculation
        section): ``chunk`` draft -> verify -> accept steps under ONE jitted
        ``lax.scan``.  Each step drafts up to ``spec_k`` tokens per slot
        from that slot's own device-resident history (prompt-lookup bigram
        match — no draft model), scores all ``k + 1`` window positions in a
        single batched ``lm.verify_step`` forward through the paged cache,
        accepts the longest draft prefix that matches what the backbone
        itself emits, commits the accepted run plus one corrected token,
        and rolls the speculative KV writes past the reject point back by
        resetting ``len`` and the drift trackers (pages past true_len are
        decode-private — never freed, simply overwritten next step).

        Keyed ``("spec", num_slots, cap, chunk, k)`` in the same executable
        cache as the plain decode fns, so restore/compile_count cover it
        for free.  Per-slot acceptance lives INSIDE the scan carry: a mixed
        co-batch never serializes on its slowest stream, and a zero-accept
        slot degrades to exactly today's one-token step."""
        k = self.spec_k
        key = ("spec", self.num_slots, cap, chunk, k)
        if key not in self._jit_decode:
            cfg, bt = self.cfg, self.fm.seg_block_t
            impl = self._impl(self.num_slots, cap)
            refresh_thr = self.scale_refresh * 127.0 \
                if self.paged and self.scale_refresh > 0 else None
            nslots = self.num_slots
            T = k + 1
            # draft sentinel: one past the vocab.  The embed gather clips it
            # to a valid row (harmless garbage compute) and neither argmax
            # nor sampling can ever RETURN it, so a filled position never
            # matches and the step commits exactly one token — the plain
            # decode step, bit for bit.
            FILL = cfg.vocab_size
            force_fill = self.spec_force_fill
            sample = self._sample
            H = self._spec_hist_len
            plain_w = self._plain_pages
            bidx = jnp.arange(nslots)

            def spec_decode_chunk(params, pool, tokens, keys, hist, hlen,
                                  spec_cols, lora_stack, adapter_idx, perm,
                                  inv, blocks):
                seg = None
                if impl == "segmented":
                    seg = {"perm": perm, "inv": inv, "block_adapter": blocks,
                           "block_t": bt}
                # widen the page tables with the speculative headroom
                # columns (the pool carries the plain-width table so every
                # non-spec plane compiles bit-identically to a spec_k=0
                # engine); sliced back off before returning
                widened, ci = [], 0
                for sub in pool:
                    if isinstance(sub, dict) and "page_table" in sub:
                        sub = dict(sub)
                        sub["page_table"] = jnp.concatenate(
                            [sub["page_table"], spec_cols[ci]], axis=-1)
                        ci += 1
                    widened.append(sub)
                pool = widened

                def draft_fn(tok, hist, hlen):
                    # prompt-lookup drafter: find the LATEST earlier
                    # occurrence of the current (prev, tok) bigram in the
                    # slot's history and propose the k tokens that followed
                    # it.  Pure in-graph gather/compare — runs under the
                    # scan so later steps draft from tokens committed
                    # earlier in the SAME chunk.
                    if force_fill:
                        return jnp.full((nslots, k), FILL, jnp.int32), \
                            jnp.zeros((nslots,), jnp.int32)
                    prev = jnp.take_along_axis(
                        hist, jnp.maximum(hlen - 2, 0)[:, None], axis=1)[:, 0]
                    mt = (hist[:, :-1] == prev[:, None]) \
                        & (hist[:, 1:] == tok[:, None]) \
                        & (jnp.arange(H - 1)[None] + 1 < (hlen - 1)[:, None])
                    has = jnp.any(mt, axis=1)
                    jbest = (H - 2) - jnp.argmax(mt[:, ::-1], axis=1)
                    src = jbest[:, None] + 2 + jnp.arange(k)[None]
                    cand = jnp.take_along_axis(
                        hist, jnp.minimum(src, H - 1), axis=1)
                    valid = has[:, None] & (src < hlen[:, None])
                    draft = jnp.where(valid, cand.astype(jnp.int32),
                                      jnp.int32(FILL))
                    return draft, jnp.sum(valid.astype(jnp.int32), axis=1)

                def body(carry, _):
                    pool, tok, keys, hist, hlen, fin = carry
                    draft, nprop = draft_fn(tok, hist, hlen)
                    seq = jnp.concatenate([tok[:, None], draft], axis=1)
                    logits, pool = lm.verify_step(
                        params, cfg, tokens=seq, cache=pool, lora=lora_stack,
                        adapter_idx=adapter_idx, lora_impl=impl, lora_seg=seg)
                    # per-position sampling: greedy consumes no PRNG (keys
                    # pass through untouched — bit-exact vs the sequential
                    # engine); sampled mode advances each row's key once per
                    # WINDOW position, so its PRNG stream diverges from the
                    # non-speculative engine's (documented approximate: each
                    # committed token is still an exact conditional sample)
                    ts = []
                    for j in range(T):
                        t_j, keys = sample(logits[:, j], keys)
                        ts.append(t_j)
                    g = jnp.stack(ts, axis=1)                      # (B, T)
                    match = (draft == g[:, :k]).astype(jnp.int32)
                    m = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                    nxt = jnp.take_along_axis(g, (m - 1)[:, None],
                                              axis=1)[:, 0]
                    # quarantine only on COMMITTED positions: the rejected
                    # tail conditions on wrong tokens and its logits are
                    # discarded anyway
                    fin_pos = lm.finite_logits(logits)             # (B, T)
                    fin = fin & jnp.all(
                        fin_pos | (jnp.arange(T)[None] >= m[:, None]), axis=1)
                    # rollback = tracker reset: len and the drift maxima
                    # rewind to the commit point's running values (the
                    # verify layer stacked a positionwise cummax for exactly
                    # this gather); int8 codes/scales past the rolled-back
                    # len sit above it where the next write overwrites them.
                    # The cmax stacks are STRIPPED so the carry pytree keeps
                    # the plain pool structure across scan steps.
                    rolled = []
                    for sub in pool:
                        if isinstance(sub, dict) and "k_cmax" in sub:
                            d = dict(sub)
                            selm = jnp.broadcast_to(
                                (m - 1)[None, :, None, None],
                                sub["k_cmax"].shape[:2] + (1,)
                                + sub["k_cmax"].shape[3:])
                            d["k_max"] = jnp.take_along_axis(
                                sub["k_cmax"], selm, axis=2)[:, :, 0]
                            d["v_max"] = jnp.take_along_axis(
                                sub["v_cmax"], selm, axis=2)[:, :, 0]
                            d["len"] = sub["len"] - T + m[None, :]
                            del d["k_cmax"], d["v_cmax"]
                            rolled.append(d)
                        else:
                            rolled.append(sub)
                    # committed tokens append to the device history so later
                    # scan steps draft from them; uncommitted columns
                    # scatter out of bounds and drop
                    wpos = jnp.where(jnp.arange(T)[None] < m[:, None],
                                     hlen[:, None] + jnp.arange(T)[None], H)
                    hist = hist.at[bidx[:, None], wpos].set(g, mode="drop")
                    hlen = hlen + m
                    return (rolled, nxt, keys, hist, hlen, fin), (g, m, nprop)

                fin0 = jnp.ones((nslots,), jnp.bool_)
                (pool, tok, keys, hist, hlen, fin), (gs, ms, ps) = \
                    jax.lax.scan(body, (pool, tokens, keys, hist, hlen, fin0),
                                 None, length=chunk)
                drift = jnp.zeros((nslots,), jnp.bool_)
                if refresh_thr is not None:
                    for sub in pool:
                        if isinstance(sub, dict) and "k_max" in sub:
                            o = (sub["k_max"] > refresh_thr * jnp.maximum(
                                    sub["slot_k_scale"], 1e-8)) | \
                                (sub["v_max"] > refresh_thr * jnp.maximum(
                                    sub["slot_v_scale"], 1e-8))
                            drift = drift | jnp.any(o, axis=(0, 2))
                narrowed = []
                for sub in pool:
                    if isinstance(sub, dict) and "page_table" in sub:
                        sub = dict(sub)
                        sub["page_table"] = sub["page_table"][..., :plain_w]
                        narrowed.append(sub)
                    else:
                        narrowed.append(sub)
                pool = narrowed
                # gs: (slots, chunk, T) committed-candidate tokens;
                # ms/ps: (slots, chunk) commit / proposal counts per step
                return (pool, tok, keys, gs.transpose(1, 0, 2), ms.T, ps.T,
                        drift, fin)

            self._jit_decode[key] = jax.jit(spec_decode_chunk,
                                            donate_argnums=1)
        return self._jit_decode[key]

    def _spec_history(self):
        """Host-side build of the per-slot (history, length) pair the
        speculative drafter reads on device: prompt + generated tokens,
        right-padded to ``_spec_hist_len``; position ``hlen - 1`` holds the
        pending token (``_tokens``).  Rebuilt per dispatch — the device
        copy mutates inside the scan and is deliberately discarded (host
        state stays the single source of truth across preempt/spill)."""
        H = self._spec_hist_len
        hist = np.zeros((self.num_slots, H), np.int32)
        hlen = np.zeros((self.num_slots,), np.int32)
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            seq = np.concatenate([
                np.asarray(s.prompt, np.int64).reshape(-1),
                np.asarray(s.tokens, np.int64).reshape(-1),
            ]).astype(np.int32)[-H:]
            hist[i, :len(seq)] = seq
            hlen[i] = len(seq)
        return jnp.asarray(hist), jnp.asarray(hlen)

    def _spec_dispatch_now(self) -> bool:
        """Adaptive spec/plain demotion: keep speculating while the accept
        EMA clears ``spec_disable_below`` committed tokens per slot-step;
        below it, demote to the plain fn (counted in ``spec_fallbacks``)
        and re-probe speculatively after ``spec_probe_every`` plain
        dispatches so a workload that turns self-similar again is
        re-detected.  Probes are cheap by construction: they clamp to a
        ONE-step chunk (the chunk-1 spec executable is already in the
        warmed ladder) so a dry probe costs about one extra plain
        dispatch instead of a full verify-width chunk, and consecutive
        dry probes back the re-probe interval off exponentially (capped
        at 16x) so a sustained adversarial trace pays a vanishing probe
        tax.  Both executables are warmed, so flipping modes never
        recompiles."""
        self._spec_probe = False
        if self.spec_k <= 0 or not self.paged:
            return False
        if self._spec_accept_ema == 0.0 \
                or self._spec_accept_ema >= self.spec_disable_below:
            self._spec_cool = 0
            self._spec_probe_interval = self.spec_probe_every
            return True
        self._spec_cool += 1
        if self._spec_cool >= self._spec_probe_interval:
            self._spec_cool = 0
            self._spec_probe = True
            return True
        self.spec_fallbacks += 1
        return False

    # ---- segment metadata (per composition, not per token) ----
    def _segments(self, cap: int):
        if self._impl(self.num_slots, cap) != "segmented":
            z = jnp.zeros((1,), jnp.int32)      # gather never reads these
            return z, z, z
        key = (self._slot_adapters.tobytes(), cap)
        if key != self._seg_key:
            perm, inv, blocks = self.fm.segment_meta(self._slot_adapters, cap, 1)
            self._seg_dev = (jnp.asarray(perm), jnp.asarray(inv),
                             jnp.asarray(blocks))
            self._seg_key = key
        return self._seg_dev

    def _spec_segments(self, cap: int):
        """Segment metadata for the speculative verify co-batch: same
        composition as ``_segments`` but ``spec_k + 1`` tokens per slot row
        (the verify window flattens row-major, matching ``segment_meta``'s
        per-token repeat).  Memoized on the composition signature, so
        steady-state dispatches never touch the host metadata path."""
        if self._impl(self.num_slots, cap) != "segmented":
            z = jnp.zeros((1,), jnp.int32)      # gather never reads these
            return z, z, z
        key = (self._slot_adapters.tobytes(), cap)
        if key != self._spec_seg_key:
            perm, inv, blocks = self.fm.segment_meta(
                self._slot_adapters, cap, self.spec_k + 1)
            self._spec_seg_dev = (jnp.asarray(perm), jnp.asarray(inv),
                                  jnp.asarray(blocks))
            self._spec_seg_key = key
        return self._spec_seg_dev

    def _prefill_segments(self, adapter_slot: int, cap: int, plen: int):
        if self._impl(1, cap) != "segmented":
            z = jnp.zeros((1,), jnp.int32)
            return z, z, z
        ids = np.full((plen,), adapter_slot, np.int32)
        perm, inv, blocks = self.fm.segment_meta(ids, cap, 1)
        return jnp.asarray(perm), jnp.asarray(inv), jnp.asarray(blocks)

    def bucket_for_prompt(self, n: int) -> int:
        """Smallest admission bucket holding an n-token prompt."""
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.prompt_buckets[-1]

    def bucket_for_tail(self, n: int) -> int:
        """Smallest tail bucket holding an n-token private tail (chunked
        shared-prefix admission). Tail buckets are powers of two of the
        page size, capped at ``prompt_len`` — a static jit key, so any mix
        of tail lengths across sharer churn reuses the same executables."""
        for b in self.tail_buckets:
            if n <= b:
                return b
        return self.tail_buckets[-1]

    # ---- serving surface ----
    def _norm_enc_feats(self, enc_feats) -> np.ndarray:
        """Normalize one stream's encoder input to the engine's fixed
        ``(enc_len, d_model)`` frame shape. ``None`` means zero frames (the
        stub-frontend analogue of silence) so decoder-only callers — the
        serve-loop warmup included — join without change. The encoder is
        bidirectional, so frame count is STRICT: zero-padding would change
        every encoder output, not just the tail."""
        d = self.cfg.d_model
        if enc_feats is None:
            return np.zeros((self.enc_len, d), np.float32)
        enc_feats = np.asarray(enc_feats, np.float32).reshape(-1, d)
        assert enc_feats.shape[0] == self.enc_len, \
            (f"enc_feats must carry exactly enc_len={self.enc_len} frames "
             f"(got {enc_feats.shape[0]}): the encoder is bidirectional, "
             f"padding is not transparent")
        return enc_feats

    def join(self, task_id: str, prompt: np.ndarray, *,
             adapter_id: Optional[str] = None, max_new_tokens: int = 8,
             rid: int = -1, eos_id: Optional[int] = None,
             deadline: Optional[float] = None,
             enc_feats: Optional[np.ndarray] = None) -> int:
        """Admit one request: prefill its prompt (LoRA applied, K/V int8-
        quantized in-graph), scatter it into a free slot (paged: into freshly
        allocated pages), produce the first token. Returns the slot index.

        A full pool behaves per layout: the dense pool raises (its capacity
        is the static slot count — the caller must drain first); the paged
        pool **defers** — the request queues FIFO and admits during a later
        ``step_chunk`` once a slot AND enough free pages exist — returning
        -1. Deferral, not failure: a burst beyond capacity drains instead of
        crashing the serving tick.

        Admission is variable-length: the prompt is right-padded to the
        smallest prompt-length bucket that holds it (a static jit key —
        at most ``len(prompt_buckets)`` prefill executables ever compile)
        while the true length is a traced operand masking the pads out of
        attention and the KV cache. Prompts longer than the largest bucket
        keep their LAST ``prompt_len`` tokens (causal LM: the suffix
        matters) — that loses context, so it WARNS; the decode budget clamps
        to the pool's ``max_new`` capacity."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.cfg.is_encoder_decoder:
            enc_feats = self._norm_enc_feats(enc_feats)   # validate at join
        req = _PendingJoin(task_id=task_id, prompt=prompt,
                           adapter_id=adapter_id,
                           max_new_tokens=max_new_tokens, rid=rid,
                           eos_id=eos_id,
                           deadline=float("inf") if deadline is None
                           else float(deadline),
                           enc_feats=enc_feats
                           if self.cfg.is_encoder_decoder else None)
        if self.paged and not self.can_admit(len(prompt), prompt=prompt,
                                             adapter_id=adapter_id):
            # deferral must be able to END: a request whose prompt bucket +
            # chunk headroom (minus the pages its prefix currently shares)
            # exceeds the whole arena would pend forever (drain() and the
            # serve loop would spin) — that is a pool configuration error,
            # not backpressure. A request that only fits BECAUSE of the
            # discount and whose registered sharer later retires becomes
            # STRANDED: it stays queued without blocking others, and only
            # a full engine wedge raises (_raise_if_wedged).
            if self._never_fits(req):
                plen = self.bucket_for_prompt(min(max(len(prompt), 1),
                                                  self.prompt_len))
                base = self._pages_for(self._adm_s_max(plen)) + \
                    self._pages_for(self._headroom_tokens())
                raise ValueError(
                    f"prompt needs {base} pages (bucket {plen} + chunk "
                    f"headroom) beyond any shared prefix but the arena "
                    f"only has {self.total_pages - 1} usable pages; raise "
                    f"total_pages or shrink prompt_buckets/chunk")
            self.pending.append(req)
            self.deferrals += 1
            return -1
        if not self.free_slots():
            raise RuntimeError("no free decode slots; step_chunk() first")
        return self._admit_now(req)

    def _admit_now(self, req: _PendingJoin) -> int:
        with tracing.span("fmplex.admit", rid=req.rid) as sp:
            return self._admit(req, sp)

    def _admit(self, req: _PendingJoin, sp) -> int:
        if req.resume is not None and self.paged and self.spill is not None:
            slot = self._try_spill_resume(req)
            if slot is not None:
                return slot
        t_adm = time.perf_counter()
        prompt = req.prompt
        if len(prompt) > self.prompt_len:
            warnings.warn(
                f"prompt of {len(prompt)} tokens exceeds the engine's largest "
                f"admission bucket ({self.prompt_len}); left-truncating to "
                f"the last {self.prompt_len} tokens (context is lost — size "
                f"prompt_buckets to the workload)", RuntimeWarning,
                stacklevel=2)
            prompt = prompt[-self.prompt_len:]     # causal LM: suffix matters
        true_prompt = prompt
        # variable-length bucketed admission for every stack: attention masks
        # right-pads out of its K/V and the recurrent scans carry state
        # through them unchanged, so the bucket is the only jit key
        true_len = max(1, len(prompt))
        sp.set(prompt=true_len)
        plen = self.bucket_for_prompt(true_len)
        if len(prompt) < plen:                     # right-pad to the bucket
            prompt = np.concatenate(
                [prompt, np.zeros(plen - len(prompt), np.int32)])
        max_new_tokens = max(1, min(req.max_new_tokens, self.max_new))
        slot = self.free_slots()[0]
        cap = self.fm.adapters.capacity()
        aslot = self.fm.adapters.index(req.adapter_id)
        if self.paged and self.chunked_prefill:
            admitted = self._try_admit_tail(req, true_prompt, true_len, slot,
                                            cap, aslot, max_new_tokens, t_adm)
            if admitted is not None:
                return admitted
        perm, inv, blocks = self._prefill_segments(aslot, cap, plen)
        # encoder operand: (1, enc_len, d) frames for enc-dec, None (an
        # empty pytree leaf — same trace) for decoder-only stacks
        enc = jnp.asarray(self._norm_enc_feats(req.enc_feats)[None]) \
            if self.cfg.is_encoder_decoder else None
        first, fin, key, cache = self._prefill_fn(cap, plen)(
            self.fm.params, jnp.asarray(prompt[None]),
            jnp.full((1,), true_len, jnp.int32), enc, self._keys[slot][None],
            self.fm.adapters.stacked(), jnp.full((1,), aslot, jnp.int32),
            perm, inv, blocks)
        self._keys = self._keys.at[slot].set(key[0])
        # the prefill consumed real device work whether or not the stream
        # survives it — record the admission for token-level charging
        self.admissions += 1
        self.tail_tokens_computed += true_len   # full prefill: whole prompt
        if req.resume is None:
            self.admitted_log.append((req.rid, req.task_id, true_len,
                                      true_len))
        # numeric health rides the admission's existing host sync: a
        # non-finite prefill (poisoned adapter / Inf activations) quarantines
        # RIGHT HERE — no pages allocated, no pool write, and crucially no
        # prefix registration (NaN K/V must never enter the COW registry
        # where later joins would map it)
        fin_ok = bool(np.asarray(fin)[0])
        if not fin_ok:
            self.quarantines += 1
        if fin_ok and self.paged:
            npages = self._pages_for(self._adm_s_max(plen))
            shared = self._match_prefix(req.adapter_id, true_prompt)
            m = len(shared)
            # continue the digest chain into the spill arena: spilled
            # prefix pages are restored by DMA into this admission's own
            # freshly allocated pages (positions m..m+k-1), verified
            # against their digests, and re-registered below — this full
            # path is the fallback when the chunked tail admission above
            # declined (nothing shareable, or not enough free pages), so
            # the prefill recomputed those positions and its content is
            # discarded in favor of the restored bit-exact pages
            spilled = self._match_spilled_prefix(req.adapter_id, true_prompt,
                                                 m)
            k = len(spilled)
            priv = self._take_pages(npages - m)
            pages = priv
            if m:
                self._share_pages(shared)
                self.prefix_hits += 1
                self.shared_pages_mapped += m
                pages = np.concatenate(
                    [np.asarray(shared, np.int32), priv])
            if k:
                blob = [
                    {key: np.concatenate([e.blob[j][key] for _, e in spilled],
                                         axis=1)
                     for key in ("k", "v", "k_scale", "v_scale")}
                    for j in range(len(spilled[0][1].blob))]
                self._restore_pages(blob, priv[:k])
                for key, _ in spilled:
                    self.spill.pop(("prefix", key))
                self.spill_prefix_hits += 1
                self.restored_pages += k
            # COW admission: the slot MAPS the shared prefix pages, but the
            # scatter points those positions at the trash page — their
            # (bit-identical) content is already in the arena and must not
            # be rewritten while other streams read it; restored spilled
            # positions are likewise masked so the scatter cannot overwrite
            # the restored content
            scatter = pages.copy()
            scatter[:m + k] = TRASH_PAGE
            self.pool = self._paged_write_fn(npages)(
                self.pool, cache, jnp.int32(slot), jnp.asarray(scatter),
                jnp.int32(true_len))
            self._ptab[slot, :npages] = pages
            self._held[slot] = npages
            self._lens[slot] = true_len
            # trim: bucket padding beyond the true length scattered zero
            # pages — release them now (always private: the shared prefix
            # never extends past the prompt); decode growth re-allocates
            keep = self._pages_for(true_len)
            if keep < npages:
                self._release_pages(self._ptab[slot, keep:npages])
                self._ptab[slot, keep:npages] = TRASH_PAGE
                self._held[slot] = keep
            self._register_prefix(req.adapter_id, true_prompt, slot,
                                  true_len, cache=cache)
            self._ptab_dirty = True
        elif fin_ok:
            self.pool = self._write_fn()(self.pool, cache, slot)
        return self._finish_admission(req, slot, aslot, first, fin_ok,
                                      true_prompt, true_len, max_new_tokens,
                                      t_adm)

    def _try_admit_tail(self, req: _PendingJoin, true_prompt: np.ndarray,
                        true_len: int, slot: int, cap: int, aslot: int,
                        max_new_tokens: int, t_adm: float) -> Optional[int]:
        """Chunked shared-prefix admission: when the prompt's leading pages
        are already in the arena (registered by a live sharer, or restorable
        from the prefix spill tier), MAP them and prefill only the private
        tail — the tail's queries attend the mapped int8 pages dequantized
        through the page vector. Returns the slot, or None to fall back to
        the always-correct full prefill (nothing shareable, or the tail
        bucket + restores need more free pages than the arena has right
        now — the admission gate budgeted for the full path, not this
        one)."""
        ps = self.page_size
        shared = self._match_prefix(req.adapter_id, true_prompt)
        spilled = self._match_spilled_prefix(req.adapter_id, true_prompt,
                                             len(shared))
        # always leave >= 1 tail token: the first generated token needs a
        # real last-position forward pass, and the boundary page decode
        # appends into must be recomputed into a PRIVATE copy — a fully
        # registered page-aligned prompt re-prefills its last page
        skip = min(len(shared) + len(spilled), (true_len - 1) // ps)
        if skip < 1:
            return None
        m_eff = min(len(shared), skip)
        k_eff = skip - m_eff
        spilled = spilled[:k_eff]
        tail_len = true_len - skip * ps
        tbucket = self.bucket_for_tail(tail_len)
        npages = self._pages_for(tbucket)
        if len(self._free_pages) < k_eff + npages:
            return None
        # ---- map phase: shared pages ref++, spilled pages restored H2D ----
        shared_eff = np.asarray(shared[:m_eff], np.int32)
        priv_restore = self._take_pages(k_eff)
        priv_tail = self._take_pages(npages)
        if m_eff:
            self._share_pages(shared_eff)
        if k_eff:
            blob = [
                {key: np.concatenate([e.blob[j][key] for _, e in spilled],
                                     axis=1)
                 for key in ("k", "v", "k_scale", "v_scale")}
                for j in range(len(spilled[0][1].blob))]
            self._restore_pages(blob, priv_restore)
            # spill entries are popped only AFTER the numeric-health gate:
            # a quarantined admission must not cost the arena its prefix
        prefix_ids = np.full((self._prefix_width,), TRASH_PAGE, np.int32)
        prefix_ids[:m_eff] = shared_eff
        prefix_ids[m_eff:skip] = priv_restore
        # ---- tail-compute phase ----
        tail = true_prompt[skip * ps:]
        if len(tail) < tbucket:
            tail = np.concatenate(
                [tail, np.zeros(tbucket - len(tail), np.int32)])
        perm, inv, blocks = self._prefill_segments(aslot, cap, tbucket)
        # exact-parity float path when EVERY mapped page still has its float
        # sidecar (live pages in _page_float, spilled pages carrying kf/vf);
        # otherwise attend the int8 arena content dequantized — correct to
        # quantization error, and the only option once the floats are gone
        use_float = (all(int(p) in self._page_float for p in shared_eff)
                     and all("kf" in e.blob[0] for _, e in spilled))
        if use_float:
            # sharers of one live prefix reuse a single assembled + uploaded
            # operand set: registered pages are immutable, so the key (the
            # mapped page ids) fully determines the content, and
            # _release_pages drops entries the moment any member id frees
            fpkey = tuple(int(x) for x in prefix_ids[:skip])
            prefix_fp = self._prefix_fp_cache.get(fpkey)
            if prefix_fp is None:
                srcs = [self._page_float[int(p)] for p in shared_eff] + \
                       [[{"k": sub["kf"], "v": sub["vf"]} for sub in e.blob]
                        for _, e in spilled]
                prefix_fp = []
                for i in range(len(srcs[0])):
                    k0 = srcs[0][i]["k"]        # (nper, ps, kv, hd)
                    bk = np.zeros((k0.shape[0], 1, self._prefix_width * ps)
                                  + k0.shape[2:], k0.dtype)
                    bv = np.zeros_like(bk)
                    for j, src in enumerate(srcs):
                        bk[:, 0, j * ps:(j + 1) * ps] = src[i]["k"]
                        bv[:, 0, j * ps:(j + 1) * ps] = src[i]["v"]
                    prefix_fp.append({"k": jnp.asarray(bk),
                                      "v": jnp.asarray(bv)})
                while len(self._prefix_fp_cache) >= 32:   # FIFO bound
                    self._prefix_fp_cache.pop(
                        next(iter(self._prefix_fp_cache)))
                self._prefix_fp_cache[fpkey] = prefix_fp
            first, fin, key, cache = self._tail_prefill_fn(
                cap, tbucket, "float")(
                self.fm.params, prefix_fp, jnp.asarray(tail[None]),
                jnp.full((1,), tail_len, jnp.int32),
                jnp.full((1,), skip * ps, jnp.int32), self._keys[slot][None],
                self.fm.adapters.stacked(), jnp.full((1,), aslot, jnp.int32),
                perm, inv, blocks)
        else:
            first, fin, key, cache = self._tail_prefill_fn(
                cap, tbucket, "pages")(
                self.fm.params, self.pool, jnp.asarray(tail[None]),
                jnp.full((1,), tail_len, jnp.int32), jnp.asarray(prefix_ids),
                jnp.full((1,), skip * ps, jnp.int32), self._keys[slot][None],
                self.fm.adapters.stacked(), jnp.full((1,), aslot, jnp.int32),
                perm, inv, blocks)
        self._keys = self._keys.at[slot].set(key[0])
        self.admissions += 1
        self.tail_tokens_computed += tail_len
        self.prefill_tokens_saved += skip * ps
        if req.resume is None:
            self.admitted_log.append((req.rid, req.task_id, true_len,
                                      tail_len))
        fin_ok = bool(np.asarray(fin)[0])
        if not fin_ok:
            # quarantined tail: roll the map phase back — shared refcounts
            # drop to their pre-join values, restored/tail pages return to
            # the free list (none were registered, so nothing re-spills),
            # and the untouched spill entries keep the prefix restorable
            self.quarantines += 1
            if m_eff:
                self._release_pages(shared_eff)
            self._release_pages(priv_restore)
            self._release_pages(priv_tail)
        else:
            # restored prefix pages get their float sidecars back from the
            # spill blob (kept exact across the D2H round trip), so they
            # keep serving float-mode tails to future sharers
            for pg, (_, e) in zip(priv_restore, spilled):
                if "kf" in e.blob[0]:
                    self._page_float[int(pg)] = [
                        {"k": sub["kf"], "v": sub["vf"]} for sub in e.blob]
            for key_, _ in spilled:
                self.spill.pop(("prefix", key_))
            if k_eff:
                self.spill_prefix_hits += 1
                self.restored_pages += k_eff
            if m_eff:
                self.prefix_hits += 1
                self.shared_pages_mapped += m_eff
            boundary = true_len // ps - skip
            self.pool = self._paged_tail_write_fn(npages)(
                self.pool, cache, jnp.int32(slot), jnp.asarray(priv_tail),
                jnp.int32(true_len), jnp.int32(boundary),
                jnp.asarray(prefix_ids), jnp.int32(skip))
            self._ptab[slot, :skip] = prefix_ids[:skip]
            self._ptab[slot, skip:skip + npages] = priv_tail
            self._held[slot] = skip + npages
            self._lens[slot] = true_len
            # trim tail-bucket padding beyond the true length (always
            # private pages; the prefix never extends past the prompt)
            keep = self._pages_for(true_len)
            if keep < skip + npages:
                self._release_pages(self._ptab[slot, keep:skip + npages])
                self._ptab[slot, keep:skip + npages] = TRASH_PAGE
                self._held[slot] = keep
            # a float-mode tail's cache is exact, so its freshly registered
            # pages earn sidecars of their own; a pages-mode tail carries
            # the prefix dequantization error and must not seed sidecars
            # that future sharers would trust as exact
            self._register_prefix(req.adapter_id, true_prompt, slot,
                                  true_len,
                                  cache=cache if use_float else None,
                                  cache_page0=skip)
            self._ptab_dirty = True
        return self._finish_admission(req, slot, aslot, first, fin_ok,
                                      true_prompt, true_len, max_new_tokens,
                                      t_adm)

    def _finish_admission(self, req: _PendingJoin, slot: int, aslot: int,
                          first, fin_ok: bool, true_prompt: np.ndarray,
                          true_len: int, max_new_tokens: int,
                          t_adm: float) -> int:
        if self.state_pool is not None:
            # strict 1:1 with the decode slot — a double allocation here is
            # a lifecycle bug (some exit path didn't free), not backpressure
            self.state_pool.alloc(slot)
        self._tokens = self._tokens.at[slot].set(first[0])
        now = time.perf_counter()
        tok0 = int(first[0])
        eos = self.eos_id if req.eos_id is None else req.eos_id
        if req.resume is not None:
            # preempted stream resuming: keep its identity/latency stamps,
            # append the re-prefill's next token to the existing stream.
            # s.prompt deliberately stays the ORIGINAL prompt — s.tokens
            # still holds everything generated, so a SECOND preemption
            # rebuilds prompt+tokens without duplicating the first resume's
            # prefix (and re-truncates from the fullest context available)
            s = req.resume
            s.tokens.append(tok0)
            s.done = (not fin_ok or len(s.tokens) >= s.max_new or
                      (s.eos_id is not None and tok0 == s.eos_id))
            if not fin_ok:
                s.status = "quarantined"
            self.slots[slot] = s
            # a stale spill entry (free pages or budget forced the fallback)
            # no longer matches the stream's state once it decodes again
            self._drop_stream_spill(s.rid)
            self.resume_costs.append(("reprefill", now - t_adm))
        else:
            self.slots[slot] = DecodeSlot(
                rid=req.rid, task_id=req.task_id, adapter_slot=aslot,
                max_new=max_new_tokens, eos_id=eos,
                tokens=[tok0], t_join=now, t_first=now,
                prompt_tokens=true_len, prompt=true_prompt,
                adapter_id=req.adapter_id, deadline=req.deadline,
                status="ok" if fin_ok else "quarantined",
                done=(not fin_ok or max_new_tokens == 1
                      or (eos is not None and tok0 == eos)),
                enc_feats=req.enc_feats)
        self._slot_adapters[slot] = aslot
        self._seg_key = None                    # composition changed
        return slot

    def leave(self, slot: int) -> DecodeSlot:
        """Retire a slot (finished or cancelled) and free it for admission
        (paged: its pages return to the free list)."""
        s = self.slots[slot]
        assert s is not None, slot
        self.slots[slot] = None
        self._slot_adapters[slot] = FREE
        self._seg_key = None                    # composition changed
        if self.paged:
            self._release_slot_pages(slot)
        if self.state_pool is not None:
            self.state_pool.free(slot)
        # keep the freed slot's cache length bounded while it idles
        for sub in self.pool:
            if isinstance(sub, dict) and "len" in sub:
                sub["len"] = sub["len"].at[:, slot].set(0)
        return s

    # ---- paged page-pressure handling ----
    def _preempt(self, slot: int):
        """Evict a live stream to reclaim its pages: it re-queues at the
        FRONT of the pending queue with its generated prefix folded into the
        prompt (re-admission also refreshes its int8 scales). With a spill
        arena attached, the victim's pages/scales/trackers/PRNG key are
        captured D2H first and resume restores them by H2D copy — exact
        token AND sampling-stream parity; the folded prompt is kept as the
        recompute fallback for when the host budget evicts the spill.
        Without an arena, sampling streams lose PRNG continuity across a
        preemption; greedy streams resume exactly."""
        s = self.slots[slot]
        if self.spill is not None:
            self._spill_stream(slot, s)
        prompt = np.concatenate([
            np.asarray(s.prompt if s.prompt is not None else [], np.int32),
            np.asarray(s.tokens, np.int32)])
        self.slots[slot] = None
        self._slot_adapters[slot] = FREE
        self._seg_key = None
        self._release_slot_pages(slot)
        if self.state_pool is not None:
            # the victim's dense state is NOT captured (spill is demoted on
            # such stacks): re-admission re-prefills the folded prompt, which
            # recomputes recurrent state exactly
            self.state_pool.free(slot)
        for sub in self.pool:
            if isinstance(sub, dict) and "len" in sub:
                sub["len"] = sub["len"].at[:, slot].set(0)
        self.pending.appendleft(_PendingJoin(
            task_id=s.task_id, prompt=prompt, adapter_id=s.adapter_id,
            max_new_tokens=s.max_new, rid=s.rid, eos_id=s.eos_id, resume=s,
            enc_feats=s.enc_feats))
        self.preemptions += 1

    def _ensure_chunk_pages(self):
        """Top every live slot up to ``len + _headroom_tokens()`` tokens of
        pages before the chunk dispatches (``chunk`` tokens, or
        ``chunk * (k + 1)`` under speculation — speculative writes land
        above ``len`` before acceptance rolls ``len`` back, so the pages
        must exist up front). When the free list runs dry, preempt the
        youngest live streams (least work redone) until it doesn't; a single
        stream that cannot fit is a configuration error (pool smaller than
        one stream's chunk growth)."""
        while True:
            live = [i for i, s in enumerate(self.slots)
                    if s is not None and not s.done]
            preempted = False
            for i in live:
                if self.slots[i] is None:       # preempted by an earlier pass
                    continue
                need = self._pages_for(self._lens[i]
                                       + self._headroom_tokens()) \
                    - self._held[i]
                if need <= 0:
                    continue
                while need > len(self._free_pages):
                    victims = [j for j in live
                               if j != i and self.slots[j] is not None
                               and not self.slots[j].done]
                    if not victims:
                        raise RuntimeError(
                            f"paged pool exhausted: {need} pages needed for "
                            f"one stream, {len(self._free_pages)} free and "
                            f"nothing left to preempt (total_pages="
                            f"{self.total_pages} is too small)")
                    self._preempt(min(
                        victims, key=lambda j: len(self.slots[j].tokens)))
                    preempted = True
                pages = self._take_pages(need)
                h = self._held[i]
                self._ptab[i, h:h + need] = pages
                self._held[i] = h + need
                self._ptab_dirty = True
            if not preempted:
                return

    def _never_fits(self, req: _PendingJoin) -> bool:
        """True when the request cannot be admitted even into an EMPTY
        arena, counting the pages its prefix currently shares — deferring
        it would spin forever."""
        plen = self.bucket_for_prompt(min(max(len(req.prompt), 1),
                                          self.prompt_len))
        m = len(self._match_prefix(req.adapter_id, req.prompt))
        return (self._pages_for(self._adm_s_max(plen)) - m
                + self._pages_for(self._headroom_tokens())) \
            > self.total_pages - 1

    def _viable_pending(self) -> list[int]:
        """Pending indices that could fit the arena at its CURRENT sharing
        state. A deferred join admitted on the strength of a prefix
        discount whose registered sharer has since retired is STRANDED: it
        stays queued (a later admission re-registering its prefix would
        unstrand it) but is invisible to the drain and exempt from the
        head-of-line fairness cap — it cannot be starved of something no
        amount of waiting provides. ``step_chunk`` raises only when the
        whole engine wedges on stranded entries (nothing live, nothing
        viable), the one state no future engine event can fix."""
        return [i for i, r in enumerate(self.pending)
                if not self._never_fits(r)]

    def _spill_resume_need(self, req: _PendingJoin) -> Optional[int]:
        """Gate-level page need to resume ``req`` from its stream spill, or
        None when no usable entry exists (nothing spilled / budget evicted
        it / it could never fit even an empty arena — the gate then prices
        the legacy re-prefill instead). A spill resume restores the TRUE
        page count held at preemption, which can exceed the re-prefill
        bucket's (truncated) estimate — pricing it honestly is what lets
        ``_try_spill_resume`` actually find its pages free."""
        if self.spill is None or req.resume is None:
            return None
        entry = self.spill.peek(("stream", req.rid))
        if entry is None:
            return None
        n = int(entry.meta["n_pages"])
        hr = self._pages_for(self._headroom_tokens())
        if n + hr > self.total_pages - 1:
            return None
        return n + hr + self._imminent_page_need()

    def _next_admissible_pending(self) -> Optional[int]:
        """Index of the next deferred join the pool can take: the (viable)
        head, or — bounded lookahead — a smaller prompt within
        ``pending_lookahead`` viable entries of it whose pages ARE free
        while the head's are not. Skip-ahead is capped: after
        ``hol_skip_cap`` consecutive bypasses the window collapses to the
        head alone until it admits, so a large blocked head is delayed,
        never starved."""
        if not self.pending or not self.free_slots():
            return None
        viable = self._viable_pending()
        window = 1 if self._hol_skips >= self.hol_skip_cap else \
            self.pending_lookahead
        for idx in viable[:window]:
            req = self.pending[idx]
            need = self._admission_need(len(req.prompt), prompt=req.prompt,
                                        adapter_id=req.adapter_id)
            spill_need = self._spill_resume_need(req)
            if spill_need is not None:
                # both resume paths must be viable: the spill restore (its
                # true page count) AND the re-prefill fallback it degrades
                # to on a digest mismatch discovered at restore time
                need = max(need, spill_need)
            if len(self._free_pages) >= need:
                return idx
        return None

    def can_admit_pending(self) -> bool:
        return self._next_admissible_pending() is not None

    def _drain_pending(self):
        """Admit deferred joins while slots and pages allow — FIFO with the
        bounded skip-ahead of ``_next_admissible_pending`` (one large prompt
        at the head no longer starves small prompts queued behind it).
        Bypassing a stranded entry never consumes the fairness budget."""
        while True:
            idx = self._next_admissible_pending()
            if idx is None:
                return
            req = self.pending[idx]
            bypassed_viable = any(not self._never_fits(self.pending[i])
                                  for i in range(idx))
            del self.pending[idx]
            if bypassed_viable:
                self._hol_skips += 1
                self.hol_bypasses += 1
            else:
                self._hol_skips = 0
            self._admit_now(req)

    # ---- failure semantics: deadlines, cancellation, terminal rejection ----
    def _reject_pending(self, p: _PendingJoin, status: str):
        p.status = status
        if p.resume is not None:
            p.resume.status = status
            p.resume.done = True
            self._drop_stream_spill(p.rid)
        self.rejected.append(p)

    def _expire_deadlines(self, now: float):
        """Deadline enforcement on chunk entry: live slots past their
        deadline are marked done (``deadline_cancelled``) and retire through
        the normal sweep with their partial tokens; expired pending entries
        are terminally rejected — ``deadline_shed`` if never admitted,
        ``deadline_cancelled`` for a preempted resume (it was mid-flight),
        ``rejected_stranded`` when the entry is stranded (satellite of the
        stranded-sharer fix: a stranded join with a deadline no longer idles
        forever)."""
        for s in self.slots:
            if s is not None and not s.done and s.deadline < now:
                s.done = True
                s.status = "deadline_cancelled"
                self.deadline_cancels += 1
        if not self.pending:
            return
        keep: collections.deque[_PendingJoin] = collections.deque()
        for p in self.pending:
            if p.deadline >= now:
                keep.append(p)
            elif self._never_fits(p):
                self._reject_pending(p, "rejected_stranded")
                self.stranded_rejections += 1
            elif p.resume is not None:
                self._reject_pending(p, "deadline_cancelled")
                self.deadline_cancels += 1
            else:
                self._reject_pending(p, "deadline_shed")
                self.deadline_sheds += 1
        if len(keep) != len(self.pending):
            self.pending = keep

    def cancel(self, rid: int):
        """Client-cancel one stream by rid wherever it lives. Returns
        ``("slot", DecodeSlot)`` for a live stream (retired through
        ``leave`` — pages refcount-released, registry references dropped),
        ``("pending", _PendingJoin)`` for a deferred or preempted entry
        (popped; a preempted resume's pages were already freed at
        preemption), or ``None`` when the rid is not in the engine."""
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                s.done = True
                s.status = "cancelled"
                self.cancels += 1
                return ("slot", self.leave(i))
        for i, p in enumerate(self.pending):
            if p.rid == rid:
                del self.pending[i]
                p.status = "cancelled"
                if p.resume is not None:
                    p.resume.status = "cancelled"
                    p.resume.done = True
                    self._drop_stream_spill(p.rid)
                self.cancels += 1
                return ("pending", p)
        return None

    def shed_stranded(self) -> int:
        """Terminally reject every stranded pending entry (regardless of
        deadline) into ``rejected`` — the serve loop's graceful-degradation
        path when the engine would otherwise wedge. Returns the count."""
        keep: collections.deque[_PendingJoin] = collections.deque()
        n = 0
        for p in self.pending:
            if self._never_fits(p):
                self._reject_pending(p, "rejected_stranded")
                self.stranded_rejections += 1
                n += 1
            else:
                keep.append(p)
        if n:
            self.pending = keep
        return n

    def take_rejected(self) -> list[_PendingJoin]:
        """Drain the terminally rejected pending entries (serve-loop hook)."""
        out, self.rejected = self.rejected, []
        return out

    def take_admitted(self) -> list[tuple[int, str, int, int]]:
        """Drain the (rid, task_id, true_prompt_len, tail_tokens) admission
        log — the serve loop charges prompt tokens from HERE, at actual
        admission, so a join that deferred and was later shed never carried
        a charge. ``tail_tokens`` is what the prefill ACTUALLY computed
        (< true_prompt_len when a chunked admission mapped a shared prefix);
        fair-share accounting charges it, not the full prompt — a sharer
        must not be billed for compute the registry saved it."""
        out, self.admitted_log = self.admitted_log, []
        return out

    def take_decode_charges(self) -> dict:
        """Drain the committed-decode-token log, keyed ``(task_id, rid)`` —
        the serve loop charges fair-share decode budgets from HERE.  Every
        dispatch logs the tokens each stream actually COMMITTED
        (speculative: accepted + corrected; plain: the chunk length), so
        under speculation a high-accept task is charged for its real
        throughput instead of the old uniform ``chunk x active_slots``
        split.  The rid in the key lets drain-synchronous callers skip
        streams already priced at arrival."""
        out = dict(self._decode_charges)
        self._decode_charges = collections.Counter()
        return out

    def spec_task_accept_rates(self) -> dict:
        """Per-task draft accept rate (accepted / proposed, cumulative) —
        the per-task gauges ``serving.metrics`` exports."""
        return {t: (a / p if p else 0.0)
                for t, (p, a) in self._spec_task_stats.items()}

    def _raise_if_wedged(self):
        """Nothing live, nothing viable, stranded joins pending: no future
        engine event can admit them (new joins defer behind the pending
        queue, so the re-registration that would unstrand them can never
        happen either) — drain()/the serve loop would spin forever. Loud
        configuration error instead."""
        if self.pending and self.active_count() == 0 \
                and not self._viable_pending():
            raise ValueError(
                f"{len(self.pending)} deferred prompt(s) no longer fit the "
                f"arena ({self.total_pages - 1} usable pages) — the shared "
                f"prefix they were admitted against was released and "
                f"nothing is left to free; raise total_pages or shrink "
                f"prompt_buckets/chunk")

    # ---- deadline overrun clamp (satellite) ----
    def chunk_ladder(self) -> tuple[int, ...]:
        """The only chunk lengths the clamp ever dispatches (descending):
        full, half, single-step. A small fixed ladder keeps the set of
        decode jit keys bounded — ``warm_decode_ladder`` can precompile all
        of them so deadline traffic never recompiles in steady state."""
        return tuple(sorted({self.chunk, max(1, self.chunk // 2), 1},
                            reverse=True))

    def _effective_chunk(self, live: list[int], now: float) -> int:
        """Deadlines are only checked on chunk entry, so a full chunk can
        overrun a tight SLO by ``chunk - 1`` steps. When the nearest live
        deadline is closer than a full chunk (measured against the per-step
        EMA), shrink this dispatch to the largest ladder length that still
        lands within ~one step of the deadline."""
        if not self.deadline_clamp or self._step_ema <= 0.0:
            return self.chunk
        tight = min((self.slots[i].deadline for i in live), default=float("inf"))
        if tight == float("inf"):
            return self.chunk
        room = max(1, int(np.ceil((tight - now) / self._step_ema)))
        if room >= self.chunk:
            return self.chunk
        for c in self.chunk_ladder():
            if c <= room:
                self.deadline_clamps += 1
                return c
        self.deadline_clamps += 1
        return 1

    def warm_decode_ladder(self):
        """Precompile (and dispatch once) every ladder chunk length against
        the live pool so the deadline clamp never recompiles in steady
        state. Only callable while no stream is live: the garbage rows this
        steps land in the trash page (paged) or in regions the next
        admission overwrites wholesale (dense) — the same free-slots-keep-
        stepping contract the engine already relies on. Sampling PRNG keys
        DO advance (they advance every chunk for every slot anyway)."""
        assert self.active_count() == 0, \
            "warm_decode_ladder must run on an idle engine"
        if self.paged:
            self._sync_page_table()
        cap = self.fm.adapters.capacity()
        perm, inv, blocks = self._segments(cap)
        for c in self.chunk_ladder():
            self.pool, self._tokens, self._keys, _, _, _ = \
                self._decode_fn(cap, c)(
                    self.fm.params, self.pool, self._tokens, self._keys,
                    self.fm.adapters.stacked(),
                    jnp.asarray(self._slot_adapters), perm, inv, blocks)

    def warm_speculative(self):
        """Precompile (and dispatch once) the speculative decode fn for
        every ladder chunk length, so spec/plain mode flips and deadline
        clamps never recompile in steady state.  Same idle-engine garbage
        contract as ``warm_decode_ladder``: every free slot's history is
        empty (hlen 0 -> drafter proposes nothing -> each step commits one
        token into the trash page)."""
        assert self.active_count() == 0, \
            "warm_speculative must run on an idle engine"
        if self.spec_k <= 0 or not self.paged:
            return
        self._sync_page_table()
        cap = self.fm.adapters.capacity()
        perm, inv, blocks = self._spec_segments(cap)
        hist = jnp.zeros((self.num_slots, self._spec_hist_len), jnp.int32)
        hlen = jnp.zeros((self.num_slots,), jnp.int32)
        for c in self.chunk_ladder():
            self.pool, self._tokens, self._keys, *_ = \
                self._spec_decode_fn(cap, c)(
                    self.fm.params, self.pool, self._tokens, self._keys,
                    hist, hlen, self._spec_cols,
                    self.fm.adapters.stacked(),
                    jnp.asarray(self._slot_adapters), perm, inv, blocks)

    def warm_spill(self):
        """Precompile the spill tier's D2H gather and H2D restore scatters
        so spill traffic, spilled-prefix restores and spill resumes never
        retrace in steady state. The warm round trip is a no-op: an empty
        capture reads only the trash page (garbage by contract), the
        restore scatters zeros back into it, and slot 0's running state is
        written back to itself unchanged."""
        assert self.active_count() == 0, \
            "warm_spill must run on an idle engine"
        if self.spill is None or not self.paged:
            return
        none_ = np.empty((0,), np.int32)
        blob = self._capture_pages(none_, 0)
        self._restore_pages(blob, none_)
        state = [{k: sub[k] for k in ("slot_k_scale", "slot_v_scale",
                                      "k_max", "v_max")} for sub in blob]
        self.pool = self._slot_restore_fn()(self.pool, state, jnp.int32(0),
                                            jnp.int32(int(self._lens[0])))

    def warm_rescale(self):
        """Precompile the proactive scale refresh, which first runs whenever
        a slot first drifts — possibly mid-measurement. Idle-engine garbage
        contract: it rewrites the trash page and slot 0's running scales,
        both of which the next admission into slot 0 overwrites."""
        assert self.active_count() == 0, \
            "warm_rescale must run on an idle engine"
        if not (self.paged and self.scale_refresh > 0):
            return
        self.pool = self._rescale_fn()(self.pool, jnp.int32(0),
                                       jnp.int32(TRASH_PAGE))

    def warm_chunked(self):
        """Precompile the chunked-admission planes (one tail prefill + one
        tail-page scatter per tail bucket) so sharer joins never recompile
        in steady state, whatever tail length they arrive with. The warm
        prefill attends only trash-page content behind a masked-out prefix
        window and its outputs are DISCARDED — in particular the advanced
        PRNG key, so a warmed engine's sampling streams stay bit-identical
        to an unwarmed one's. The warm scatter targets the trash page at
        slot 0 with a zero length (idle-engine garbage contract, same as
        ``warm_decode_ladder``)."""
        assert self.active_count() == 0, \
            "warm_chunked must run on an idle engine"
        if not (self.paged and self.chunked_prefill):
            return
        cap = self.fm.adapters.capacity()
        aslot = self.fm.adapters.index(None)
        prefix_ids = jnp.full((self._prefix_width,), TRASH_PAGE, jnp.int32)
        # zero float-prefix operand at the fixed (prefix_width) shape every
        # float-mode tail call uses — same dtype as the sidecar slices
        # (native cache dtype), so the warm trace is the steady-state trace
        warm_fp = [{"k": c["k"], "v": c["v"]}
                   for c, p in zip(
                       lm.init_cache(self.cfg, 1,
                                     self._prefix_width * self.page_size,
                                     kv_quant=False), self.pool)
                   if isinstance(p, dict) and "page_table" in p]
        for tb in self.tail_buckets:
            perm, inv, blocks = self._prefill_segments(aslot, cap, tb)
            self._tail_prefill_fn(cap, tb, "float")(
                self.fm.params, warm_fp, jnp.zeros((1, tb), jnp.int32),
                jnp.full((1,), tb, jnp.int32),
                jnp.zeros((1,), jnp.int32), self._keys[0][None],
                self.fm.adapters.stacked(), jnp.full((1,), aslot, jnp.int32),
                perm, inv, blocks)
            self._tail_prefill_fn(cap, tb, "pages")(
                self.fm.params, self.pool, jnp.zeros((1, tb), jnp.int32),
                jnp.full((1,), tb, jnp.int32), prefix_ids,
                jnp.zeros((1,), jnp.int32), self._keys[0][None],
                self.fm.adapters.stacked(), jnp.full((1,), aslot, jnp.int32),
                perm, inv, blocks)
            npages = self._pages_for(tb)
            cache = lm.init_cache(self.cfg, 1, npages * self.page_size,
                                  kv_quant=False)
            self.pool = self._paged_tail_write_fn(npages)(
                self.pool, cache, jnp.int32(0),
                jnp.full((npages,), TRASH_PAGE, jnp.int32), jnp.int32(0),
                jnp.int32(-1), prefix_ids, jnp.int32(0))

    def step_chunk(self) -> list[DecodeSlot]:
        """Advance every occupied slot by up to ``chunk`` tokens under one
        jitted scan; retire and return the slots that finished. Paged:
        streams already done retire FIRST (their pages fund deferred
        admissions and spare a live stream from preemption), then deferred
        admissions drain into the freed capacity, then live slots top up
        with pages for the chunk and the page table syncs. Entered with
        nothing occupied and only STRANDED deferred joins left, raises the
        wedge configuration error — checked on ENTRY so the call that
        retires the last live stream still returns it. Deadline enforcement
        runs first: expired live slots are marked done (and retire below),
        expired pending entries are terminally rejected — so a wedge of
        deadline-carrying strandeds clears itself instead of raising."""
        with tracing.span("fmplex.decode_chunk", live=0, steps=0) as sp:
            return self._step_chunk(sp)

    def _step_chunk(self, sp) -> list[DecodeSlot]:
        t0 = time.perf_counter()
        self._expire_deadlines(t0)
        if self.paged:
            self._raise_if_wedged()
        retired = [self.leave(i) for i, s in enumerate(self.slots)
                   if s is not None and s.done]
        if self.paged:
            self._drain_pending()
        live = [i for i, s in enumerate(self.slots)
                if s is not None and not s.done]
        if live and self.paged:
            with tracing.span("fmplex.page_topup"):
                self._ensure_chunk_pages()
            # preemption may have evicted members of the live set
            live = [i for i, s in enumerate(self.slots)
                    if s is not None and not s.done]
        finished = []
        if live:
            if self.paged:
                self._sync_page_table()
            eff = self._effective_chunk(live, t0)
            cap = self.fm.adapters.capacity()
            use_spec = self._spec_dispatch_now()
            if use_spec and self._spec_probe:
                eff = 1     # probes are single-step (see _spec_dispatch_now)
            t_disp = time.perf_counter()
            if use_spec:
                perm, inv, blocks = self._spec_segments(cap)
                hist, hlen = self._spec_history()
                self.pool, self._tokens, self._keys, out_g, out_m, out_p, \
                    drift, fin = self._spec_decode_fn(cap, eff)(
                        self.fm.params, self.pool, self._tokens, self._keys,
                        hist, hlen, self._spec_cols,
                        self.fm.adapters.stacked(),
                        jnp.asarray(self._slot_adapters), perm, inv, blocks)
                with tracing.span("fmplex.chunk_sync"):
                    out_g = np.asarray(out_g)   # (slots, eff, k+1): one sync
                    out_m = np.asarray(out_m)   # (slots, eff) commit counts
                    out_p = np.asarray(out_p)   # (slots, eff) proposals
                    fin = np.asarray(fin)
            else:
                perm, inv, blocks = self._segments(cap)
                self.pool, self._tokens, self._keys, out, drift, fin = \
                    self._decode_fn(cap, eff)(
                        self.fm.params, self.pool, self._tokens, self._keys,
                        self.fm.adapters.stacked(),
                        jnp.asarray(self._slot_adapters), perm, inv, blocks)
                with tracing.span("fmplex.chunk_sync"):
                    out = np.asarray(out)       # one host sync per chunk
                    fin = np.asarray(fin)       # rides the same sync
            # per-SCAN-STEP cost: the deadline clamp reasons in scan steps
            # either way, and a speculative step's extra verify cost is
            # exactly what the EMA must learn for the ladder to clamp right
            dt = (time.perf_counter() - t_disp) / eff
            self._step_ema = dt if self._step_ema == 0.0 \
                else 0.5 * self._step_ema + 0.5 * dt
            self.steps += eff
            self.live_slot_steps += len(live) * eff
            sp.set(live=len(live), steps=eff)
            with tracing.span("fmplex.chunk_commit"):
                if self.paged:
                    for i, s in enumerate(self.slots):
                        if s is not None:
                            self._lens[i] += int(out_m[i].sum()) if use_spec \
                                else eff
                now = time.perf_counter()
                for i in live:
                    s = self.slots[i]
                    if use_spec:
                        committed = [int(t) for st in range(eff)
                                     for t in out_g[i, st, :out_m[i, st]]]
                        prop = int(out_p[i].sum())
                        self.spec_commits += len(committed)
                        self.draft_proposed += prop
                        self.draft_accepted += len(committed) - eff
                        ts = self._spec_task_stats.setdefault(
                            s.task_id, [0, 0])
                        ts[0] += prop
                        ts[1] += len(committed) - eff
                    else:
                        committed = [int(t) for t in out[i]]
                    # fair-share accounting charges tokens actually COMMITTED
                    # for this stream (accepted + corrected), never a flat
                    # chunk x active_slots — see take_decode_charges()
                    self._decode_charges[(s.task_id, s.rid)] += len(committed)
                    take = min(len(committed), s.max_new - len(s.tokens))
                    for t in committed[:take]:
                        s.tokens.append(t)
                        if s.eos_id is not None and t == s.eos_id:
                            break
                    # quarantine check only for LIVE slots: a freed slot's
                    # garbage row may legitimately go non-finite (stale scales)
                    # and must not trip anything
                    if not fin[i]:
                        s.done = True
                        s.status = "quarantined"
                        self.quarantines += 1
                        finished.append(i)
                    elif len(s.tokens) >= s.max_new or (
                            s.eos_id is not None and s.tokens[-1] == s.eos_id):
                        s.done = True
                        finished.append(i)
                if use_spec:
                    self.spec_dispatches += 1
                    rate = sum(int(out_m[i].sum()) for i in live) \
                        / max(eff * len(live), 1)
                    ema = self._spec_accept_ema
                    self._spec_accept_ema = rate if ema == 0.0 \
                        else 0.5 * ema + 0.5 * rate
                    if self._spec_probe:
                        # dry probe (1.0 committed/slot-step == zero accepts)
                        # -> back off; any acceptance -> restore the base
                        # cadence and let the EMA drive re-promotion
                        self._spec_probe_interval = min(
                            self._spec_probe_interval * 2,
                            self.spec_probe_every * 16) if rate <= 1.0 \
                            else self.spec_probe_every
            self._maybe_refresh_scales(np.asarray(drift))
        retired += [self.leave(i) for i in finished]
        self.last_chunk_s = time.perf_counter() - t0
        return retired

    def drain(self) -> list[DecodeSlot]:
        """Step until every occupied slot retires (and, paged, every deferred
        admission has been served)."""
        out = []
        while self.active_count() or self.pending:
            out += self.step_chunk()
        return out

    # ---- engine snapshot / restore (durability layer) ----
    _COUNTERS = ("steps", "live_slot_steps", "admissions", "deferrals",
                 "preemptions", "prefix_hits", "shared_pages_mapped",
                 "scale_refreshes", "hol_bypasses", "_hol_skips", "quarantines",
                 "deadline_cancels", "deadline_sheds", "stranded_rejections",
                 "cancels", "spilled_pages", "restored_pages",
                 "digest_failures", "spill_resumes", "spill_prefix_hits",
                 "deadline_clamps", "tail_tokens_computed",
                 "prefill_tokens_saved", "draft_proposed", "draft_accepted",
                 "spec_dispatches", "spec_commits", "spec_fallbacks")

    def _config_dict(self) -> dict:
        """Constructor kwargs that rebuild an identical engine."""
        return {
            "num_slots": self.num_slots, "max_new": self.max_new,
            "chunk": self.chunk, "kv_quant": self.kv_quant,
            "eos_id": self.eos_id, "prompt_buckets": self.prompt_buckets,
            "temperature": self.temperature, "top_k": self.top_k,
            "paged": True, "page_size": self.page_size,
            "total_pages": self.total_pages,
            "prefix_sharing": self.prefix_sharing,
            "scale_refresh": self.scale_refresh,
            "pending_lookahead": self.pending_lookahead,
            "hol_skip_cap": self.hol_skip_cap,
            "deadline_clamp": self.deadline_clamp,
            "chunked_prefill": self.chunked_prefill,
            "spec_k": self.spec_k,
            "spec_force_fill": self.spec_force_fill,
            "spec_disable_below": self.spec_disable_below,
            "spec_probe_every": self.spec_probe_every,
            "enc_len": self.enc_len,
        }

    def snapshot(self) -> EngineSnapshot:
        """Capture the engine's FULL logical state between chunks: used-page
        contents (D2H) with per-page sha256 digests, page tables, refcounts,
        the chained-digest prefix registry, per-slot sampling/PRNG/deadline
        state, the pending queue and counters. The snapshot is isolated
        (deep copies) — the live engine can keep running — and the spill
        arena rides along BY REFERENCE (it is host RAM already). Paged-only:
        the dense layout has no allocator state worth surviving a reset."""
        import copy
        assert self.paged, "snapshot/restore is a paged-arena feature"
        used = np.nonzero(self._page_refs > 0)[0].astype(np.int32)
        idx = jnp.asarray(used)
        pages, slot_state = [], []
        for sub in self._paged_subs():
            host = jax.device_get({
                "k": sub["k"][:, idx], "v": sub["v"][:, idx],
                "k_scale": sub["k_scale"][:, idx],
                "v_scale": sub["v_scale"][:, idx],
                "slot_k_scale": sub["slot_k_scale"],
                "slot_v_scale": sub["slot_v_scale"],
                "k_max": sub["k_max"], "v_max": sub["v_max"],
            })
            pages.append({k: np.asarray(host[k])
                          for k in ("k", "v", "k_scale", "v_scale")})
            slot_state.append({k: np.asarray(host[k])
                               for k in ("slot_k_scale", "slot_v_scale",
                                         "k_max", "v_max")})
        snap = EngineSnapshot(
            config=self._config_dict(),
            used_pages=used, pages=pages, page_digests={},
            slot_state=slot_state,
            ptab=self._ptab.copy(), held=self._held.copy(),
            lens=self._lens.copy(), page_refs=self._page_refs.copy(),
            slot_adapters=self._slot_adapters.copy(),
            tokens=np.asarray(self._tokens), keys=np.asarray(self._keys),
            slots=copy.deepcopy(self.slots),
            pending=copy.deepcopy(list(self.pending)),
            rejected=copy.deepcopy(self.rejected),
            registry=dict(self._prefix_registry),
            page_key=dict(self._page_key),
            counters={k: getattr(self, k) for k in self._COUNTERS},
            spill=self.spill,
            # fixed-size per-slot dense state (recurrent / cross K/V): the
            # page capture above covers only the paged arena
            dense_state=capture_dense_state(self.pool)
            if self.plan.needs_state_slots else None)
        snap.counters["admitted_log"] = list(self.admitted_log)
        snap.page_digests = {int(p): snap.page_digest(i)
                             for i, p in enumerate(used)}
        return snap

    @classmethod
    def restore(cls, fm: PhysicalFM, snap: EngineSnapshot, *,
                reuse_jits_from: Optional["DecodeEngine"] = None
                ) -> "DecodeEngine":
        """Rebuild a fresh engine (and device arena) from a snapshot. Every
        restored page's sha256 digest is recomputed and verified BEFORE any
        stream can decode against it: a corrupted page drops out of the
        registry and every live stream mapping it is requeued through the
        lossless fold-and-re-prefill path (``digest_failures`` counted) —
        recovery can recompute, but it can never serve poisoned KV.

        ``reuse_jits_from`` shares the old engine's jit caches when its
        config matches (an in-process restore after a device reset — the
        executables are code, not device state), making the restored engine
        recompile-free from the first chunk. A cross-process restore (via
        ``checkpoint.ckpt.load_snapshot``) recompiles on first use like any
        fresh engine."""
        import copy
        eng = cls(fm, **snap.config)
        if reuse_jits_from is not None and \
                reuse_jits_from._config_dict() == snap.config and \
                reuse_jits_from.fm is fm:
            for name in ("_jit_prefill", "_jit_decode", "_jit_write",
                         "_jit_rescale", "_jit_gather", "_jit_page_restore",
                         "_jit_slot_restore"):
                setattr(eng, name, getattr(reuse_jits_from, name))
        used = np.asarray(snap.used_pages)
        bad = [int(p) for i, p in enumerate(used)
               if snap.page_digest(i) != snap.page_digests[int(p)]]
        # rebuild the device arena from the host capture: full-shape host
        # arrays (zeros outside used pages), one upload per leaf
        for j, sub in enumerate(eng._paged_subs()):
            cap, st = snap.pages[j], snap.slot_state[j]
            for k in ("k", "v", "k_scale", "v_scale"):
                full = np.zeros(sub[k].shape, np.asarray(cap[k]).dtype)
                if len(used):
                    full[:, used] = cap[k]
                sub[k] = jnp.asarray(full)
            for k in ("slot_k_scale", "slot_v_scale", "k_max", "v_max"):
                sub[k] = jnp.asarray(st[k])
            sub["len"] = jnp.asarray(np.broadcast_to(
                snap.lens[None].astype(np.int32),
                (sub["len"].shape[0], len(snap.lens))))
        eng._ptab = snap.ptab.copy()
        eng._held = snap.held.copy()
        eng._lens = snap.lens.copy()
        eng._page_refs = snap.page_refs.copy()
        eng._free_pages = [p for p in range(eng.total_pages - 1, TRASH_PAGE,
                                            -1) if eng._page_refs[p] == 0]
        eng._prefix_registry = dict(snap.registry)
        eng._page_key = dict(snap.page_key)
        eng._ptab_dirty = True
        eng._slot_adapters = snap.slot_adapters.copy()
        eng._seg_key = None
        eng._tokens = jnp.asarray(snap.tokens)
        eng._keys = jnp.asarray(snap.keys)
        eng.slots = copy.deepcopy(snap.slots)
        eng.pending = collections.deque(copy.deepcopy(snap.pending))
        eng.rejected = copy.deepcopy(snap.rejected)
        if getattr(snap, "dense_state", None) is not None:
            eng.pool = restore_dense_state(eng.pool, snap.dense_state)
        if eng.state_pool is not None:
            # re-mark live slots BEFORE the bad-page requeue below: its
            # _preempt path frees the victim's state slot
            for i, s in enumerate(eng.slots):
                if s is not None:
                    eng.state_pool.alloc(i)
        counters = dict(snap.counters)
        eng.admitted_log = list(counters.pop("admitted_log", []))
        for k in cls._COUNTERS:
            setattr(eng, k, counters.get(k, getattr(eng, k)))
        if snap.spill is not None:
            eng.spill = snap.spill
        # digest-verification contract: streams mapping a corrupted page
        # requeue through the fold (their tokens are host state and intact);
        # the corrupt page's registry entry is gone before any join can map
        # it — the spill capture inside the requeue is suppressed because
        # the device content being captured is exactly what failed to verify
        for p in bad:
            eng.digest_failures += 1
            key = eng._page_key.pop(p, None)
            if key is not None:
                eng._prefix_registry.pop(key, None)
        if bad:
            badset = set(bad)
            for i, s in enumerate(eng.slots):
                if s is None or s.done:
                    continue
                if badset & {int(x) for x in eng._ptab[i, :eng._held[i]]}:
                    sp, eng.spill = eng.spill, None
                    try:
                        eng._preempt(i)
                    finally:
                        eng.spill = sp
        return eng
