"""Paged int8 KV pool: kernel parity (jnp oracle + Pallas interpret mode),
paged-vs-dense decode parity over join/leave churn with ragged prompts, page
recycling after retire, zero recompiles across churn + page allocation,
join-burst deferral (regression: beyond-capacity admission queues and drains
instead of crashing the tick), preemption under page pressure, memory-aware
loop admission, copy-on-write prefix sharing (exact parity, refcounted
release, sharer preemption isolation, admission-gate dedup discount),
bounded pending-queue lookahead (head-of-line regression), the required
prompt length on the paged memory gate, and proactive int8 scale refresh."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.decode_engine import DecodeEngine
from repro.core.physical import PhysicalFM
from repro.kernels import ops, ref
from repro.kernels.paged_decode_attention import paged_decode_attention

BT = 8


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("stablelm-1.6b"))


def _randomized_adapter(fm, i):
    tree = fm.adapters._mod.init_single_adapter(
        jax.random.PRNGKey(i), fm.cfg, fm.adapters.rank)
    leaves, tdef = jax.tree.flatten(tree)
    ks = jax.random.split(jax.random.PRNGKey(100 + i), len(leaves))
    return jax.tree.unflatten(tdef, [
        jax.random.normal(k, l.shape, l.dtype) * 0.05
        for k, l in zip(ks, leaves)])


def _fm(cfg, impl="segmented", na=3):
    fm = PhysicalFM(cfg, seed=0, input_len=8, lora_rank=4, lora_impl=impl,
                    seg_block_t=BT)
    for i in range(na):
        fm.adapters.add(f"lora{i}", _randomized_adapter(fm, i))
    return fm


# ---------------- kernel parity ----------------

def _paged_case(seed=0, B=3, H=8, KV=2, hd=16, ps=8, P=11, MP=4,
                lens=(9, 25, 1), shared=0, free_rows=()):
    """Random int8 pages in the oracle's head-major layout (P, KV, ps, hd),
    disjoint pages per stream; ``shared`` maps row 0's first pages into row
    1 (prefix sharing); ``free_rows`` keep the all-trash table of a free
    slot (their lengths are garbage)."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, hd).astype(np.float32))
    kp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8))
    vp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8))
    ks = jnp.asarray(rng.rand(P, KV).astype(np.float32) * 0.05 + 1e-3)
    vs = jnp.asarray(rng.rand(P, KV).astype(np.float32) * 0.05 + 1e-3)
    pt = np.zeros((B, MP), np.int32)
    free = list(range(1, P))
    rng.shuffle(free)
    for b in range(B):
        if b in free_rows:
            continue
        for j in range(shared if b == 1 else 0, -(-int(lens[b]) // ps)):
            pt[b, j] = free.pop()
    pt[1, :shared] = pt[0, :shared]
    return q, kp, vp, ks, vs, jnp.asarray(pt), jnp.asarray(
        np.asarray(lens, np.int32))


def _model_layout(pages):
    """Oracle pages (P, KV, ps, hd) -> the arena as the pool stores it,
    (P, ps, KV * hd): one row per token, the KV heads side by side."""
    P, KV, ps, hd = pages.shape
    return pages.transpose(0, 2, 1, 3).reshape(P, ps, KV * hd)


@pytest.mark.parametrize("window", [None, 6])
def test_paged_kernel_interpret_matches_ref(window):
    """Pallas paged decode (interpret mode on CPU) on the model-layout arena
    vs the jnp gather oracle."""
    q, kp, vp, ks, vs, pt, lens = _paged_case()
    want = ref.paged_decode_attention_ref(q, kp, vp, ks, vs, pt, lens,
                                          window=window)
    got = paged_decode_attention(q, _model_layout(kp), _model_layout(vp), ks,
                                 vs, pt, lens, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# stablelm's widths (G = 1, hd 64) give 8 pages of 16 a block, so a stream
# of 256 keys walks two blocks; qwen2-7b's (KV 4, H 28, hd 128) give 32
PAGED_CASES = {
    "lengths": dict(B=4, H=32, KV=32, hd=64, ps=16, P=24, MP=16,
                    lens=(1, 16, 17, 256)),
    "shared_prefix": dict(B=3, H=32, KV=32, hd=64, ps=16, P=16, MP=8,
                          lens=(40, 50, 9), shared=2),
    "free_row": dict(B=3, H=32, KV=32, hd=64, ps=16, P=16, MP=8,
                     lens=(70, 37, 20), free_rows=(1,)),
    "mha_hd64": dict(B=3, H=8, KV=8, hd=64, ps=16, P=100, MP=48,
                     lens=(700, 33, 5)),
    "gqa7_hd128": dict(B=3, H=28, KV=4, hd=128, ps=16, P=130, MP=80,
                       lens=(1280, 600, 17)),
    "window": dict(B=3, H=32, KV=32, hd=64, ps=16, P=26, MP=16,
                   lens=(1, 100, 256), window=40),
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_kernel_interpret_cases(case):
    """The kernel (interpret mode) against the oracle across lengths at
    page and block edges, shared prefix pages, a free row (first entry the
    trash page: zeros, whatever its length), MHA and GQA widths and a
    sliding window that skips whole blocks."""
    kw = dict(PAGED_CASES[case])
    window = kw.pop("window", None)
    q, kp, vp, ks, vs, pt, lens = _paged_case(seed=7, **kw)
    want = np.array(ref.paged_decode_attention_ref(
        q, kp, vp, ks, vs, pt, lens, window=window))
    got = np.asarray(paged_decode_attention(
        q, _model_layout(kp), _model_layout(vp), ks, vs, pt, lens,
        window=window, interpret=True))
    for b in kw.get("free_rows", ()):
        assert np.all(got[b] == 0.0)
        want[b] = 0.0
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_ref_matches_dense_int8_ref():
    """Gathering pages into a dense layout and running the dense int8 oracle
    must reproduce the paged oracle exactly (uniform per-stream scales, the
    layout a fresh admission writes)."""
    B, KV, ps, MP, hd = 2, 2, 8, 3, 16
    P = 1 + B * MP
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, 6, hd).astype(np.float32))
    kp = rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8)
    vp = rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8)
    row_ks = rng.rand(B, KV).astype(np.float32) * 0.05 + 1e-3
    row_vs = rng.rand(B, KV).astype(np.float32) * 0.05 + 1e-3
    pt = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    ks = np.zeros((P, KV), np.float32)
    vs = np.zeros((P, KV), np.float32)
    for b in range(B):
        ks[pt[b]] = row_ks[b]
        vs[pt[b]] = row_vs[b]
    lens = np.array([19, 5], np.int32)
    got = ref.paged_decode_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ks),
        jnp.asarray(vs), jnp.asarray(pt), jnp.asarray(lens))
    k_dense = kp[pt].transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * ps, hd)
    v_dense = vp[pt].transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * ps, hd)
    want = ref.decode_attention_int8_ref(
        jnp.asarray(q), jnp.asarray(k_dense), jnp.asarray(v_dense),
        jnp.asarray(row_ks), jnp.asarray(row_vs), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_ops_paged_dispatch_model_layout():
    """The ops wrapper reads the model-layout arena (P, ps, KV * hd)."""
    q, kp, vp, ks, vs, pt, lens = _paged_case(seed=2)
    got = ops.paged_decode_attention(q, _model_layout(kp), _model_layout(vp),
                                     ks, vs, pt, lens)
    want = ref.paged_decode_attention_ref(q, kp, vp, ks, vs, pt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


# ---------------- paged engine vs dense engine ----------------

def _churn(eng, cfg, prompts, names):
    """Join/leave churn with ragged prompt lengths; returns rid->tokens."""
    out = {}
    i = 0
    for i in range(4):
        eng.join(f"t{i}", prompts[i], adapter_id=names[i % 4],
                 max_new_tokens=3 + i, rid=i)
    joined = 4
    while eng.active_count() or eng.pending_count():
        for s in eng.step_chunk():
            out[s.rid] = s.tokens
        while joined < len(prompts) and eng.free_slots() and \
                eng.can_admit(len(prompts[joined])):
            eng.join(f"t{joined}", prompts[joined],
                     adapter_id=names[joined % 4], max_new_tokens=4,
                     rid=joined)
            joined += 1
    return out


def test_paged_matches_dense_over_churn_ragged_prompts(cfg):
    """The paged pool must produce the SAME greedy token streams as the dense
    int8 pool across join/leave churn with ragged prompt lengths — paging is
    a memory layout, not a numeric change."""
    rng = np.random.RandomState(5)
    lens = [8, 3, 6, 1, 7, 4, 8, 2]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    names = ["lora0", "lora1", "lora2", None]
    outs = {}
    for mode in ("dense", "paged"):
        fm = _fm(cfg)
        kw = dict(num_slots=4, prompt_len=8, max_new=8, chunk=2)
        if mode == "paged":
            kw.update(paged=True, page_size=4)
        outs[mode] = _churn(DecodeEngine(fm, **kw), cfg, prompts, names)
    assert outs["paged"] == outs["dense"]
    assert len(outs["paged"]) == len(prompts)


def test_page_recycling_no_stale_leak(cfg):
    """A retired stream's pages go back to the free list; a new stream that
    recycles them must decode exactly like one admitted into a FRESH pool —
    no stale K/V from the previous owner can leak through the masks."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(9)
    p_old = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    p_new = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)

    def serve(eng, p, steps):
        eng.join("t", p, adapter_id="lora0", max_new_tokens=steps, rid=0)
        (d,) = eng.drain()
        return d.tokens

    recycled = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=8,
                            chunk=2, paged=True, page_size=4, total_pages=7)
    first = serve(recycled, p_old, 8)           # fills most of the arena
    assert recycled.free_page_count() == 6      # all pages recycled
    got = serve(recycled, p_new, 6)             # reuses the same pages
    fresh = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=8,
                         chunk=2, paged=True, page_size=4, total_pages=7)
    assert got == serve(fresh, p_new, 6)
    assert len(first) == 8 and len(got) == 6


def test_paged_zero_recompiles_across_churn_and_page_alloc(cfg):
    """After one warm join per prompt bucket, churn — including decode page
    allocation, recycling, deferral-drain — adds ZERO executables: page ids,
    tables and lengths are traced operands, never jit keys."""
    fm = _fm(cfg)
    eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=6, chunk=2,
                       paged=True, page_size=4, prompt_buckets=(4, 16))
    rng = np.random.RandomState(3)
    for plen in (4, 16):                        # warm each bucket once
        eng.join("w", rng.randint(0, cfg.vocab_size, plen),
                 adapter_id="lora0", max_new_tokens=2, rid=-1)
    eng.drain()
    compiles = eng.compile_count()
    names = ["lora0", "lora1", None, "lora2"]
    for i, plen in enumerate((1, 3, 7, 9, 13, 16, 2, 11)):
        eng.join(f"t{i}", rng.randint(0, cfg.vocab_size, plen),
                 adapter_id=names[i % 4], max_new_tokens=2 + i % 3, rid=i)
        if not eng.free_slots():
            eng.step_chunk()
    eng.drain()
    assert eng.compile_count() == compiles
    assert eng.free_page_count() == eng.total_pages - 1


# ---------------- deferral + preemption ----------------

def test_join_burst_defers_and_drains(cfg):
    """Regression for the mid-loop crash: a burst of admissions beyond pool
    capacity must QUEUE (join returns -1) and drain across chunks — every
    stream completes, nothing raises."""
    fm = _fm(cfg, na=1)
    eng = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=8, chunk=2,
                       paged=True, page_size=4, total_pages=9)
    rng = np.random.RandomState(1)
    slots = [eng.join(f"t{i}", rng.randint(0, cfg.vocab_size, 4 + i % 5),
                      adapter_id="lora0", max_new_tokens=6, rid=i)
             for i in range(6)]
    assert slots.count(-1) == 4 and eng.pending_count() == 4
    assert eng.deferrals == 4
    done = eng.drain()
    assert sorted(d.rid for d in done) == list(range(6))
    assert all(len(d.tokens) == 6 for d in done)
    assert eng.free_page_count() == 8           # everything returned


def test_dense_join_still_raises_when_full(cfg):
    """The dense layout keeps its historical contract: static slot capacity,
    the caller drains first."""
    fm = _fm(cfg, na=1)
    eng = DecodeEngine(fm, num_slots=1, prompt_len=8, max_new=4, chunk=2)
    p = np.arange(8) % cfg.vocab_size
    eng.join("a", p, adapter_id="lora0", max_new_tokens=4, rid=0)
    with pytest.raises(RuntimeError, match="no free decode slots"):
        eng.join("b", p, adapter_id="lora0", max_new_tokens=4, rid=1)


def test_page_pressure_preempts_and_completes(cfg):
    """Two long streams on an arena that holds only one to completion: the
    younger stream is preempted (pages reclaimed, re-queued with its
    generated prefix) and BOTH still finish with full budgets. A resumed
    stream must keep its ORIGINAL prompt on the slot — folding the combined
    resume prompt back in would duplicate the generated prefix on a second
    preemption."""
    fm = _fm(cfg, na=1)
    eng = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=24, chunk=4,
                       paged=True, page_size=4, total_pages=10)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    with warnings.catch_warnings():             # resume prompt > bucket warns
        warnings.simplefilter("ignore", RuntimeWarning)
        for i in range(2):
            eng.join(f"t{i}", prompts[i], adapter_id="lora0",
                     max_new_tokens=24, rid=i)
        done = eng.drain()
    assert sorted(d.rid for d in done) == [0, 1]
    assert all(len(d.tokens) == 24 for d in done)
    assert eng.preemptions > 0
    assert eng.free_page_count() == 9
    for d in done:                              # original prompt, always
        np.testing.assert_array_equal(d.prompt, prompts[d.rid])


def test_join_raises_when_prompt_can_never_fit(cfg):
    """A prompt whose bucket + chunk headroom exceeds the whole arena is a
    configuration error: deferring it would spin drain()/the serve loop
    forever, so join must raise immediately."""
    fm = _fm(cfg, na=1)
    eng = DecodeEngine(fm, num_slots=2, prompt_len=16, max_new=8, chunk=4,
                       paged=True, page_size=4, total_pages=3)  # 2 usable
    with pytest.raises(ValueError, match="usable pages"):
        eng.join("t", np.arange(16, dtype=np.int32) % cfg.vocab_size,
                 adapter_id="lora0", max_new_tokens=4, rid=0)


def test_sharer_admitted_on_discount_strands_then_wedge_raises(cfg):
    """A full-length prompt that only fits the arena thanks to its shared
    prefix is ACCEPTED (deferred, not the old ValueError). If its
    registered sharer then retires, the request is stranded: it stops
    blocking other work, and only once the engine has nothing live and
    nothing viable left does step_chunk raise the configuration error —
    never mid-service for unrelated streams."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(61)
    prefix = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    eng = DecodeEngine(fm, num_slots=2, prompt_len=16, max_new=4, chunk=2,
                       paged=True, page_size=4, total_pages=5,  # 4 usable
                       prompt_buckets=(8, 16))
    eng.join("a", prefix, adapter_id="lora0", max_new_tokens=4, rid=0)
    # full 16-token prompt: bucket 4 pages + chunk 1 > 4 usable — only the
    # 2-page prefix discount lets it in (deferred while A holds the pages)
    big = np.concatenate([prefix, rng.randint(0, cfg.vocab_size,
                                              8).astype(np.int32)])
    assert eng.join("b", big, adapter_id="lora0", max_new_tokens=2,
                    rid=1) == -1
    done = []
    with pytest.raises(ValueError, match="no longer fit"):
        for _ in range(50):                     # A retires -> B stranded
            done += eng.step_chunk()
    assert [d.rid for d in done] == [0]         # A served fine regardless
    assert len(done[0].tokens) == 4


# ---------------- copy-on-write prefix sharing ----------------

def _isolated_tokens(fm, prompt, steps, **kw):
    """Reference: the prompt served ALONE on a fresh paged pool."""
    eng = DecodeEngine(fm, num_slots=2, prompt_len=16, max_new=24, chunk=2,
                       paged=True, page_size=4, **kw)
    eng.join("ref", prompt, adapter_id="lora0", max_new_tokens=steps, rid=0)
    (d,) = eng.drain()
    return d.tokens


def test_prefix_sharing_exact_parity_and_dedup(cfg):
    """Streams sharing a page-aligned prompt prefix MAP the registered
    pages instead of copying them — and because admission quantizes per
    page (a page's scale depends only on the tokens it covers), the shared
    engine's token streams are EXACTLY the unshared engine's. After drain,
    every refcount returns to zero and the registry empties."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(21)
    prefix = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)  # 2 pages
    prompts = [np.concatenate([prefix,
                               rng.randint(0, cfg.vocab_size,
                                           1 + i).astype(np.int32)])
               for i in range(3)]
    outs, infos = {}, {}
    for share in (True, False):
        eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=6,
                           chunk=2, paged=True, page_size=4,
                           prefix_sharing=share)
        for i, p in enumerate(prompts):
            eng.join(f"t{i}", p, adapter_id="lora0", max_new_tokens=6, rid=i)
        infos[share] = (eng.shared_page_count(), eng.dedup_saved_pages(),
                        eng.used_page_count())
        outs[share] = {d.rid: d.tokens for d in eng.drain()}
        assert (eng._page_refs[1:] == 0).all()
        assert not eng._prefix_registry and not eng._page_key
        assert eng.free_page_count() == eng.total_pages - 1
    assert outs[True] == outs[False]            # sharing is exact
    shared, saved, used = infos[True]
    _, _, used_unshared = infos[False]
    assert shared == 2 and saved == 4           # 2 sharers x 2 prefix pages
    assert used == used_unshared - saved        # dedup = real pages saved


def test_prefix_sharing_divergent_tails_match_isolated(cfg):
    """COW boundary: sharers with different suffixes each produce the same
    stream as when served ALONE on a fresh pool — private tails never leak
    across the shared prefix pages."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(22)
    prefix = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(0, cfg.vocab_size,
                                           2 + i).astype(np.int32)])
               for i in range(3)]
    eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=8, chunk=2,
                       paged=True, page_size=4)
    for i, p in enumerate(prompts):
        eng.join(f"t{i}", p, adapter_id="lora0", max_new_tokens=8, rid=i)
    assert eng.prefix_hits == 2
    done = {d.rid: d.tokens for d in eng.drain()}
    for i, p in enumerate(prompts):
        assert done[i] == _isolated_tokens(fm, p, 8)


def test_prefix_no_sharing_across_adapters(cfg):
    """LoRA changes the projected V: identical prompts under different
    adapters must NOT share pages."""
    fm = _fm(cfg, na=2)
    rng = np.random.RandomState(23)
    p = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    eng = DecodeEngine(fm, num_slots=4, prompt_len=8, max_new=4, chunk=2,
                       paged=True, page_size=4)
    eng.join("a", p, adapter_id="lora0", max_new_tokens=4, rid=0)
    eng.join("b", p, adapter_id="lora1", max_new_tokens=4, rid=1)
    assert eng.prefix_hits == 0 and eng.shared_page_count() == 0
    eng.join("c", p, adapter_id="lora0", max_new_tokens=4, rid=2)
    assert eng.prefix_hits == 1                 # same adapter DOES share
    eng.drain()


def test_preempt_sharer_keeps_other_stream_valid(cfg):
    """Preempting one sharer releases only ITS references: the surviving
    sharer's mapped pages stay intact and its stream matches the isolated
    reference token for token; the preempted stream resumes and completes
    with its original prompt preserved."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(24)
    prefix = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    pa = np.concatenate([prefix, rng.randint(0, cfg.vocab_size,
                                             2).astype(np.int32)])
    pb = np.concatenate([prefix, rng.randint(0, cfg.vocab_size,
                                             3).astype(np.int32)])
    eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=5, chunk=2,
                       paged=True, page_size=4)
    sa = eng.join("a", pa, adapter_id="lora0", max_new_tokens=5, rid=0)
    sb = eng.join("b", pb, adapter_id="lora0", max_new_tokens=5, rid=1)
    assert eng.shared_page_count() == 2
    eng.step_chunk()                            # both decode a little
    eng._preempt(sb)                            # evict the sharer B
    assert eng.preemptions == 1
    assert eng.shared_page_count() == 0         # B's references dropped...
    refs = eng._page_refs[eng._ptab[sa, :eng._held[sa]]]
    assert (refs == 1).all()                    # ...but A's pages survive
    done = {d.rid: d for d in eng.drain()}
    assert done[0].tokens == _isolated_tokens(fm, pa, 5)
    assert len(done[1].tokens) == 5             # resumed stream completed
    np.testing.assert_array_equal(done[1].prompt, pb)
    assert (eng._page_refs[1:] == 0).all()
    assert eng.free_page_count() == eng.total_pages - 1


def test_admission_gate_discounts_shared_prefix(cfg):
    """The memory gate knows a sharer only allocates its private tail: an
    admission that would NOT fit as a full copy passes ``can_admit`` when
    its prompt shares a registered prefix — the capacity multiplier the
    whole feature exists for."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(25)
    prefix = rng.randint(0, cfg.vocab_size, 12).astype(np.int32)  # 3 pages
    p0 = np.concatenate([prefix, rng.randint(0, cfg.vocab_size,
                                             2).astype(np.int32)])
    p1 = np.concatenate([prefix, rng.randint(0, cfg.vocab_size,
                                             3).astype(np.int32)])
    eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=4, chunk=2,
                       paged=True, page_size=4, total_pages=9,  # 8 usable
                       prompt_buckets=(16,))
    eng.join("a", p0, adapter_id="lora0", max_new_tokens=4, rid=0)
    # full copy: bucket 4 + chunk 1 headroom = 5 > 4 free -> blocked
    assert not eng.can_admit(len(p1))
    fresh = rng.randint(0, cfg.vocab_size, 15).astype(np.int32)
    assert not eng.can_admit(prompt=fresh, adapter_id="lora0")
    # sharer: 3 of its 4 bucket pages are already mapped -> fits
    assert eng.can_admit(prompt=p1, adapter_id="lora0")
    assert eng.join("b", p1, adapter_id="lora0", max_new_tokens=4,
                    rid=1) >= 0
    eng.drain()


def test_can_admit_requires_prompt_len_on_paged(cfg):
    """Regression: the paged memory gate consulted with the old silent
    1-token default wildly under-estimated admissions; the paged path now
    requires the prompt length (dense keeps the cheap slot-only check)."""
    fm = _fm(cfg, na=1)
    paged = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=4, chunk=2,
                         paged=True, page_size=4)
    with pytest.raises(TypeError, match="prompt_tokens"):
        paged.can_admit()
    assert paged.can_admit(8) is True
    assert paged.can_admit(prompt=np.arange(5, dtype=np.int32)) is True
    dense = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=4, chunk=2)
    assert dense.can_admit() is True            # dense: slot check only


# ---------------- pending-queue head-of-line lookahead ----------------

def test_pending_hol_small_admits_past_blocked_large_head(cfg):
    """Regression (head-of-line blocking): with a large deferred prompt at
    the pending head that free pages cannot cover, a small prompt queued
    BEHIND it admits anyway (bounded skip-ahead) — and the head itself
    still completes once pages free up."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(31)
    eng = DecodeEngine(fm, num_slots=4, prompt_len=16, max_new=8, chunk=2,
                       paged=True, page_size=4, total_pages=9,  # 8 usable
                       prompt_buckets=(4, 16))
    # background stream holds half the arena and keeps decoding
    eng.join("bg", rng.randint(0, cfg.vocab_size, 16), adapter_id="lora0",
             max_new_tokens=8, rid=0)
    # large head: bucket 16 needs 4 pages + headroom > 4 free -> defers
    assert eng.join("big", rng.randint(0, cfg.vocab_size, 15),
                    adapter_id="lora0", max_new_tokens=4, rid=1) == -1
    # small prompt behind it: bucket 4 needs 1 page + headroom -> fits
    assert eng.join("small", rng.randint(0, cfg.vocab_size, 3),
                    adapter_id="lora0", max_new_tokens=6, rid=2) == -1
    done = eng.step_chunk()                     # drains the pending queue
    active = [s.rid for s in eng.slots if s is not None]
    assert 2 in active, "small prompt still starved behind the large head"
    assert 1 in eng.pending_rids(), "large head admitted without pages?"
    assert eng.hol_bypasses == 1
    done += eng.drain()                         # head admits as pages free
    assert sorted(d.rid for d in done) == [0, 1, 2]
    assert eng.free_page_count() == eng.total_pages - 1


def test_pending_hol_skip_cap_protects_head(cfg):
    """Fairness: after ``hol_skip_cap`` consecutive bypasses the lookahead
    window collapses to the head alone — later small prompts wait even
    though their pages are free, so the head is delayed, never starved."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(32)
    eng = DecodeEngine(fm, num_slots=8, prompt_len=16, max_new=12, chunk=2,
                       paged=True, page_size=4, total_pages=9,  # 8 usable
                       prompt_buckets=(4, 16), pending_lookahead=8,
                       hol_skip_cap=2)
    eng.join("bg", rng.randint(0, cfg.vocab_size, 16), adapter_id="lora0",
             max_new_tokens=12, rid=0)
    assert eng.join("big", rng.randint(0, cfg.vocab_size, 15),
                    adapter_id="lora0", max_new_tokens=2, rid=1) == -1
    for i in range(4):                          # four small prompts behind
        assert eng.join(f"s{i}", rng.randint(0, cfg.vocab_size, 2),
                        adapter_id="lora0", max_new_tokens=2,
                        rid=10 + i) == -1
    done = eng.step_chunk()
    # exactly hol_skip_cap smalls bypassed; the rest wait behind the head
    assert eng.hol_bypasses == 2
    assert eng.pending_rids()[0] == 1 and 13 in eng.pending_rids()
    done += eng.drain()
    assert sorted(d.rid for d in done) == [0, 1, 10, 11, 12, 13]


def test_boundary_page_stamped_at_slot_scale(cfg):
    """The prompt/decode boundary page (partial page decode appends into)
    must carry the SLOT-WIDE admission scale, not its prompt-local one — a
    page holding a few small-magnitude prompt tokens would otherwise clip
    every decode-era K/V written into it (regression for the per-page
    admission quantize)."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(51)
    eng = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=8, chunk=2,
                       paged=True, page_size=4)
    slot = eng.join("t", rng.randint(0, cfg.vocab_size, 6).astype(np.int32),
                    adapter_id="lora0", max_new_tokens=2, rid=0)
    bpage = int(eng._ptab[slot, 6 // 4])        # partial page (tokens 4-5)
    fpage = int(eng._ptab[slot, 0])             # full prompt page
    for sub in eng.pool:
        if not (isinstance(sub, dict) and "page_table" in sub):
            continue
        slot_ks = np.asarray(sub["slot_k_scale"])[:, slot]
        np.testing.assert_allclose(np.asarray(sub["k_scale"])[:, bpage],
                                   slot_ks, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sub["v_scale"])[:, bpage],
                                   np.asarray(sub["slot_v_scale"])[:, slot],
                                   rtol=1e-6)
        # full pages keep their (finer) content-local scales
        assert (np.asarray(sub["k_scale"])[:, fpage] <= slot_ks + 1e-12).all()
    eng.drain()


# ---------------- proactive int8 scale refresh ----------------

def test_scale_refresh_triggers_deterministically(cfg):
    """With an artificially low threshold the refresh path fires on normal
    decode: the tail page re-quantizes in place (counted), the stream still
    completes, equal configurations reproduce the stream exactly, and the
    refresh adds no executables after its first compile."""
    fm = _fm(cfg, na=1)
    rng = np.random.RandomState(41)
    p = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)

    def stream(**kw):
        eng = DecodeEngine(fm, num_slots=2, prompt_len=8, max_new=12,
                           chunk=4, paged=True, page_size=4, **kw)
        eng.join("t", p, adapter_id="lora0", max_new_tokens=12, rid=0)
        (d,) = eng.drain()
        return d.tokens, eng

    t1, e1 = stream(scale_refresh=0.01)
    assert e1.scale_refreshes > 0
    compiles = e1.compile_count()
    e1.join("t2", p[:5], adapter_id="lora0", max_new_tokens=12, rid=1)
    e1.drain()
    assert e1.scale_refreshes > 1
    assert e1.compile_count() == compiles       # refresh jit compiled once
    t2, _ = stream(scale_refresh=0.01)
    assert t1 == t2                             # deterministic
    t3, e3 = stream(scale_refresh=0.0)          # disabled: never fires
    assert e3.scale_refreshes == 0 and len(t3) == 12


# ---------------- memory-aware loop admission ----------------

def _loop_server(cfg, *, engine_kwargs):
    from repro.core.server import FMplexServer
    from repro.core.vfm import TaskExtensions
    fm = PhysicalFM(cfg, seed=0, input_len=8, lora_rank=4,
                    lora_impl="segmented", seg_block_t=BT)
    fm.calibrate(sizes=(1, 2, 4))
    srv = FMplexServer("s0")
    srv.deploy_fm("fm0", fm, scheduler="bfq")
    fm.adapters.new("lora0", seed=0)
    srv.bind_task("gen", "fm0", weight=1.0,
                  extensions=TaskExtensions(adapter_id="lora0"))
    srv.decode_engine("fm0", **engine_kwargs)
    return srv, srv.serve_loop("fm0")


def test_loop_memory_aware_admission_defers_not_raises(cfg):
    """A generative burst against a tiny paged arena: the loop must DEFER
    admissions while pages are short (requests stay queued, ticks keep
    serving) and still complete every stream; occupancy samples land in
    ``page_samples`` for the kv-page gauges."""
    from repro.core.request import Request
    srv, loop = _loop_server(cfg, engine_kwargs=dict(
        num_slots=2, prompt_len=8, max_new=8, chunk=2,
        paged=True, page_size=4, total_pages=9))
    rng = np.random.RandomState(0)
    trace = [Request("gen", 0.0,
                     payload=rng.randint(0, cfg.vocab_size,
                                         4 + i % 5).astype(np.int32),
                     tokens=float(8 + 6), max_new_tokens=6)
             for i in range(6)]
    served = loop.run(trace)
    assert len(served) == 6
    assert all(r.finish_time is not None and len(r.result) == 6
               for r in served)
    eng = srv.engines["fm0"]
    assert eng.free_page_count() == 8
    # loop admissions are individually vetted by tick()'s can_admit gate
    # (one per admit tick), so none should spill into the engine's own
    # deferral queue — requests wait AT THEIR TAG in the scheduler instead
    assert eng.deferrals == 0
    assert loop.page_samples and max(loop.page_samples) > 0

    from repro.serving.metrics import mixed_stats, page_gauges
    stats = mixed_stats(served, page_samples=loop.page_samples)
    assert stats["kv_pages"]["occupancy_p95"] <= 1.0
    assert stats["decode"]["n"] == 6
    g = page_gauges(eng)
    assert g["paged"] and g["used_pages"] == 0 and g["free_pages"] == 8


def test_long_tail_trace_shape():
    from repro.serving.loadgen import long_tail_token_trace
    tr = long_tail_token_trace("t", 50.0, 4.0, prompt_len=16, vocab=100,
                               new_lo=8, new_hi=512, seed=0,
                               min_prompt_len=2)
    assert len(tr) > 50
    news = np.array([r.max_new_tokens for r in tr])
    assert news.min() >= 8 and news.max() <= 512
    assert np.median(news) < news.mean()        # long tail skews the mean
    assert all(2 <= len(r.payload) <= 16 for r in tr)


def test_shared_prefix_trace_shape():
    from repro.serving.loadgen import shared_prefix_token_trace
    tr = shared_prefix_token_trace("t", 50.0, 4.0, prefix_len=8,
                                   prompt_len=16, vocab=100,
                                   shared_frac=0.8, n_prefixes=2,
                                   max_new=6, seed=0)
    assert len(tr) > 50
    assert all(1 <= len(r.payload) <= 16 for r in tr)
    heads = {r.payload[:8].tobytes() for r in tr}
    counts = sorted((sum(1 for r in tr
                         if r.payload[:8].tobytes() == h) for h in heads),
                    reverse=True)
    # two dominant prefix families cover ~80% of the trace
    assert sum(counts[:2]) > 0.6 * len(tr)
    assert all(1 <= r.max_new_tokens <= 6 for r in tr)


def test_loop_shared_prefix_sampling_and_gauges(cfg):
    """The serve loop on a shared-prefix workload: dedup samples land in
    ``shared_samples``, ``mixed_stats`` grows the kv_sharing section and
    ``page_gauges`` reports the sharing counters."""
    from repro.core.request import Request
    srv, loop = _loop_server(cfg, engine_kwargs=dict(
        num_slots=4, prompt_len=16, max_new=6, chunk=2,
        paged=True, page_size=4))
    rng = np.random.RandomState(7)
    prefix = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    trace = [Request("gen", 0.0,
                     payload=np.concatenate(
                         [prefix, rng.randint(0, cfg.vocab_size,
                                              1 + i % 4).astype(np.int32)]),
                     tokens=float(12 + 4), max_new_tokens=4)
             for i in range(6)]
    served = loop.run(trace)
    assert len(served) == 6
    eng = srv.engines["fm0"]
    assert eng.prefix_hits > 0
    assert loop.shared_samples and max(loop.shared_samples) > 0

    from repro.serving.metrics import mixed_stats, page_gauges
    stats = mixed_stats(served, page_samples=loop.page_samples,
                        shared_samples=loop.shared_samples)
    assert stats["kv_sharing"]["dedup_frac_max"] > 0
    g = page_gauges(eng)
    assert g["prefix_hits"] > 0 and g["dedup_saved_pages"] == 0
    assert g["shared_pages"] == 0 and g["logical_pages"] == 0
