"""Self-speculative decoding: verify-window kernel parity, bit-exact greedy
parity vs the plain engine over ragged churn (join/leave/preempt/spill-
resume), zero-accept == plain-step equivalence, device-length rollback
invariant, zero steady-state recompiles across accept swings (adaptive
demotion included), committed-token charge accounting, and sampled-mode
sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.decode_engine import DecodeEngine
from repro.core.physical import PhysicalFM
from repro.kernels import ops, ref

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


def _fm(cfg, impl="segmented", na=2):
    fm = PhysicalFM(cfg, seed=0, input_len=8, lora_rank=4, lora_impl=impl,
                    seg_block_t=BT)
    for i in range(na):
        fm.adapters.add(f"lora{i}", _randomized_adapter(fm, i))
    return fm


def _copy_inclined(fm):
    """Zero every attention out-projection: logits then depend only on the
    current token, the greedy chain becomes a deterministic bigram machine
    that cycles (pigeonhole over a finite vocab), and the prompt-lookup
    drafter's bigram matches start accepting. Random-weight reduced models
    never self-overlap, so this is the accept-heavy regime's test double."""
    fm.params = jax.tree_util.tree_map_with_path(
        lambda path, l: l * 0.0
        if any(getattr(k, "key", None) == "wo" for k in path) else l,
        fm.params)
    return fm


def _engine(fm, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prompt_len", 16)
    kw.setdefault("max_new", 24)
    kw.setdefault("chunk", 4)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 4)
    kw.setdefault("total_pages", 48)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(fm, **kw)


def _streams(eng_or_done):
    done = eng_or_done.drain() if isinstance(eng_or_done, DecodeEngine) \
        else eng_or_done
    return {d.rid: list(d.tokens) for d in done}


# ---------------- verify-window kernel parity ----------------

def _verify_case(seed=0, B=3, T=5, H=8, KV=2, hd=16, ps=8, P=11, MP=5,
                 lens=(9, 23, 1)):
    """Head-major arena + page tables sized so every row holds its
    base_len + T window positions (speculative writes land above len)."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, T, H, hd).astype(np.float32))
    kp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8))
    vp = jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)).astype(np.int8))
    ks = jnp.asarray(rng.rand(P, KV).astype(np.float32) * 0.05 + 1e-3)
    vs = jnp.asarray(rng.rand(P, KV).astype(np.float32) * 0.05 + 1e-3)
    pt = np.zeros((B, MP), np.int32)
    free = list(range(1, P))
    rng.shuffle(free)
    for b in range(B):
        for j in range(-(-(int(lens[b]) + T) // ps)):
            pt[b, j] = free.pop()
    return q, kp, vp, ks, vs, jnp.asarray(pt), jnp.asarray(
        np.asarray(lens, np.int32))


def _model_layout(pages):
    """Oracle pages (P, KV, ps, hd) -> the arena as the pool stores it."""
    P, KV, ps, hd = pages.shape
    return pages.transpose(0, 2, 1, 3).reshape(P, ps, KV * hd)


@pytest.mark.parametrize("window", [None, 6])
def test_verify_attention_matches_unrolled_ref(window):
    """The fused one-gather XLA verify path must match T independent
    single-token paged decode reads at successive lengths (the oracle) —
    only matmul batching may separate them."""
    q, kp, vp, ks, vs, pt, base = _verify_case()
    want = ref.paged_verify_attention_ref(q, kp, vp, ks, vs, pt, base,
                                          window=window)
    got = ops.paged_verify_attention(
        q, _model_layout(kp), _model_layout(vp), ks, vs, pt, base,
        window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_verify_attention_pallas_fallback_matches_ref():
    """The Pallas backend (unrolled per-position kernel calls, interpret
    mode on CPU) agrees with the oracle too."""
    q, kp, vp, ks, vs, pt, base = _verify_case(seed=3, T=3)
    want = ref.paged_verify_attention_ref(q, kp, vp, ks, vs, pt, base)
    got = ops.paged_verify_attention(
        q, _model_layout(kp), _model_layout(vp), ks, vs, pt, base,
        backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------- greedy parity vs the plain engine ----------------

def test_spec_greedy_parity_zero_accept(cfg):
    """Random weights never self-overlap, so every draft misses and every
    speculative step commits exactly one token — the streams must be
    bit-identical to a plain engine's, and the counters must show real
    proposals with zero accepts."""
    fm = _fm(cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 16, 12, 5)]
    plain = _engine(fm, spec_k=0)
    for i, p in enumerate(prompts):
        plain.join(f"t{i}", p, adapter_id=["lora0", None][i % 2],
                   max_new_tokens=10 + i, rid=i)
    want = _streams(plain)

    spec = _engine(fm, spec_k=4)
    for i, p in enumerate(prompts):
        spec.join(f"t{i}", p, adapter_id=["lora0", None][i % 2],
                  max_new_tokens=10 + i, rid=i)
    got = _streams(spec)
    assert got == want
    assert spec.spec_dispatches >= 1
    assert spec.draft_proposed >= 0 and spec.draft_accepted == 0


def test_spec_force_fill_equals_plain(cfg):
    """``spec_force_fill`` replaces every draft with the out-of-vocab
    sentinel, so acceptance is structurally impossible — the zero-accept
    knob. Output must equal the plain engine's exactly even on an
    accept-heavy (copy-inclined) model."""
    fm = _copy_inclined(_fm(cfg))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, 11).astype(np.int32)
               for _ in range(3)]
    plain = _engine(fm, spec_k=0)
    for i, p in enumerate(prompts):
        plain.join(f"t{i}", p, max_new_tokens=12, rid=i)
    want = _streams(plain)

    spec = _engine(fm, spec_k=3, spec_force_fill=True,
                   spec_disable_below=1.0)      # never demote: all spec steps
    for i, p in enumerate(prompts):
        spec.join(f"t{i}", p, max_new_tokens=12, rid=i)
    got = _streams(spec)
    assert got == want
    assert spec.draft_accepted == 0 and spec.spec_dispatches >= 1


def _dev_lens_match(eng):
    """KV rollback invariant: after every chunk the device length tracker of
    each live slot equals the host's committed length — a partial accept
    rolled ``len`` (and the int8 scale trackers) back rather than leaving
    speculatively-written positions visible."""
    for sub in eng.pool:
        if isinstance(sub, dict) and "page_table" in sub:
            dev = np.asarray(sub["len"])
            for s, st in enumerate(eng.slots):
                if st is not None and not st.done:
                    assert (dev[:, s] == int(eng._lens[s])).all(), \
                        (s, dev[:, s], eng._lens[s])


def test_spec_greedy_parity_accept_heavy_churn(cfg):
    """The load-bearing parity claim: on a copy-inclined model (accepts
    actually fire, rollback actually runs) a speculative engine driven
    through ragged churn — staggered budgets, mid-flight joins, a
    preemption that spills D2H mid-speculation and resumes — produces
    BIT-IDENTICAL greedy streams to the plain engine, while the device
    length tracker never drifts from the host's committed view."""
    fm = _copy_inclined(_fm(cfg))
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 16, 6, 13, 8, 15)]
    budgets = [20, 9, 16, 24, 11, 18]

    def drive(spec_k):
        eng = _engine(fm, spec_k=spec_k, spec_disable_below=1.0,
                      spill_bytes=32 << 20)
        for i in (0, 1):
            eng.join(f"t{i}", prompts[i], adapter_id="lora0",
                     max_new_tokens=budgets[i], rid=i)
        done, nxt, steps = [], 2, 0
        while eng.active_count() or eng.pending or nxt < len(prompts):
            done += eng.step_chunk()
            steps += 1
            if spec_k:
                _dev_lens_match(eng)
            if steps == 2:          # preempt mid-speculation (spills D2H)
                live = [i for i, s in enumerate(eng.slots)
                        if s is not None and not s.done]
                if live:
                    eng._preempt(live[0])
            while nxt < len(prompts) and eng.free_slots() \
                    and not eng.pending:
                eng.join(f"t{nxt}", prompts[nxt],
                         adapter_id=[None, "lora1"][nxt % 2],
                         max_new_tokens=budgets[nxt], rid=nxt)
                nxt += 1
        return _streams(done), eng

    want, _ = drive(0)
    got, spec = drive(4)
    assert set(got) == set(range(len(prompts)))
    assert all(len(got[i]) == budgets[i] for i in got)
    assert got == want
    # accepts really fired (the whole point of the copy-inclined double)
    assert spec.draft_accepted > 0
    rates = spec.spec_task_accept_rates()
    assert rates and max(rates.values()) > 0.5


# ---------------- steady state: zero recompiles across accept swings ------

def test_spec_zero_recompiles_across_accept_swings(cfg):
    """After warming the plain ladder AND the speculative ladder, serving
    must add ZERO executables no matter how the accept rate swings — here
    a random-weight model drives the rate to zero, the EMA demotes to plain
    dispatches and periodically probes speculation again, so both executable
    families (and the demotion boundary between them) are exercised."""
    fm = _fm(cfg)
    eng = _engine(fm, spec_k=4, spec_probe_every=4)
    rng = np.random.RandomState(3)
    # compile both prefill buckets, then both decode ladders
    eng.join("w", rng.randint(0, cfg.vocab_size, 6), max_new_tokens=2, rid=-1)
    eng.join("w", rng.randint(0, cfg.vocab_size, 14), adapter_id="lora0",
             max_new_tokens=2, rid=-1)
    eng.drain()
    eng.warm_decode_ladder()
    eng.warm_speculative()
    compiles = eng.compile_count()

    done, nxt = [], 0
    prompts = [rng.randint(0, cfg.vocab_size, 5 + (i * 3) % 11)
               for i in range(8)]
    while len(done) < len(prompts):
        while nxt < len(prompts) and eng.free_slots() and not eng.pending:
            eng.join(f"t{nxt}", prompts[nxt],
                     adapter_id=[None, "lora1"][nxt % 2],
                     max_new_tokens=6 + nxt % 5, rid=nxt)
            nxt += 1
        done += eng.step_chunk()
    assert eng.compile_count() == compiles
    # both regimes ran: speculative dispatches AND demoted plain dispatches
    assert eng.spec_dispatches >= 1 and eng.spec_fallbacks >= 1


# ---------------- accounting + sampled mode ----------------

def test_spec_decode_charges_follow_committed_tokens(cfg):
    """The per-(task, rid) charge log prices the work each stream's chunks
    actually committed: the totals drain once, are keyed by rid, and cover
    at least every token the engine kept."""
    fm = _fm(cfg)
    eng = _engine(fm, spec_k=2)
    rng = np.random.RandomState(4)
    eng.join("A", rng.randint(0, cfg.vocab_size, 9), max_new_tokens=8, rid=1)
    eng.join("B", rng.randint(0, cfg.vocab_size, 12), max_new_tokens=14,
             rid=2)
    done = _streams(eng)
    charges = eng.take_decode_charges()
    assert eng.take_decode_charges() == {}            # drained
    assert set(charges) == {("A", 1), ("B", 2)}
    # decode commits everything after the prefill's first token; charges
    # may exceed kept tokens (committed-then-truncated tail work) but
    # never undercount them
    assert charges[("A", 1)] >= len(done[1]) - 1
    assert charges[("B", 2)] >= len(done[2]) - 1


def test_spec_sampled_mode_smoke(cfg):
    """Sampled speculation is documented APPROXIMATE (the PRNG stream
    advances per verify position, not per committed token) — but it must
    complete, stay inside the vocabulary, and never leak the out-of-vocab
    draft FILL sentinel into a stream."""
    fm = _copy_inclined(_fm(cfg))
    eng = _engine(fm, spec_k=3, temperature=0.8, top_k=8,
                  spec_disable_below=1.0)
    rng = np.random.RandomState(5)
    for i in range(3):
        eng.join(f"t{i}", rng.randint(0, cfg.vocab_size, 10),
                 max_new_tokens=12, rid=i)
    out = _streams(eng)
    assert len(out) == 3
    for toks in out.values():
        assert len(toks) == 12
        assert all(0 <= t < cfg.vocab_size for t in toks)
