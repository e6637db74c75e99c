"""Speculative decoding (ops/decode.py::speculative_decode): the spec
path's whole correctness claim is BIT-PARITY with ``greedy_decode`` —
acceptance is exact argmax match, so draft quality may change speed but
never output. These tests pin that claim on CPU for both draft sources
(self-drafting n-gram lookup and a second zoo LM) across bucket shapes,
pin the ``decode_chunk`` multi-token forward against a sequence of
single ``decode_step`` calls for both LM families, and pin the
``greedy_decode`` edge semantics (eos at the first generated position,
no eos within budget, a prompt exactly filling its bucket) that the
spec path has to match.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import (
    GPT2Config,
    MistralConfig,
    SpecDecodeConfig,
)
from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.models.gpt2 import GPT2LM
from cassmantle_tpu.models.mistral import MistralLM
from cassmantle_tpu.ops import decode
from cassmantle_tpu.ops.decode import (
    ModelDraft,
    NgramDraft,
    make_apply_fns,
    speculative_decode,
)
from cassmantle_tpu.serving.pipeline import PromptGenerator


def greedy_decode(pair, params, ids, lens, *rest):
    """``ops.decode.greedy_decode``, every row in its own bucket's
    program (the speculative path serves no other)."""
    return decode.greedy_decode(
        pair, params, ids, lens, *rest,
        position_offset=jnp.zeros(ids.shape[:1], jnp.int32))


@pytest.fixture(scope="module")
def base_cfg():
    return _tiny_config()


@pytest.fixture(scope="module")
def gpt2_lm(base_cfg):
    """(cfg, params, apply_fns) for ops-level decode tests."""
    cfg = base_cfg.models.gpt2
    model = GPT2LM(cfg)
    ids = jnp.zeros((1, 8), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return cfg, params, make_apply_fns(model)


def _prompt(b, p, vocab, seed=3):
    """Right-padded (B, P) prompt bucket with per-row lengths."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(b, p)).astype(np.int32)
    lens = np.linspace(max(2, p // 2), p, num=b).astype(np.int32)
    for i, n in enumerate(lens):
        ids[i, n:] = 0
    return jnp.asarray(ids), jnp.asarray(lens)


# -- decode_chunk vs decode_step: one forward == S single steps -------------


def test_decode_chunk_matches_step_sequence_gpt2(gpt2_lm):
    """decode_chunk scores S positions in one forward with logits equal
    to feeding the same tokens one decode_step at a time — the verify
    forward's contract (models/layers.py chunk_causal_mask)."""
    cfg, params, (prefill, step, chunk) = gpt2_lm
    ids, lens = _prompt(2, 8, cfg.vocab_size)
    max_len = 24
    last, cache0 = prefill(params, ids, lens, max_len)
    toks = jnp.asarray(
        np.random.RandomState(5).randint(0, cfg.vocab_size, (2, 5)),
        dtype=jnp.int32)
    positions = jnp.arange(max_len)[None, :]
    prompt_valid = positions < lens[:, None]

    stepped = []
    cache = cache0
    for j in range(5):
        idx = jnp.int32(8 + j)
        valid = prompt_valid | ((positions >= 8) & (positions <= idx))
        logits, cache = step(params, toks[:, j], idx, cache, valid,
                             jnp.full((2, 1), idx))
        stepped.append(logits)
    stepped = jnp.stack(stepped, axis=1)               # (B, 5, V)

    valid = prompt_valid | ((positions >= 8) & (positions <= 12))
    chunked, cache_c = chunk(params, toks, jnp.int32(8), cache0, valid)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(stepped),
                               rtol=2e-5, atol=2e-5)
    # the chunk-append lands the same kv slab the stepped path wrote
    for (ck, cv), (sk, sv) in zip(cache_c, cache):
        np.testing.assert_allclose(np.asarray(ck), np.asarray(sk),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cv), np.asarray(sv),
                                   rtol=2e-5, atol=2e-5)


def test_decode_chunk_matches_step_sequence_mistral():
    """Same contract for the Mistral family: RoPE follows true positions
    and the sliding window is enforced PER QUERY inside the chunk (the
    prompt here is longer than the window, so early cache positions must
    drop out of later queries' bands)."""
    cfg = MistralConfig.tiny()             # sliding_window=16
    model = MistralLM(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), dtype=jnp.int32))
    prefill, step, chunk = make_apply_fns(model)
    p, s, max_len = 24, 6, 40              # 24 > window: band active
    ids, lens = _prompt(2, p, cfg.vocab_size, seed=7)
    last, cache0 = prefill(params, ids, lens, max_len)
    toks = jnp.asarray(
        np.random.RandomState(9).randint(0, cfg.vocab_size, (2, s)),
        dtype=jnp.int32)
    positions = jnp.arange(max_len)[None, :]
    prompt_valid = positions < lens[:, None]

    stepped = []
    cache = cache0
    for j in range(s):
        idx = jnp.int32(p + j)
        valid = prompt_valid | ((positions >= p) & (positions <= idx))
        logits, cache = step(params, toks[:, j], idx, cache, valid,
                             jnp.full((2, 1), idx))
        stepped.append(logits)
    stepped = jnp.stack(stepped, axis=1)

    valid = prompt_valid | ((positions >= p) & (positions <= p + s - 1))
    chunked, _ = chunk(params, toks, jnp.int32(p), cache0, valid)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(stepped),
                               rtol=2e-5, atol=2e-5)


# -- greedy_decode edge semantics (the spec the spec path must match) -------


def test_greedy_eos_at_first_generated_position(gpt2_lm):
    """If the very first generated token is EOS: gen_len == 0 and every
    output position reads EOS (the eos-freeze fill)."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(1, 8, cfg.vocab_size)
    # run once with an unreachable eos to learn the first greedy token,
    # then make THAT token the eos — deterministic eos-at-position-0
    toks, _ = greedy_decode(fns[:2], params, ids, lens,
                            jax.random.PRNGKey(0), 6, cfg.vocab_size)
    first = int(toks[0, 0])
    toks, gen_len = greedy_decode(fns[:2], params, ids, lens,
                                  jax.random.PRNGKey(0), 6, first)
    assert int(gen_len[0]) == 0
    assert np.all(np.asarray(toks) == first)


def test_greedy_no_eos_within_budget(gpt2_lm):
    """An eos that never fires (the serving layer's out-of-vocab
    sentinel) must yield gen_len == max_new for every row."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(3, 8, cfg.vocab_size)
    toks, gen_len = greedy_decode(fns[:2], params, ids, lens,
                                  jax.random.PRNGKey(0), 6, cfg.vocab_size)
    assert toks.shape == (3, 6)
    assert np.all(np.asarray(gen_len) == 6)


def test_greedy_tokens_after_eos_are_eos(gpt2_lm):
    """Tokens past the first EOS are overwritten with EOS and gen_len
    stops there — the mid-sequence eos-freeze convention."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(1, 8, cfg.vocab_size)
    toks, _ = greedy_decode(fns[:2], params, ids, lens,
                            jax.random.PRNGKey(0), 6, cfg.vocab_size)
    row = np.asarray(toks)[0]
    mid = int(row[3])                      # make a mid-chain token the eos
    j = int(np.argmax(row == mid))         # its FIRST occurrence
    toks2, gen_len2 = greedy_decode(fns[:2], params, ids, lens,
                                    jax.random.PRNGKey(0), 6, mid)
    row2 = np.asarray(toks2)[0]
    np.testing.assert_array_equal(row2[:j], row[:j])
    assert int(gen_len2[0]) == j
    assert np.all(row2[j:] == mid)


# -- speculative_decode: bit-parity with greedy_decode ----------------------


def _spec_parity_case(gpt2_lm, draft, draft_params, b, p, max_new, eos,
                      gamma=3):
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(b, p, cfg.vocab_size)
    ref_t, ref_l = greedy_decode(fns[:2], params, ids, lens,
                                 jax.random.PRNGKey(0), max_new, eos)
    got_t, got_l, stats = speculative_decode(
        fns, params, ids, lens, max_new, eos, gamma, draft, draft_params)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
    return np.asarray(ref_t), np.asarray(stats)


@pytest.mark.parametrize("shape", [(1, 16), (3, 32)])
def test_spec_parity_ngram_ops(gpt2_lm, shape):
    """n-gram draft, two (B, P) bucket shapes, eos unreachable: tokens
    and gen_len bit-identical, and chunks + accepted == max_new (every
    chunk commits 1 + accepted tokens; the loop stops exactly at the
    budget when nothing terminates early)."""
    cfg = gpt2_lm[0]
    b, p = shape
    _, stats = _spec_parity_case(gpt2_lm, NgramDraft(ngram=2), None,
                                 b, p, 8, cfg.vocab_size)
    chunks, drafted, accepted = (int(x) for x in stats)
    assert chunks >= 1 and drafted == 3 * chunks
    assert 0 <= accepted <= drafted
    assert chunks + accepted == 8


@pytest.mark.parametrize("shape", [(1, 16), (3, 32)])
def test_spec_parity_model_draft_ops(gpt2_lm, shape):
    """Self-draft ModelDraft (the degenerate where draft == target),
    same parity bar across both bucket shapes."""
    cfg, params, fns = gpt2_lm
    b, p = shape
    draft = ModelDraft(fns[0], fns[1])
    _spec_parity_case(gpt2_lm, draft, params, b, p, 8, cfg.vocab_size)


def test_spec_self_draft_full_acceptance(gpt2_lm):
    """A draft identical to the target must have every proposal
    accepted (the self-draft degenerate is an exact oracle), so 8
    tokens commit in ceil(8/(gamma+1)) verify forwards. Regression for
    the draft-cache sync step: without it, stale kv at each chunk's
    correction position (the rejected token's kv on partial accept, a
    zero-filled slot on full accept) compounded and silently eroded
    the accept rate to ~0.2 on this exact setup."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(1, 16, cfg.vocab_size)
    draft = ModelDraft(fns[0], fns[1])
    _, _, stats = speculative_decode(fns, params, ids, lens, 8,
                                     cfg.vocab_size, 3, draft, params)
    chunks, drafted, accepted = (int(x) for x in np.asarray(stats))
    assert accepted == drafted
    assert chunks == 2


def test_spec_parity_with_midstream_eos(gpt2_lm):
    """An eos that fires mid-generation (and at different steps per
    row) exercises the done-row lockstep masking: finished rows must
    not throttle live rows, and output stays bit-identical."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(3, 16, cfg.vocab_size)
    ref_t, _ = greedy_decode(fns[:2], params, ids, lens,
                             jax.random.PRNGKey(0), 8, cfg.vocab_size)
    eos = int(np.asarray(ref_t)[0, 4])     # row 0 terminates at step 4
    _spec_parity_case(gpt2_lm, NgramDraft(ngram=2), None, 3, 16, 8, eos)


def test_spec_parity_eos_at_first_position(gpt2_lm):
    """The eos-at-position-0 edge through the SPEC path: gen_len 0,
    all-eos fill, bit-identical to greedy."""
    cfg, params, fns = gpt2_lm
    ids, lens = _prompt(1, 16, cfg.vocab_size)
    ref_t, _ = greedy_decode(fns[:2], params, ids, lens,
                             jax.random.PRNGKey(0), 8, cfg.vocab_size)
    eos = int(np.asarray(ref_t)[0, 0])
    toks, stats = _spec_parity_case(gpt2_lm, NgramDraft(ngram=2), None,
                                    1, 16, 8, eos)
    assert np.all(toks == eos)


def test_spec_parity_budget_smaller_than_gamma(gpt2_lm):
    """max_new < gamma: the never-overshoot clip caps the last chunk's
    commit at the budget; output still bit-identical."""
    cfg = gpt2_lm[0]
    _, stats = _spec_parity_case(gpt2_lm, NgramDraft(ngram=2), None,
                                 1, 16, 2, cfg.vocab_size, gamma=4)
    assert int(stats[0]) <= 2              # at most one chunk per token


# -- the serving path (PromptGenerator) -------------------------------------


@pytest.fixture(scope="module")
def plain_gen(base_cfg):
    return PromptGenerator(base_cfg)


@pytest.fixture(scope="module")
def ngram_gen(base_cfg):
    return PromptGenerator(base_cfg.replace(
        spec_decode=SpecDecodeConfig(mode="ngram", gamma=3, ngram=2)))


def test_promptgen_spec_parity_and_bucket_boundary(plain_gen, ngram_gen):
    """decode_ids_batch parity through the serving layer, including a
    prompt of EXACTLY 32 byte-tokens (the _bucket_for boundary: it must
    fill bucket 32, not spill into the next), co-batched with a short
    prompt (bucket padding dummies in play)."""
    boundary = "x" * 32                    # byte tokenizer: 1 char = 1 token
    assert len(plain_gen.tokenizer.encode(boundary)) == 32
    assert plain_gen._bucket_for(32, 8, 55) == 32
    texts = [boundary, "the storm rolled"]
    ref_t, ref_l = plain_gen.decode_ids_batch(texts, max_new_tokens=8,
                                              seed=0)
    got_t, got_l = ngram_gen.decode_ids_batch(texts, max_new_tokens=8,
                                              seed=0)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
    assert ngram_gen.last_spec_stats["chunks"] >= 1
    # rows also equal their own single decodes (the own-bucket contract)
    for i, t in enumerate(texts):
        one_t, one_l = plain_gen.decode_ids(t, max_new_tokens=8, seed=0)
        np.testing.assert_array_equal(np.asarray(ref_t)[i],
                                      np.asarray(one_t)[0])


def test_promptgen_spec_parity_two_buckets_both_drafts(base_cfg):
    """Acceptance bar: bit-parity for BOTH draft sources across two
    prompt-bucket shapes (32 and 64 — position table widened so the
    64 bucket keeps room for the chunk scratch tail), with the
    draft-model source using a genuinely smaller second LM (its own
    params and cache, not the self-draft degenerate)."""
    big = base_cfg.replace(models=dc.replace(
        base_cfg.models,
        gpt2=dc.replace(base_cfg.models.gpt2, max_positions=128)))
    small_draft = GPT2Config(vocab_size=256, hidden_size=32, num_layers=1,
                             num_heads=2, max_positions=128,
                             dtype="float32")
    texts = ["storm", "y" * 40]            # buckets 32 and 64
    plain = PromptGenerator(big)
    ref_t, ref_l = plain.decode_ids_batch(texts, max_new_tokens=8, seed=0)
    for spec_cfg in (
        SpecDecodeConfig(mode="ngram", gamma=4, ngram=2),
        SpecDecodeConfig(mode="draft_model", gamma=4,
                         draft_model=small_draft),
    ):
        gen = PromptGenerator(big.replace(spec_decode=spec_cfg))
        got_t, got_l = gen.decode_ids_batch(texts, max_new_tokens=8,
                                            seed=0)
        np.testing.assert_array_equal(np.asarray(got_t),
                                      np.asarray(ref_t))
        np.testing.assert_array_equal(np.asarray(got_l),
                                      np.asarray(ref_l))
        assert gen.last_spec_stats["chunks"] >= 2  # both buckets drafted


def test_promptgen_spec_parity_mistral(base_cfg):
    """The Mistral family through the serving spec path (ngram draft):
    sliding-window chunk masking must hold bit-parity end to end."""
    mcfg = base_cfg.replace(models=dc.replace(
        base_cfg.models, mistral=MistralConfig.tiny()))
    texts = ["the storm rolled over the", "b c d b c d b c d"]
    plain = PromptGenerator(mcfg)
    spec = PromptGenerator(mcfg.replace(
        spec_decode=SpecDecodeConfig(mode="ngram", gamma=3, ngram=2)))
    ref_t, ref_l = plain.decode_ids_batch(texts, max_new_tokens=8, seed=0)
    got_t, got_l = spec.decode_ids_batch(texts, max_new_tokens=8, seed=0)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
    assert spec.last_spec_stats["chunks"] >= 1


def test_promptgen_generate_batch_ab_smoke(plain_gen, ngram_gen):
    """The tier-1 A/B smoke (ISSUE 5 satellite): draft + verify run end
    to end through generate_batch, output text matches the plain
    generator exactly, the accept rate is NONZERO (a repetitive prompt
    the n-gram lookup can actually predict), and the chunk count shows
    real amortization (fewer verify forwards than tokens)."""
    texts = ["b c d b c d b c d b c d", "the storm rolled"]
    ref = plain_gen.generate_batch(texts, max_new_tokens=8)
    got = ngram_gen.generate_batch(texts, max_new_tokens=8)
    assert got == ref
    stats = ngram_gen.last_spec_stats
    assert stats["accepted"] > 0
    assert stats["accept_rate"] > 0.0
    # 2 bucket groups x 8 tokens = 16 stepped forwards on the plain
    # path; accepted drafts must have saved at least one verify forward
    assert stats["chunks"] < 16
    from cassmantle_tpu.utils.logging import metrics

    snap = metrics.snapshot()
    assert snap["counters"]["decode.spec_chunks"] >= stats["chunks"]
    assert "decode.spec_accept_rate" in snap["gauges"]
    assert snap["timings"]["decode.verify_s"]["count"] >= 1


def test_promptgen_spec_reuses_compiled_buckets(ngram_gen):
    """Batches of 3 and 4 share the (4, P) spec graph — the serving
    buckets compile once (the greedy path's guarantee, kept)."""
    ngram_gen.decode_ids_batch(["a", "b", "c"], max_new_tokens=4)
    misses = speculative_decode._cache_size()
    ngram_gen.decode_ids_batch(["d", "e", "f", "g"], max_new_tokens=4)
    assert speculative_decode._cache_size() == misses


def test_promptgen_steady_state_zero_recompiles(plain_gen, ngram_gen):
    """The jit compile-count sentinel (utils/jit_sentinel.py), pinned
    on the real prompt-decode serving path: after one warmup dispatch
    per (prompt bucket, batch bucket) pair, further decode traffic in
    the SAME buckets — different texts, different seeds, both the
    greedy and the speculative path — compiles NOTHING. A bucket key
    quietly becoming per-call (the recompile-hazard class) fails here
    instead of shipping as a silent latency cliff."""
    from cassmantle_tpu.utils import jit_sentinel

    # warmup: one dispatch per (prompt 32, batch 4) and (32, 1) bucket
    plain_gen.decode_ids_batch(["a storm", "a tide", "a dune"],
                               max_new_tokens=4)
    plain_gen.decode_ids_batch(["a solo warm dispatch"],
                               max_new_tokens=4)
    ngram_gen.decode_ids_batch(["a storm", "a tide", "a dune"],
                               max_new_tokens=4)
    with jit_sentinel.no_new_compiles():
        plain_gen.decode_ids_batch(["new words", "другой", "third?"],
                                   max_new_tokens=4)
        plain_gen.decode_ids_batch(["and a fourth dispatch"],
                                   max_new_tokens=4)
        ngram_gen.decode_ids_batch(["fresh texts here", "again",
                                    "and again"], max_new_tokens=4)


def test_promptgen_seeded_recompile_fails_steady_state(ngram_gen):
    """The sentinel actually ARMS the steady-state contract: traffic
    that enters a cold batch bucket inside the assertion window (a
    seeded recompile regression) raises JitRecompileError naming the
    compiled function."""
    from cassmantle_tpu.utils import jit_sentinel

    ngram_gen.decode_ids_batch(["warm", "the", "bucket"],
                               max_new_tokens=4)
    with pytest.raises(jit_sentinel.JitRecompileError):
        with jit_sentinel.no_new_compiles():
            # 5 rows -> batch bucket 8: a bucket this module never
            # warmed, so the spec graph must compile mid-window
            ngram_gen.decode_ids_batch(
                ["a", "b", "c", "d", "e"], max_new_tokens=4)


def test_promptgen_spec_falls_back_when_bucket_lacks_scratch_room(
        ngram_gen, plain_gen):
    """A prompt whose bucket + budget + scratch tail exceeds the
    position table must silently take the plain greedy path (same
    output, no spec stats) instead of overrunning the wpe table."""
    long_text = "z" * 40                   # bucket 55 (the limit); 55+8+4>64
    assert not ngram_gen._spec_enabled(55, 8)
    before = ngram_gen.last_spec_stats
    got_t, got_l = ngram_gen.decode_ids_batch([long_text],
                                              max_new_tokens=8, seed=0)
    assert ngram_gen.last_spec_stats is before  # untouched: greedy path
    ref_t, ref_l = plain_gen.decode_ids_batch([long_text],
                                              max_new_tokens=8, seed=0)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(ref_t))


def test_promptgen_kill_switch(base_cfg, plain_gen, monkeypatch):
    """CASSMANTLE_NO_SPEC_DECODE=1 (docs/DEPLOY.md §6) forces the plain
    greedy path even with spec_decode configured on."""
    monkeypatch.setenv("CASSMANTLE_NO_SPEC_DECODE", "1")
    gen = PromptGenerator(base_cfg.replace(
        spec_decode=SpecDecodeConfig(mode="ngram", gamma=3, ngram=2)))
    assert not gen._spec_enabled(32, 8)
    t, l = gen.decode_ids_batch(["the storm rolled"], max_new_tokens=8,
                                seed=0)
    assert gen.last_spec_stats is None
    ref_t, _ = plain_gen.decode_ids_batch(["the storm rolled"],
                                          max_new_tokens=8, seed=0)
    np.testing.assert_array_equal(np.asarray(t), np.asarray(ref_t))


def test_promptgen_temperature_disables_spec(base_cfg):
    """Sampled decodes (temperature > 0) never take the spec path —
    exact-argmax acceptance is only sound for greedy."""
    cfg = base_cfg.replace(
        sampler=dc.replace(base_cfg.sampler, text_temperature=0.8),
        spec_decode=SpecDecodeConfig(mode="ngram", gamma=3))
    gen = PromptGenerator(cfg)
    assert not gen._spec_enabled(32, 8)
