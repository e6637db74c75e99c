"""Greedy text decode as prefill + ``lax.scan`` over KV-cache steps.

Replaces the reference's remote LLM call (backend.py:240-268). The whole
generation — prefill over the padded prompt bucket plus ``max_new_tokens``
cached decode steps — compiles to one XLA computation with static shapes.
Early stop is data-dependent, so instead of breaking the loop (illegal under
jit) tokens after EOS are overwritten with EOS and reported lengths stop at
the first EOS, matching the reference's "decode 32-96 tokens then trim"
behavior (backend.py:250-255, 265).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from cassmantle_tpu.utils.profiling import named_jit


def make_apply_fns(model):
    """(prefill_fn, decode_step_fn, decode_chunk_fn) for any zoo LM
    exposing the prefill/decode_step/decode_chunk contract — the one
    definition of the calling convention the decode loops expect
    (params threaded first so weights stay traced jit arguments)."""
    cls = type(model)

    def prefill(params, ids, prompt_len, max_len, *row_mask):
        return model.apply(params, ids, prompt_len, max_len, *row_mask,
                           method=cls.prefill)

    def decode_step(params, token, index, cache, valid, positions):
        return model.apply(params, token, index, cache, valid, positions,
                           method=cls.decode_step)

    def decode_chunk(params, tokens, index, cache, valid):
        return model.apply(params, tokens, index, cache, valid,
                           method=cls.decode_chunk)

    return prefill, decode_step, decode_chunk


def make_apply_pair(model):
    """(prefill_fn, decode_step_fn) — the ``greedy_decode`` subset of
    :func:`make_apply_fns`, kept for callers that never draft."""
    return make_apply_fns(model)[:2]


@partial(named_jit, name="lm_decode", static_argnums=(0, 5, 6, 7, 8),
         static_argnames=("cache_stats",))
def greedy_decode(
    model_apply_pair,          # (prefill_fn, decode_step_fn), static; both
                               # take ``params`` first so weights enter the
                               # jit as device buffers, NOT as captured
                               # constants baked into the HLO
    params,                    # model param tree (traced argument)
    input_ids: jax.Array,      # (B, P) right-padded prompt bucket
    prompt_len: jax.Array,     # (B,)
    rng: jax.Array,            # consumed only when temperature > 0
    max_new_tokens: int,
    eos_token: int,
    temperature: float = 0.0,
    top_k: int = 40,
    row_mask=None,             # (B,) True = a request, False = batch padding
    cache_stats=None,          # static: final cache -> tree of counters
    *,
    position_offset: jax.Array,  # (B,) int32, <= 0: own bucket minus P
):
    """Returns (generated (B, max_new_tokens), gen_len (B,)).

    Every row's generated token ``i`` is written to cache slot ``P + i``
    and sits at position ``P + i + position_offset[row]``: a row whose
    own prompt bucket is narrower than this program's ``P`` names the
    difference, and decodes at the positions of a decode of its own
    (serving/pipeline.py ``decode_ids_batch`` runs a batch in its widest
    row's program). It is an operand of every program, zeros where each
    row is in its own bucket's, so a mixed batch runs the program a
    same-bucket one compiled.

    The cache is whatever tree the model's ``prefill`` returns; the scan
    only carries it. A model whose cache counts what it did (a sparse
    LM's routing, models/qwen3_next.py) names ``cache_stats``: its
    ``prefill`` is then told which rows are requests (``row_mask``) and
    the counters of the final cache come back as a third result, with
    the tokens and so without a host sync of their own.

    ``temperature=0`` (default) is exact greedy argmax — the reference's
    hosted text-generation call decodes greedily (no sampling params,
    backend.py:250-255). ``temperature>0`` switches to top-k Gumbel
    sampling per step (the standard serving sampler), statically — the
    greedy graph carries no sampling ops."""
    prefill_fn, decode_step_fn = model_apply_pair
    b, p = input_ids.shape
    max_len = p + max_new_tokens

    with jax.named_scope("lm_prefill"):
        last_logits, cache = prefill_fn(
            params, input_ids, prompt_len, max_len,
            *(() if cache_stats is None else (row_mask,)))

    positions = jnp.arange(max_len)[None, :]          # (1, L)
    prompt_valid = positions < prompt_len[:, None]     # (B, L)

    def pick(logits, i):
        if temperature <= 0.0:  # static branch: pure greedy
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = max(1, min(top_k, logits.shape[-1]))
        k_logits, k_idx = jax.lax.top_k(logits, k)
        choice = jax.random.categorical(
            jax.random.fold_in(rng, i),
            k_logits.astype(jnp.float32) / temperature, axis=-1)
        return jnp.take_along_axis(
            k_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    @jax.named_scope("lm_decode_step")
    def step(carry, i):
        logits, cache, done = carry
        token = pick(logits, i)
        token = jnp.where(done, jnp.int32(eos_token), token)
        emitted = token
        done = done | (token == eos_token)
        # All rows write cache slot P+i; each sits at its own position
        # (a prompt shorter than its bucket leaves masked slots between,
        # never attended).
        idx = jnp.int32(p + i)
        valid = prompt_valid | (
            (positions >= p) & (positions <= idx)
        )
        logits, cache = decode_step_fn(
            params, token, idx, cache, valid,
            (idx + position_offset)[:, None])
        return (logits, cache, done), emitted

    init_done = jnp.zeros((b,), dtype=bool)
    (_, cache, _), tokens = jax.lax.scan(
        step, (last_logits, cache, init_done), jnp.arange(max_new_tokens)
    )
    tokens = tokens.T  # (B, max_new_tokens)
    is_eos = tokens == eos_token
    gen_len = jnp.where(
        is_eos.any(axis=1),
        jnp.argmax(is_eos, axis=1),
        jnp.int32(max_new_tokens),
    )
    if cache_stats is None:
        return tokens, gen_len
    return tokens, gen_len, cache_stats(cache)


# -- speculative decoding ---------------------------------------------------
#
# The greedy loop above is memory-bound: every emitted token reads the full
# weight set once (docs/PERF_NOTES.md "LM decode accounting"). Speculative
# decoding amortizes that read: a cheap DRAFT proposes ``gamma`` tokens and
# the target scores all gamma+1 positions in ONE ``decode_chunk`` forward.
# Because serving decodes greedily (temperature=0 — the reference's decode
# mode), acceptance is exact argmax match: every committed token is, by
# construction, the token the target's own argmax chain would have emitted,
# so the output is bit-identical to ``greedy_decode`` — CPU-testable, no
# distribution-level rejection sampling needed.


class NgramDraft(NamedTuple):
    """Self-drafting prompt-lookup draft: the longest recent ``ngram``
    suffix of the already-decoded context is matched against earlier
    context and the continuation after the match is proposed. Zero extra
    HBM (no second model), effective whenever generations echo the
    prompt or loop on phrases. Static/hashable: lives in the jit key."""

    ngram: int = 3


class ModelDraft(NamedTuple):
    """A smaller zoo LM drafting for the target (gpt2-small for
    gpt2-large/Mistral). ``prefill_fn``/``step_fn`` follow the
    make_apply_fns convention; the draft's params ride as the traced
    ``draft_params`` argument. The draft MUST share the target's
    tokenizer/vocab — token ids are compared directly."""

    prefill_fn: Callable
    step_fn: Callable


def _ngram_propose(ctx, prompt_len, prompt_width, n_gen, gamma, k):
    """Propose (B, gamma) continuation tokens by suffix lookup.

    ``ctx`` (B, L) is the bucket-layout context buffer: the right-padded
    prompt occupies columns < ``prompt_width`` (real tokens only below
    each row's ``prompt_len``) and ``n_gen`` committed/known generated
    tokens sit at ``prompt_width..prompt_width+n_gen-1``. The last ``k``
    known tokens are matched against every earlier window (pad gaps are
    blanked to -1 so they can never fake a match); the rightmost match
    wins (most recent context) and the ``gamma`` tokens after it are the
    proposal. No match → propose the last token repeated (the degenerate
    loop draft). Pure function of traced values — fixed shapes, no
    syncs; correctness never depends on proposal quality (verify
    corrects everything)."""
    b, length = ctx.shape
    pos = jnp.arange(length)[None, :]
    end = jnp.int32(prompt_width) + n_gen          # one past the known region
    real = (pos < prompt_len[:, None]) | (
        (pos >= prompt_width) & (pos < end))
    mctx = jnp.where(real, ctx, jnp.int32(-1))
    suffix = jax.lax.dynamic_slice(
        mctx, (jnp.int32(0), end - k), (b, k))     # (B, k) last known tokens
    # all length-k windows, via k static shifts: windows[j] = mctx[:, j:j+k]
    shifted = jnp.stack(
        [mctx[:, t:length - k + t] for t in range(k)], axis=-1
    )                                              # (B, L-k, k)
    match = jnp.all(shifted == suffix[:, None, :], axis=-1)
    window_j = jnp.arange(length - k)[None, :]
    # the window must end strictly before the suffix so a continuation
    # exists (and the suffix can't trivially match itself)
    match = match & (window_j < end - k)
    j_star = jnp.max(jnp.where(match, window_j, -1), axis=-1)   # (B,)
    found = j_star >= 0

    def take(row, start):
        return jax.lax.dynamic_slice(row, (start,), (gamma,))

    start = jnp.clip(j_star + k, 0, length - gamma)
    proposal = jax.vmap(take)(ctx, start)
    last = jax.lax.dynamic_slice(mctx, (jnp.int32(0), end - 1), (b, 1))
    return jnp.where(found[:, None], proposal,
                     jnp.broadcast_to(last, (b, gamma))).astype(jnp.int32)


@partial(jax.jit, static_argnums=(0, 4, 5, 6, 7))
def speculative_decode(
    model_apply_fns,           # (prefill_fn, decode_step_fn, decode_chunk_fn)
    params,                    # target param tree (traced)
    input_ids: jax.Array,      # (B, P) right-padded prompt bucket
    prompt_len: jax.Array,     # (B,)
    max_new_tokens: int,
    eos_token: int,
    gamma: int,                # drafted tokens per chunk
    draft,                     # NgramDraft | ModelDraft (static)
    draft_params=None,         # draft LM params (ModelDraft only; traced)
    row_mask=None,             # (B,) True = real row; None = all real
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Draft/verify greedy decode, bit-identical to ``greedy_decode``.

    Returns (generated (B, max_new_tokens), gen_len (B,), stats (3,)
    int32 = [chunks, drafted, accepted]).

    Loop shape: a ``lax.while_loop`` over fixed-size chunks — every
    chunk's verify forward scores ``gamma+1`` positions (the known-next
    token plus the gamma drafts) in one ``decode_chunk``, commits the
    accepted prefix plus the correction, and stops as soon as every
    live row is finished. Best case the loop runs ⌈max_new/γ⌉ chunks
    (full acceptance, the γ+1-fold weight-read amortization); worst
    case it degrades to one committed token per chunk, never fewer —
    all shapes static either way, so the serving buckets compile once.

    Batch rows advance in LOCKSTEP: the committed count per chunk is the
    minimum across live rows (keeping the kv-cache append index scalar —
    the decode_step/decode_chunk cache convention). Finished rows and
    ``row_mask=False`` rows (the serving layer's batch-bucket padding
    dummies) are excluded from that min so they never throttle real
    rows; masked rows' outputs are deterministic but NOT parity-checked
    (the serving layer drops them).

    Rollback needs no copies: a rejected suffix simply stays out of the
    next chunk's validity mask and is overwritten by the next
    chunk-append (the valid-mask convention, models/layers.py).
    """
    prefill_fn, _, chunk_fn = model_apply_fns
    b, p = input_ids.shape
    g1 = gamma + 1
    # scratch tail: the last chunk's full-width append may land past the
    # budget; committed output is sliced back to max_new_tokens
    max_len = p + max_new_tokens + g1
    eos = jnp.int32(eos_token)

    last_logits, cache = prefill_fn(params, input_ids, prompt_len, max_len)

    positions = jnp.arange(max_len)[None, :]          # (1, L)
    prompt_valid = positions < prompt_len[:, None]     # (B, L)

    is_model_draft = isinstance(draft, ModelDraft)
    if is_model_draft:
        _, d_cache = draft.prefill_fn(draft_params, input_ids, prompt_len,
                                      max_len)
    else:
        d_cache = ()
    # context buffer for the n-gram draft: bucket layout + scratch tail
    # (a model draft keeps its context in its own kv cache — no buffer)
    ctx = (jnp.zeros((b, 0), jnp.int32) if is_model_draft
           else jnp.pad(input_ids.astype(jnp.int32),
                        ((0, 0), (0, max_new_tokens + g1))))
    out = jnp.zeros((b, max_new_tokens + g1), dtype=jnp.int32)
    done = jnp.zeros((b,), dtype=bool)
    stats = jnp.zeros((3,), dtype=jnp.int32)          # chunks/drafted/accepted
    # last committed token, for the model draft's cache-sync step; the
    # initial value re-writes the last prompt column's kv verbatim
    # (k/v at a position depend only on that position's token)
    prev_tok = input_ids[:, p - 1].astype(jnp.int32)

    def live_done(done):
        return done if row_mask is None else (done | ~row_mask)

    def cond(carry):
        g, out, last_logits, cache, d_cache, ctx, prev, done, stats = carry
        return (g < max_new_tokens) & ~jnp.all(live_done(done))

    def chunk(carry):
        g, out, last_logits, cache, d_cache, ctx, prev, done, stats = carry
        idx = jnp.int32(p) + g                         # cache index of y_first
        y_first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        y_first = jnp.where(done, eos, y_first)

        # -- draft: gamma proposals continuing after y_first -----------
        if is_model_draft:
            with jax.named_scope("spec_draft"):
                # cache-sync step: the previous chunk committed through
                # position idx-1, but the draft's own scan last wrote
                # kv for ITS tokens — on a rejection the slot at the
                # correction position holds the rejected token's kv, and
                # on full acceptance it was never written at all. One
                # step re-feeding the last committed token repairs the
                # slot (k/v depend only on that position's token), so
                # stale kv never accumulates to erode the accept rate.
                sync_valid = prompt_valid | (
                    (positions >= p) & (positions <= idx - 1))
                _, d_cache = draft.step_fn(
                    draft_params, prev, idx - 1, d_cache, sync_valid,
                    jnp.full((b, 1), idx - 1))

                def d_step(state, _):
                    dc, cur, tok = state
                    valid = prompt_valid | (
                        (positions >= p) & (positions <= cur))
                    logits, dc = draft.step_fn(draft_params, tok, cur, dc,
                                               valid, jnp.full((b, 1), cur))
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (dc, cur + 1, nxt), nxt
                (d_cache, _, _), drafts = jax.lax.scan(
                    d_step, (d_cache, idx, y_first), None, length=gamma)
                drafts = drafts.T                      # (B, gamma)
            new_ctx = ctx
        else:
            ctx_y = jax.lax.dynamic_update_slice(
                ctx, y_first[:, None], (jnp.int32(0), idx))
            drafts = _ngram_propose(ctx_y, prompt_len, p, g + 1, gamma,
                                    draft.ngram)
            new_ctx = ctx_y

        # -- verify: ONE target forward over [y_first, drafts] ---------
        chunk_toks = jnp.concatenate([y_first[:, None], drafts], axis=1)
        valid = prompt_valid | (
            (positions >= p) & (positions <= idx + gamma))
        with jax.named_scope("spec_verify"):
            logits, new_cache = chunk_fn(params, chunk_toks, idx, cache,
                                         valid)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, g1)

        # true greedy continuation under the eos-freeze convention
        # (tokens after EOS are EOS — greedy_decode's step semantics),
        # and the leading-match accept count, unrolled over static gamma
        emit = [y_first]
        cur_done = done | (y_first == eos)
        accept = jnp.ones((b,), dtype=bool)
        acc_count = jnp.zeros((b,), jnp.int32)
        for j in range(gamma):
            tok = jnp.where(cur_done, eos, preds[:, j])
            emit.append(tok)
            accept = accept & (drafts[:, j] == tok)
            acc_count = acc_count + accept.astype(jnp.int32)
            cur_done = cur_done | (tok == eos)
        emit = jnp.stack(emit, axis=1)                 # (B, g1)

        # lockstep commit: min over LIVE rows; finished/dummy rows are
        # masked to full width so they never drag the batch
        c_rows = jnp.where(live_done(done), jnp.int32(g1), 1 + acc_count)
        c = jnp.minimum(jnp.min(c_rows),
                        jnp.int32(max_new_tokens) - g)  # never overshoot

        out = jax.lax.dynamic_update_slice(out, emit, (jnp.int32(0), g))
        if not is_model_draft:
            new_ctx = jax.lax.dynamic_update_slice(
                new_ctx, emit, (jnp.int32(0), idx))
        committed = jnp.arange(g1)[None, :] < c
        done = done | jnp.any((emit == eos) & committed, axis=1)
        last_logits = jax.lax.dynamic_index_in_dim(
            logits, c - 1, axis=1, keepdims=False)
        new_prev = jax.lax.dynamic_index_in_dim(
            emit, c - 1, axis=1, keepdims=False)       # last committed token
        stats = stats + jnp.stack(
            [jnp.int32(1), jnp.int32(gamma), c - 1])
        return (g + c, out, last_logits, new_cache, d_cache, new_ctx,
                new_prev, done, stats)

    g, out, _, _, _, _, _, done, stats = jax.lax.while_loop(
        cond, chunk,
        (jnp.int32(0), out, last_logits, cache, d_cache, ctx, prev_tok,
         done, stats))

    # positions past the stop point: every live row is done there, and
    # greedy emits EOS after EOS — fill, then trim the scratch tail
    tokens = jnp.where(jnp.arange(max_new_tokens + g1)[None, :] >= g,
                       eos, out)[:, :max_new_tokens]
    is_eos = tokens == eos
    gen_len = jnp.where(
        is_eos.any(axis=1),
        jnp.argmax(is_eos, axis=1),
        jnp.int32(max_new_tokens),
    )
    return tokens, gen_len, stats
