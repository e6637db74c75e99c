"""End-to-end slice (SURVEY.md §7 stage 3): tiny models on CPU-JAX, real
pipelines wired into the real game engine — content generated by GPT-2
greedy decode + DDIM diffusion, guesses scored by MiniLM cosine."""

import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import test_config as _tiny_config
from cassmantle_tpu.engine.game import Game
from cassmantle_tpu.engine.store import MemoryStore
from cassmantle_tpu.ops.blur import device_blur
from cassmantle_tpu.ops.scorer import EmbeddingScorer
from cassmantle_tpu.serving.pipeline import (
    PromptGenerator,
    Text2ImagePipeline,
    TPUContentBackend,
    two_sentences,
)


@pytest.fixture(scope="module")
def tiny_cfg():
    return _tiny_config()


@pytest.fixture(scope="module")
def t2i(tiny_cfg):
    return Text2ImagePipeline(tiny_cfg)


@pytest.fixture(scope="module")
def promptgen(tiny_cfg):
    return PromptGenerator(tiny_cfg)


def test_t2i_generates_images(t2i, tiny_cfg):
    images = t2i.generate(["a glowing lighthouse at dusk"], seed=1)
    size = tiny_cfg.sampler.image_size
    assert images.shape == (1, size, size, 3)
    assert images.dtype == np.uint8
    # determinism for a fixed seed
    again = t2i.generate(["a glowing lighthouse at dusk"], seed=1)
    np.testing.assert_array_equal(images, again)
    # different seed -> different image
    other = t2i.generate(["a glowing lighthouse at dusk"], seed=2)
    assert not np.array_equal(images, other)


def test_t2i_batch(t2i, tiny_cfg):
    images = t2i.generate(["storm", "harbor"], seed=0)
    assert images.shape[0] == 2


def test_negative_prompt_conditions_the_uncond_arm(tiny_cfg):
    """The configured negative prompt (reference backend.py:284 passes
    one to its hosted diffusion call) reaches the CFG uncond tower AND
    changes the output — not just a config field."""
    import dataclasses

    cfg = tiny_cfg.replace(sampler=dataclasses.replace(
        tiny_cfg.sampler, negative_prompt="blurry distorted fake"))
    pipe = Text2ImagePipeline(cfg, share_params_with=None)
    seen = []
    orig = pipe._tokenize
    pipe._tokenize = lambda prompts: (seen.append(list(prompts)),
                                      orig(prompts))[1]
    with_neg = pipe.generate(["a lighthouse"], seed=3)
    assert ["blurry distorted fake"] in seen

    neutral = Text2ImagePipeline(tiny_cfg, share_params_with=pipe)
    without = neutral.generate(["a lighthouse"], seed=3)
    assert not np.array_equal(with_neg, without)


@pytest.mark.slow
def test_t2i_data_parallel_mesh(tiny_cfg):
    """SD1.5 pipeline shards the batch over dp (same contract as SDXL's).

    Slow tier since round 25 (the default tier outgrew its 870s window
    again, same pressure as rounds 14/21): ~20s of mesh-pipeline
    compile whose tier-1 coverage is duplicated — test_multihost (fast)
    smokes the multi-device path, test_parallel (full tier) runs the
    real mesh collectives, and the dp padding arithmetic this test
    exercises is host-side logic pinned by the batch tests above."""
    import jax

    from cassmantle_tpu.config import MeshConfig
    from cassmantle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshConfig(dp=-1))
    pipe = Text2ImagePipeline(tiny_cfg, mesh=mesh)
    assert pipe.dp == len(jax.devices())
    size = tiny_cfg.sampler.image_size
    # partial batch pads to dp width; pad rows dropped
    images = pipe.generate(["storm", "harbor", "dune"], seed=0)
    assert images.shape == (3, size, size, 3)


def test_prompt_generator_decodes(promptgen):
    text = promptgen.generate("The Night the Trains Sang", max_new_tokens=8)
    assert isinstance(text, str) and len(text) > 0
    # deterministic greedy
    again = promptgen.generate("The Night the Trains Sang", max_new_tokens=8)
    assert text == again


def test_batched_decode_matches_single(promptgen):
    """decode_ids_batch(N texts) row-for-row equals N single decodes: a
    short prompt co-batched with a long one runs in the long one's
    program but at its OWN bucket's positions (decoding at the long
    bucket's would make round text depend on which requests happened to
    batch together). The third text is long enough to land in a
    different prompt bucket than the first two."""
    texts = ["the storm rolled", "a quiet harbor town",
             "the last train left the station long after midnight, "
             "its lanterns swinging over the empty platform boards"]
    tok_b, len_b = promptgen.decode_ids_batch(texts, max_new_tokens=6)
    assert tok_b.shape[0] == 3 and len_b.shape == (3,)
    for i, t in enumerate(texts):
        tok_1, len_1 = promptgen.decode_ids(t, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(tok_b[i]),
                                      np.asarray(tok_1[0]))
        assert int(len_b[i]) == int(len_1[0])


def test_a_batch_of_mixed_prompt_buckets_is_one_dispatch(
        promptgen, decode_dispatches):
    """One program a batch, its widest row's, padded to the batch bucket
    (the tiny model's 64 positions leave 59 for a prompt before 4 new
    tokens, the widest bucket here); each row carries its own bucket less
    the program's as its position offset, and a batch of one bucket
    carries zeros: the same operand, so the same compiled program."""
    texts = ["the storm rolled", "a quiet harbor town by the grey sea",
             "the last train left the station long after midnight, "
             "its lanterns swinging over the empty platform boards"]
    promptgen.decode_ids_batch(texts, max_new_tokens=4)
    promptgen.decode_ids_batch(texts[:2] * 2, max_new_tokens=4)
    promptgen.decode_ids_batch(texts[:1] * 2, max_new_tokens=4)
    assert decode_dispatches == [
        ((4, 59), [32 - 59, 0, 0, 0]),
        ((4, 59), [32 - 59, 0, 32 - 59, 0]),
        ((2, 32), [0, 0])]


def test_generate_batch_matches_single(promptgen):
    texts = ["The Night the Trains Sang", "The Harbor Remembers"]
    batched = promptgen.generate_batch(texts, max_new_tokens=8)
    singles = [promptgen.generate(t, max_new_tokens=8) for t in texts]
    assert batched == singles


def test_batched_decode_reuses_compiled_buckets(promptgen):
    """Batches of 3 and 4 share the (4, P) graph; greedy_decode must
    not recompile for the second call (persistent compiled buckets —
    VERDICT r4 #4)."""
    from cassmantle_tpu.ops.decode import greedy_decode

    promptgen.decode_ids_batch(["a", "b", "c"], max_new_tokens=4)
    misses = greedy_decode._cache_size()
    promptgen.decode_ids_batch(["d", "e", "f", "g"], max_new_tokens=4)
    assert greedy_decode._cache_size() == misses


def test_decode_first_token_matches_plain_forward(promptgen, tiny_cfg):
    """The bucketed/padded decode path agrees with the plain forward on
    the next token. Regression: the byte-fallback tokenizer's pad id
    (258) exceeded the tiny model vocab (256) and NaN-filled the padded
    embeddings (flax Embed out-of-bounds take), silently turning every
    tiny-config decode into token-0 garbage."""
    import jax.numpy as jnp

    from cassmantle_tpu.models import GPT2LM

    m = tiny_cfg.models.gpt2
    toks = [t % m.vocab_size
            for t in promptgen.tokenizer.encode("the storm rolled")]
    out, _ = promptgen.decode_ids("the storm rolled", max_new_tokens=1,
                                  seed=0)
    ref = GPT2LM(m).apply(promptgen.params, jnp.asarray([toks]))
    assert int(out[0, 0]) == int(jnp.argmax(ref[0, -1]))
    assert bool(jnp.isfinite(ref).all())


def test_sampled_decode_modes(tiny_cfg):
    """text_temperature>0 switches to top-k sampling: seed-determinstic,
    seed-sensitive, in-vocab; top_k=1 collapses back to exact greedy.
    Temperature 0 (default) stays the reference-parity greedy path."""
    import dataclasses

    import jax.numpy as jnp

    cfg = tiny_cfg.replace(sampler=dataclasses.replace(
        tiny_cfg.sampler, text_temperature=1.0, text_top_k=8))
    gen = PromptGenerator(cfg)
    t1, n1 = gen.decode_ids("the storm rolled", max_new_tokens=8, seed=7)
    t1b, _ = gen.decode_ids("the storm rolled", max_new_tokens=8, seed=7)
    assert jnp.array_equal(t1, t1b)  # same seed -> same sample
    vocab = cfg.models.gpt2.vocab_size
    assert int(jnp.max(t1)) < vocab and int(jnp.min(t1)) >= 0
    # a different seed flips at least one token at temperature 1 over a
    # random-init model (near-uniform top-k)
    t2, _ = gen.decode_ids("the storm rolled", max_new_tokens=8, seed=8)
    assert not jnp.array_equal(t1, t2)

    # auto-advancing internal key: two seedless sampled calls differ
    a, _ = gen.decode_ids("the storm rolled", max_new_tokens=8)
    b, _ = gen.decode_ids("the storm rolled", max_new_tokens=8)
    assert not jnp.array_equal(a, b)

    # top_k=1 == greedy, regardless of temperature
    cfg_k1 = tiny_cfg.replace(sampler=dataclasses.replace(
        tiny_cfg.sampler, text_temperature=1.0, text_top_k=1))
    greedy = PromptGenerator(tiny_cfg)
    g1, _ = greedy.decode_ids("the storm rolled", max_new_tokens=8, seed=0)
    k1, _ = PromptGenerator(cfg_k1).decode_ids(
        "the storm rolled", max_new_tokens=8, seed=3)
    assert jnp.array_equal(g1, k1)


def test_two_sentences():
    assert two_sentences("A. B. C. D") == "A. B."
    assert two_sentences("One sentence only") == "One sentence only."
    assert two_sentences("") != ""


@pytest.mark.asyncio
async def test_full_game_on_tpu_backend(tiny_cfg):
    """The minimum end-to-end slice: real pipelines + real engine."""
    store = MemoryStore()
    backend = TPUContentBackend(tiny_cfg)
    scorer = EmbeddingScorer(tiny_cfg.models.minilm, seq_len=8,
                             batch_buckets=(4, 16))
    game = Game(
        tiny_cfg, store, backend,
        embed=scorer.embed,
        similarity=scorer.similarity_async,
        blur_fn=device_blur,
    )
    await game.startup()
    prompt = await game.rounds.fetch_current_prompt()
    assert prompt["tokens"] and len(prompt["masks"]) == 2

    await game.init_client("s1")
    contents_img = await game.fetch_masked_image("s1")
    assert contents_img.shape == (
        tiny_cfg.sampler.image_size, tiny_cfg.sampler.image_size, 3
    )

    # wrong guess: low score, no win
    masks = prompt["masks"]
    result = await game.compute_client_scores(
        "s1", {str(masks[0]): "zzzz", str(masks[1]): "qqqq"}
    )
    assert result["won"] == 0

    # exact answers win
    answers = {str(m): prompt["tokens"][m] for m in masks}
    result = await game.compute_client_scores("s1", answers)
    assert result["won"] == 1

    # buffered next round generates through the real pipelines
    await game.rounds.buffer_contents()
    await game.rounds.promote_buffer()
    story = await game.fetch_story()
    assert int(story["episode"]) == 2
