"""Model zoo tests on CPU-JAX with tiny configs: shapes, determinism,
causality, and KV-cache parity (SURVEY.md §4 tier 1-2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.models import (
    ClipTextEncoder,
    GPT2LM,
    MiniLMEncoder,
    UNet,
    VAEDecoder,
    VAEEncoder,
)
from cassmantle_tpu.models.vae import postprocess_images
from cassmantle_tpu.models.weights import init_params


@pytest.fixture(scope="module")
def tiny(cfg):
    return cfg.models


def test_clip_text_shapes(tiny):
    model = ClipTextEncoder(tiny.clip_text)
    ids = jnp.array([[1, 5, 9, 2, 0, 0, 0, 0]], dtype=jnp.int32)
    params = init_params(model, 0, ids)
    out = model.apply(params, ids)
    assert out["hidden"].shape == (1, 8, tiny.clip_text.hidden_size)
    assert out["pooled"].shape == (1, tiny.clip_text.hidden_size)
    # deterministic
    out2 = model.apply(params, ids)
    np.testing.assert_allclose(out["hidden"], out2["hidden"])


def test_clip_text_causal(tiny):
    """Changing a later token must not affect earlier hidden states."""
    model = ClipTextEncoder(tiny.clip_text)
    ids_a = jnp.array([[1, 5, 9, 2]], dtype=jnp.int32)
    ids_b = jnp.array([[1, 5, 9, 7]], dtype=jnp.int32)
    params = init_params(model, 0, ids_a)
    ha = model.apply(params, ids_a)["hidden"]
    hb = model.apply(params, ids_b)["hidden"]
    np.testing.assert_allclose(ha[:, :3], hb[:, :3], atol=1e-5)
    assert not np.allclose(ha[:, 3], hb[:, 3])


def test_unet_shapes_and_determinism(tiny):
    model = UNet(tiny.unet)
    lat = jnp.ones((2, 16, 16, 4), dtype=jnp.float32)
    t = jnp.array([10, 20], dtype=jnp.int32)
    ctx = jnp.ones((2, 8, tiny.unet.context_dim), dtype=jnp.float32)
    params = init_params(model, 0, lat, t, ctx)
    out = model.apply(params, lat, t, ctx)
    assert out.shape == lat.shape
    assert out.dtype == jnp.float32
    assert np.isfinite(np.asarray(out)).all()
    out2 = model.apply(params, lat, t, ctx)
    np.testing.assert_allclose(out, out2)


def test_unet_timestep_sensitivity(tiny):
    model = UNet(tiny.unet)
    lat = jnp.ones((1, 16, 16, 4), dtype=jnp.float32)
    ctx = jnp.ones((1, 8, tiny.unet.context_dim), dtype=jnp.float32)
    params = init_params(model, 0, lat, jnp.array([0]), ctx)
    o1 = model.apply(params, lat, jnp.array([0]), ctx)
    o2 = model.apply(params, lat, jnp.array([500]), ctx)
    assert not np.allclose(o1, o2)


def test_vae_decoder_shapes(tiny):
    model = VAEDecoder(tiny.vae)
    lat = jnp.zeros((1, 8, 8, 4), dtype=jnp.float32)
    params = init_params(model, 0, lat)
    out = model.apply(params, lat)
    # channel_mults has 2 levels -> one 2x upsample
    assert out.shape == (1, 16, 16, 3)
    u8 = postprocess_images(out)
    assert u8.dtype == jnp.uint8


def test_vae_encoder_decoder_roundtrip_shapes(tiny):
    enc = VAEEncoder(tiny.vae)
    img = jnp.zeros((1, 16, 16, 3), dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    params = init_params(enc, 0, img, rng)
    z = enc.apply(params, img, rng)
    assert z.shape == (1, 8, 8, 4)


def test_gpt2_forward_and_causality(tiny):
    model = GPT2LM(tiny.gpt2)
    ids = jnp.array([[3, 7, 11, 2, 5]], dtype=jnp.int32)
    params = init_params(model, 0, ids)
    logits = model.apply(params, ids)
    assert logits.shape == (1, 5, tiny.gpt2.vocab_size)
    ids2 = ids.at[0, 4].set(9)
    logits2 = model.apply(params, ids2)
    np.testing.assert_allclose(logits[:, :4], logits2[:, :4], atol=1e-4)


def test_gpt2_kv_cache_matches_full_forward(tiny):
    """Greedy path correctness: prefill+decode_step == full forward."""
    model = GPT2LM(tiny.gpt2)
    max_len = 12
    ids = jnp.array([[3, 7, 11, 2, 0, 0]], dtype=jnp.int32)  # padded to 6
    prompt_len = jnp.array([4], dtype=jnp.int32)
    params = init_params(model, 0, ids)

    last_logits, cache = model.apply(
        params, ids, prompt_len, max_len, method=GPT2LM.prefill
    )
    full_logits = model.apply(params, ids[:, :4])
    np.testing.assert_allclose(
        last_logits, full_logits[:, 3], rtol=2e-4, atol=2e-4
    )

    # decode one step with the cache vs running the extended sequence
    next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    valid = (jnp.arange(max_len)[None, :] < 4) | (
        jnp.arange(max_len)[None, :] == 4
    )
    step_logits, cache = model.apply(
        params, next_tok, jnp.int32(4), cache, valid, jnp.full((1, 1), 4),
        method=GPT2LM.decode_step,
    )
    ext = jnp.concatenate([ids[:, :4], next_tok[:, None]], axis=1)
    full_ext = model.apply(params, ext)
    np.testing.assert_allclose(
        step_logits, full_ext[:, 4], rtol=2e-4, atol=2e-4
    )


def test_gpt2_row_in_a_wider_buckets_program_sits_at_its_own_positions(tiny):
    """A row of prompt bucket 6 decoded in the program of bucket 10 (its
    batch's widest row's): written to cache slots ``10 + i``, at position
    ``6 + i``. Its logits are the plain forward's over its own tokens at
    those positions and its solo decode's in bucket 6; the slot's
    position in the row's place (what a mixed batch read before
    ``greedy_decode`` took offsets) reads otherwise."""
    model = GPT2LM(tiny.gpt2)
    prompt = jnp.array([[3, 7, 11, 2]], dtype=jnp.int32)
    generated = jnp.array([[5, 9, 1]], dtype=jnp.int32)
    plen = jnp.array([4], dtype=jnp.int32)
    params = init_params(model, 0, prompt)

    def through_the_cache(bucket: int, own: int):
        ids = jnp.pad(prompt, ((0, 0), (0, bucket - 4)))
        max_len = bucket + 3
        logits, cache = model.apply(params, ids, plen, max_len,
                                    method=GPT2LM.prefill)
        out, slots = [logits], jnp.arange(max_len)[None, :]
        for i in range(2):
            valid = (slots < 4) | ((slots >= bucket) & (slots <= bucket + i))
            logits, cache = model.apply(
                params, generated[:, i], jnp.int32(bucket + i), cache, valid,
                jnp.full((1, 1), own + i), method=GPT2LM.decode_step)
            out.append(logits)
        return np.stack([np.asarray(x[0]) for x in out])

    seq = jnp.concatenate([prompt, generated[:, :2]], axis=1)
    positions = jnp.array([[0, 1, 2, 3, 6, 7]])
    want = np.asarray(model.apply(params, seq, None, positions))[0, 3:]
    mixed = through_the_cache(10, own=6)
    np.testing.assert_allclose(mixed, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(mixed, through_the_cache(6, own=6),
                               rtol=2e-4, atol=2e-4)
    assert np.abs(through_the_cache(10, own=10)[1:] - want[1:]).max() > 0.01


def test_minilm_embeddings(tiny):
    model = MiniLMEncoder(tiny.minilm)
    ids = jnp.array([[5, 9, 2, 0], [7, 0, 0, 0]], dtype=jnp.int32)
    mask = jnp.array([[1, 1, 1, 0], [1, 0, 0, 0]], dtype=jnp.int32)
    params = init_params(model, 0, ids, mask)
    emb = model.apply(params, ids, mask)
    assert emb.shape == (2, tiny.minilm.hidden_size)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(emb), axis=-1), 1.0, atol=1e-4
    )
    # padding must not influence the embedding
    ids_b = ids.at[0, 3].set(99)
    emb_b = model.apply(params, ids_b, mask)
    np.testing.assert_allclose(emb[0], emb_b[0], atol=1e-5)
