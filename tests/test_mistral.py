"""Mistral-family LM tests: RoPE/GQA/sliding-window semantics, the
prefill+cached-decode contract vs the plain forward, checkpoint
conversion, TP sharding, and the serving PromptGenerator wiring.

The reference uses hosted Mistral-7B-Instruct for prompt generation
(reference backend.py:25, 240-268); these tests cover the local
TPU-native replacement at tiny dims.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import MistralConfig
from cassmantle_tpu.models.mistral import (
    MistralLM,
    apply_rope,
    band_mask,
    repeat_kv,
    rope_tables,
)

CFG = MistralConfig.tiny()


@pytest.fixture(scope="module")
def model_and_params():
    model = MistralLM(CFG)
    ids = jnp.zeros((1, 8), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return model, params


def test_rope_rotation_preserves_norm_and_relative_angles():
    cos, sin = rope_tables(jnp.arange(6), 8, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 8))
    rot = apply_rope(x, cos, sin)
    # rotations preserve per-pair L2 norm
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rot), axis=-1),
        rtol=1e-5,
    )
    # position 0 is the identity rotation
    np.testing.assert_allclose(
        np.asarray(rot[:, 0]), np.asarray(x[:, 0]), rtol=1e-6, atol=1e-6
    )
    # dot products depend only on relative offset: <r(q,i), r(k,i+d)>
    # equal for all i
    q = jax.random.normal(jax.random.PRNGKey(2), (8,))
    k = jax.random.normal(jax.random.PRNGKey(3), (8,))
    cos6, sin6 = rope_tables(jnp.arange(6), 8, 10000.0)
    qr = apply_rope(jnp.tile(q, (1, 6, 1, 1)), cos6, sin6)[0, :, 0]
    kr = apply_rope(jnp.tile(k, (1, 6, 1, 1)), cos6, sin6)[0, :, 0]
    dots = [float(qr[i] @ kr[i + 2]) for i in range(4)]
    np.testing.assert_allclose(dots, dots[0] * np.ones(4), rtol=1e-4)


def test_band_mask_window():
    m = np.asarray(band_mask(jnp.arange(5), jnp.arange(5), 2))
    expected = np.array([
        [1, 0, 0, 0, 0],
        [1, 1, 0, 0, 0],
        [0, 1, 1, 0, 0],
        [0, 0, 1, 1, 0],
        [0, 0, 0, 1, 1],
    ], dtype=bool)
    np.testing.assert_array_equal(m, expected)


def test_repeat_kv():
    kv = jnp.arange(2 * 3 * 2 * 4).reshape(2, 3, 2, 4).astype(jnp.float32)
    rep = repeat_kv(kv, 2)
    assert rep.shape == (2, 3, 4, 4)
    np.testing.assert_array_equal(np.asarray(rep[:, :, 0]),
                                  np.asarray(rep[:, :, 1]))
    np.testing.assert_array_equal(np.asarray(rep[:, :, 0]),
                                  np.asarray(kv[:, :, 0]))


def test_forward_shapes_and_finite(model_and_params):
    model, params = model_and_params
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                             CFG.vocab_size)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 12, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_prefill_matches_forward(model_and_params):
    """Prefill's last-real-token logits == full forward at that position,
    including for right-padded rows."""
    model, params = model_and_params
    b, p, max_len = 2, 8, 12
    ids = jax.random.randint(jax.random.PRNGKey(5), (b, p), 0,
                             CFG.vocab_size)
    plen = jnp.asarray([8, 5], dtype=jnp.int32)
    last, cache = model.apply(params, ids, plen, max_len,
                              method=MistralLM.prefill)
    assert len(cache) == CFG.num_layers
    assert cache[0][0].shape == (b, max_len, CFG.num_kv_heads, CFG.head_dim)

    valid = jnp.arange(p)[None, :] < plen[:, None]
    full = model.apply(params, ids, valid)
    for row in range(b):
        np.testing.assert_allclose(
            np.asarray(last[row]),
            np.asarray(full[row, int(plen[row]) - 1]),
            atol=1e-4, rtol=1e-4,
        )


def test_cached_decode_matches_forward(model_and_params):
    """Greedy continuation via prefill+decode_step equals recomputing the
    full forward each step — the KV-cache/RoPE/window contract."""
    model, params = model_and_params
    p, steps, max_len = 6, 4, 12
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, p), 0,
                             CFG.vocab_size)
    plen = jnp.asarray([p], dtype=jnp.int32)

    last, cache = model.apply(params, ids, plen, max_len,
                              method=MistralLM.prefill)
    positions = jnp.arange(max_len)[None, :]
    seq = ids
    for i in range(steps):
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        idx = jnp.int32(p + i)
        valid = positions <= idx
        last, cache = model.apply(params, tok, idx, cache, valid,
                                  jnp.full((1, 1), idx),
                                  method=MistralLM.decode_step)
        full = model.apply(params, seq)
        np.testing.assert_allclose(
            np.asarray(last[0]), np.asarray(full[0, -1]),
            atol=2e-4, rtol=2e-4,
        )


def test_sliding_window_limits_attention(model_and_params):
    """With window W, logits at position i are unchanged by tokens at
    positions <= i - W."""
    model, params = model_and_params
    w = CFG.sliding_window  # 16 in tiny config
    s = w + 4
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, s), 0,
                             CFG.vocab_size)
    # perturb the earliest token: outside the window of the last position
    ids2 = ids.at[0, 0].set((ids[0, 0] + 1) % CFG.vocab_size)
    out1 = model.apply(params, ids)
    out2 = model.apply(params, ids2)
    # note: with >= 2 layers information propagates through intermediate
    # positions, so only a 1-layer check would be exact. Build a 1-layer
    # model to assert exact independence.
    one = dataclasses.replace(CFG, num_layers=1)
    m1 = MistralLM(one)
    p1 = m1.init(jax.random.PRNGKey(8), ids)
    o1 = m1.apply(p1, ids)
    o2 = m1.apply(p1, ids2)
    np.testing.assert_allclose(
        np.asarray(o1[0, -1]), np.asarray(o2[0, -1]), atol=1e-5, rtol=1e-5
    )
    # sanity: within-window positions DO see the change
    assert not np.allclose(np.asarray(o1[0, 1]), np.asarray(o2[0, 1]))
    del out1, out2


def test_greedy_decode_integration(model_and_params):
    from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair

    model, params = model_and_params
    ids = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0,
                             CFG.vocab_size)
    plen = jnp.asarray([8, 4], dtype=jnp.int32)
    tokens, gen_len = greedy_decode(
        make_apply_pair(model), params, ids, plen,
        jax.random.PRNGKey(0), 6, 0,
        position_offset=jnp.zeros((2,), jnp.int32),
    )
    assert tokens.shape == (2, 6)
    assert (np.asarray(gen_len) <= 6).all()


def test_convert_mistral_roundtrip(model_and_params):
    """Fabricate a torch-layout checkpoint from known Flax params and
    assert the converter reproduces them exactly."""
    from cassmantle_tpu.models.weights import convert_mistral

    model, params = model_and_params
    p = params["params"]
    src = {}
    src["model.embed_tokens.weight"] = np.asarray(p["embed"]["embedding"])
    for i in range(CFG.num_layers):
        b = p[f"block_{i}"]
        pre = f"model.layers.{i}"
        src[f"{pre}.input_layernorm.weight"] = np.asarray(b["ln1"]["scale"])
        src[f"{pre}.post_attention_layernorm.weight"] = np.asarray(
            b["ln2"]["scale"])
        for name, hf in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj"),
                         ("out", "o_proj")):
            src[f"{pre}.self_attn.{hf}.weight"] = np.asarray(
                b["attn"][name]["kernel"]).T
        for name, hf in (("gate", "gate_proj"), ("up", "up_proj"),
                         ("down", "down_proj")):
            src[f"{pre}.mlp.{hf}.weight"] = np.asarray(
                b["mlp"][name]["kernel"]).T
    src["model.norm.weight"] = np.asarray(p["ln_f"]["scale"])
    src["lm_head.weight"] = np.asarray(p["lm_head"]["kernel"]).T

    converted = convert_mistral(src, CFG.num_layers)
    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = jax.tree_util.tree_leaves_with_path(converted)
    assert len(flat_a) == len(flat_b)
    paths_a = {jax.tree_util.keystr(k): v for k, v in flat_a}
    paths_b = {jax.tree_util.keystr(k): v for k, v in flat_b}
    assert paths_a.keys() == paths_b.keys()
    for key, val in paths_a.items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(paths_b[key]), err_msg=key)

    # converted params actually run
    ids = jnp.zeros((1, 4), dtype=jnp.int32)
    out = model.apply(converted, ids)
    assert np.isfinite(np.asarray(out)).all()


def test_tp_sharding_rules_cover_mistral(model_and_params):
    from jax.sharding import PartitionSpec as P

    from cassmantle_tpu.parallel.sharding import param_specs

    _, params = model_and_params
    specs = param_specs(params)
    flat = {
        jax.tree_util.keystr(k): v
        for k, v in jax.tree_util.tree_leaves_with_path(specs)
    }
    get = lambda s: [v for k, v in flat.items() if s in k]
    assert all(s == P(None, "tp") for s in get("attn']['q']['kernel"))
    assert all(s == P(None, "tp") for s in get("mlp']['gate']['kernel"))
    assert all(s == P(None, "tp") for s in get("mlp']['up']['kernel"))
    assert all(s == P("tp", None) for s in get("mlp']['down']['kernel"))
    assert all(s == P("tp", None) for s in get("attn']['out']['kernel"))


def test_prompt_generator_mistral_family(tmp_path, decode_dispatches):
    """PromptGenerator serves the Mistral family end to end (byte
    tokenizer fallback, random weights): text comes back non-empty."""
    import dataclasses as dc

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    base = test_config()
    cfg = base.replace(
        models=dc.replace(base.models, mistral=MistralConfig.tiny())
    )
    gen = PromptGenerator(cfg)
    from cassmantle_tpu.models.mistral import MistralLM as cls_check

    assert isinstance(gen.model, cls_check)
    text = gen.generate("An old ship left the harbor", max_new_tokens=4)
    assert isinstance(text, str) and len(text) > 0
    # its sliding window counts cache slots: a batch of two prompt
    # buckets stays two dispatches, each row in its own bucket's program
    decode_dispatches.clear()
    gen.decode_ids_batch(["An old ship", "An old ship left the harbor "
                          "under a sky of slate and rust"], max_new_tokens=2)
    assert decode_dispatches == [((1, 32), [0]), ((1, 61), [0])]
