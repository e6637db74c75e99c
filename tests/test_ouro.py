"""Ouro-family looped prompt LM at the tiny size (two layers, four passes):
the program against its plain reference (benchmarks/references/ouro.py)
on seeded weights, through the cache, at 1, 2 and 4 rows and under mixed
prompt buckets; program variants the comparison has to see; the
published configuration held once; PromptGenerator serves the family,
publishes its counters and books its FLOPs once a pass.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import ouro as plain
from cassmantle_tpu import config as configs
from cassmantle_tpu.config import OuroConfig, SpecDecodeConfig
from cassmantle_tpu.models import ouro
from cassmantle_tpu.models.ouro import OuroLM, active_params, cache_stats
from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair
from tests.test_qwen3_next import (
    counters,
    decode_through_cache,
    error,
    sizes_of,
)

TINY = OuroConfig.tiny()
#: logits are of order 1 to 4. float32 differs from the reference by the
#: order of its sums alone (the cache changes no arithmetic: k/v as they
#: were written, every pass its own), so the worst logit counts: it read
#: 2.3e-6 to 4.2e-6. bfloat16 rounds the prompt's activations into every
#: product, and the error compounds over four passes of the stack: the
#: median position counts, 0.023 to 0.042 at 1, 2 and 4 rows; a program
#: that loops otherwise reads 1.25 to 4.2 (at least 15 times 0.08)
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.08}


def sizes(cfg) -> dict:
    return dict(sizes_of(cfg), hidden_act="silu", use_sliding_window=False,
                rope_scaling=None)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    """(model, params, sizes) in one storage dtype; the tree is cast as
    the serving path casts it."""
    cfg = dataclasses.replace(TINY, dtype=request.param)
    model = OuroLM(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: a.astype(request.param), params)
    return model, params, sizes(cfg)


def reference_of_row(params, sz, prompt, generated, bucket):
    ids = np.concatenate([prompt, generated])[None]
    positions = np.concatenate(
        [np.arange(len(prompt)), bucket + np.arange(len(generated))])[None]
    logits = plain.ouro_logits(params, jnp.asarray(ids),
                               jnp.asarray(positions), sz)
    return np.asarray(logits)[0, len(prompt) - 1:-1]


PROMPTS = [np.arange(5, 12), np.arange(40, 52), np.arange(90, 93),
           np.arange(150, 165)]
GENERATED = np.random.RandomState(0).randint(0, 256, (4, 6))


def test_full_forward_against_the_plain_reference(lm):
    model, params, sz = lm
    ids = np.random.RandomState(1).randint(0, 256, (2, 20))
    positions = np.broadcast_to(np.arange(20), (2, 20))
    got = jax.jit(model.apply)(params, jnp.asarray(ids))
    want = plain.ouro_logits(params, jnp.asarray(ids),
                             jnp.asarray(positions), sz)
    assert float(jnp.abs(want).max()) > 1.0
    assert error(got, want, sz["dtype"]) < TOLERANCE[sz["dtype"]]


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_prefill_then_decode_through_the_cache_against_the_reference(
        lm, rows):
    """Rows of different ``prompt_len`` share one bucket; each row's
    logits are the full forward's over its own tokens, the generated ones
    at positions ``bucket + i``."""
    model, params, sz = lm
    got, _ = decode_through_cache(model, params, PROMPTS[:rows],
                                  GENERATED[:rows], 16)
    for r, prompt in enumerate(PROMPTS[:rows]):
        want = reference_of_row(params, sz, prompt, GENERATED[r], 16)
        assert error(got[r], want, sz["dtype"]) < TOLERANCE[sz["dtype"]], r


def test_rows_of_mixed_buckets_decode_at_their_own_positions(lm):
    """Rows of bucket 16 in the program of a batch whose widest row is of
    bucket 32: each row's logits are the reference's with its generated
    tokens at ``own bucket + i``."""
    model, params, sz = lm
    prompts, own = PROMPTS[:2] + [np.arange(150, 170)], [16, 16, 32]
    got, _ = decode_through_cache(model, params, prompts, GENERATED[:3], 32,
                                  own=own)
    for r, prompt in enumerate(prompts):
        want = reference_of_row(params, sz, prompt, GENERATED[r], own[r])
        assert error(got[r], want, sz["dtype"]) < TOLERANCE[sz["dtype"]], r


def faulty_loop(fault: str):
    """``OuroLM._loop`` with one fault planted: ``shared_cache`` (passes 0
    and 1 write and read one cache entry, 2 and 3 another),
    ``no_norm_between`` (the final norm after the last pass alone) or
    ``a_pass_dropped`` (the first pass left out)."""

    def loop(self, x, cache, positions, mask, index=None, gates=False):
        passes = self.cfg.total_ut_steps
        layers = jnp.arange(self.cfg.num_hidden_layers)

        def one_pass(mdl, carry, p):
            slot = p // 2 if fault == "shared_cache" else p
            (x, cache), fresh = mdl.layers(carry, layers, positions, mask,
                                           slot, index)
            x = (jnp.where(p == passes - 1, mdl.norm(x), x)
                 if fault == "no_norm_between" else mdl.norm(x))
            if gates:
                mdl.sow("intermediates", "exit_gate",
                        jax.nn.sigmoid(mdl.early_exit_gate(x)))
            return (x, cache), fresh

        scan = nn.scan(one_pass, variable_broadcast="params",
                       variable_axes={"intermediates": 0},
                       split_rngs={"params": False})
        first = 1 if fault == "a_pass_dropped" else 0
        (x, cache), fresh = scan(self, (x, cache), jnp.arange(first, passes))
        return x, (fresh if cache is None else cache)

    return loop


@pytest.mark.parametrize("fault", [
    "shared_cache", "no_norm_between", "a_pass_dropped"],
    ids=["passes_share_a_cache", "no_norm_between_passes", "a_pass_dropped"])
def test_a_program_that_loops_otherwise_fails_the_comparison(
        lm, monkeypatch, fault):
    """What the comparison has to be able to see: passes that share one
    cache entry, a state handed on without the final norm between passes,
    or a pass dropped move the logits far past the tolerance."""
    model, params, sz = lm
    monkeypatch.setattr(OuroLM, "_loop", faulty_loop(fault))
    got, _ = decode_through_cache(model, params, PROMPTS[:2], GENERATED[:2],
                                  16)
    worst = max(error(got[r], reference_of_row(params, sz, PROMPTS[r],
                                               GENERATED[r], 16), sz["dtype"])
                for r in range(2))
    assert worst > 4 * TOLERANCE[sz["dtype"]]


def test_the_gate_is_computed_every_pass_and_threshold_1_runs_them_all(lm):
    """The plain forward sows the exit gate of each pass; at the published
    threshold every position runs every pass, and a threshold the gates
    reach earlier is refused by the reference, which computes them too."""
    model, params, sz = lm
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 9)))
    _, sown = model.apply(params, ids, mutable=["intermediates"])
    (gates,) = sown["intermediates"]["exit_gate"]
    assert gates.shape == (4, 2, 9, 1)
    assert (plain.exit_pass(gates[..., 0], 1.0) == 3).all()
    positions = jnp.broadcast_to(jnp.arange(9), (2, 9))
    with pytest.raises(ValueError, match="leaves before the last pass"):
        plain.ouro_logits(params, ids, positions,
                          dict(sz, early_exit_threshold=0.1))


def test_the_published_configuration_is_held_once():
    """2,667,974,657 parameters: 48 layers of 51,388,416 on one stacked
    axis, the untied embedding and head, the final norm and the exit gate;
    the tree holds each layer once, not once a pass."""
    cfg = configs.ouro_game_config().models.ouro
    tree = jax.eval_shape(OuroLM(cfg).init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    p = tree["params"]
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree)) \
        == 2_667_974_657
    stack = jax.tree_util.tree_leaves(p["layers"])
    assert {leaf.shape[0] for leaf in stack} == {48}
    assert sum(leaf.size for leaf in stack) == 48 * 51_388_416
    assert sorted(p) == ["early_exit_gate", "embed_tokens", "layers",
                         "lm_head", "norm"]
    assert (cfg.total_ut_steps, cfg.num_hidden_layers, cfg.vocab_size) == (
        4, 48, 49152)


def test_the_cache_holds_an_entry_for_every_pass_and_layer(lm):
    model, params, _ = lm
    _, cache = model.apply(params, jnp.zeros((2, 8), jnp.int32),
                           jnp.asarray([8, 3]), 20, method=OuroLM.prefill)
    k, v = cache["kv"]
    assert k.shape == v.shape == (TINY.num_hidden_layers, 4, 2, 20, 4, 8)
    # every pass wrote its own entry of the prompt's positions
    written = np.abs(np.asarray(k, np.float32)[:, :, 0, :8]).max(
        axis=(2, 3, 4))
    assert (written > 0).all()
    assert not np.asarray(k, np.float32)[:, :, :, 8:].any()


def test_a_decode_counts_its_pass_steps_and_the_slots_it_attended(lm):
    """``pass_steps`` is steps x 4; ``kv_slots`` the real rows' valid
    slots a step, times passes and layers; a padding row counts none."""
    model, params, _ = lm
    ids = jnp.asarray(np.full((2, 16), 65, np.int32))
    _, _, stats = greedy_decode(
        make_apply_pair(model), params, ids, jnp.asarray([5, 1]),
        jax.random.PRNGKey(0), 6, 257, 0.0, 40,
        row_mask=jnp.asarray([True, False]), cache_stats=cache_stats,
        position_offset=jnp.zeros((2,), jnp.int32))
    assert int(stats["pass_steps"]) == 6 * 4
    # step i attends the prompt's 5 slots and the i + 1 generated ones
    assert int(stats["kv_slots"]) == sum(5 + i + 1 for i in range(6)) \
        * 4 * TINY.num_hidden_layers


# -- the serving path ---------------------------------------------------------

@pytest.fixture(scope="module")
def generator():
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    return PromptGenerator(configs.test_ouro_config())


def test_prompt_generator_serves_the_family_and_publishes_its_counters(
        generator):
    """One dispatch of three rows (batch bucket 4): ``ouro.pass_steps``
    is the program's steps x 4 passes, once a dispatch whatever its rows;
    ``ouro.kv_slots`` counts the three real rows' slots."""
    assert generator.family.name == "ouro"
    before = counters("ouro.")
    texts = generator.generate_batch(
        ["The quiet harbor at dawn", "A", "Clockwork birds over the walls"])
    assert len(texts) == 3 and all(isinstance(t, str) and t for t in texts)
    after = counters("ouro.")
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    steps = configs.test_ouro_config().sampler.max_new_tokens
    assert delta["ouro.pass_steps"] == steps * 4
    # the rows' prompts (24, 1, 30 tokens) in bucket 32 and their steps
    attended = sum(n + i + 1 for n in (24, 1, 30) for i in range(steps))
    assert delta["ouro.kv_slots"] == attended * 4 * TINY.num_hidden_layers


def test_token_flops_count_the_stack_once_a_pass(generator):
    """The stack, its final norm and exit gate four times, the head once,
    the embedding (a look-up) not at all."""
    m = TINY
    layer = 4 * 32 * 32 + 3 * 32 * 48 + 4 * 32
    stack = m.num_hidden_layers * layer + 32 + (32 + 1)
    want = 4 * stack + 32 * 300
    assert active_params(generator.params, generator.mcfg) == want
    assert generator._token_flops() == 2.0 * want


def test_a_dense_family_still_counts_every_parameter_once():
    """The family table's default for a one-pass LM is unchanged: GPT-2's
    token FLOPs are twice its parameters (the ouro family's are pinned
    above)."""
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    gen = PromptGenerator(configs.test_config())
    assert gen.family.name == "gpt2"
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(gen.params))
    assert gen._token_flops() == 2.0 * total


@pytest.mark.parametrize("change, match", [
    (dict(models=dict(lm_int8=True)), "lm_int8"),
    (dict(spec_decode=SpecDecodeConfig(mode="ngram")), "speculative"),
    (dict(weights_dir="/nonexistent/weights"), "converter"),
], ids=["lm_int8", "spec_decode", "weights_dir"])
def test_what_the_family_does_not_serve_is_refused(change, match):
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    cfg = configs.test_ouro_config()
    weights_dir = change.pop("weights_dir", None)
    if "models" in change:
        change["models"] = dataclasses.replace(cfg.models,
                                               **change["models"])
    with pytest.raises(ValueError, match=match):
        PromptGenerator(cfg.replace(**change), weights_dir)


@pytest.mark.parametrize("field, value", [
    ("early_exit_threshold", 0.9), ("num_key_value_heads", 2),
    ("tie_word_embeddings", True)])
def test_what_the_config_does_not_serve_is_refused(field, value):
    with pytest.raises(AssertionError):
        dataclasses.replace(TINY, **{field: value})


def test_the_decode_floor_reads_the_stack_once_a_pass():
    """At the published sizes: 4,933,287,936 B of stack a pass, the
    201,326,592 B head once a step, 8,192 B a slot attended; the bytes
    bind, at 819 GB/s: a four-row step's floor is 25.6 ms."""
    sz = dict(sizes_of(OuroConfig()))
    step = plain.decode_floor_s(sz, "TPU v5 lite", 4, 0)
    assert step == pytest.approx((4 * 4_933_287_936 + 201_326_592) / 819e9)
    slots = 4 * 160 * 48 * 4  # four rows of 160 slots, every layer, pass
    four_rows = plain.decode_floor_s(sz, "TPU v5 lite", 4, slots)
    assert four_rows == pytest.approx(step + slots * 8192 / 819e9)
    assert 25.0e-3 < four_rows < 26.5e-3
    assert ouro.zero_stats().keys() == {"pass_steps", "kv_slots"}
