"""LFM2-MoE-family prompt LM at the tiny size (a dense leading layer and
one period of full, conv, conv, conv; 8 experts, top-2): the program
against its plain reference (benchmarks/references/lfm2_moe.py) on seeded
weights, through the cache, under bucket padding and batch company; the
routing rule's parts one by one; PromptGenerator serves the family and
refuses what it does not serve.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import lfm2_moe as plain
from cassmantle_tpu import config as configs
from cassmantle_tpu.config import (
    Lfm2MoeConfig,
    SpecDecodeConfig,
    lfm2_game_config,
)
from cassmantle_tpu.models.lfm2_moe import (
    Lfm2MoeLM,
    active_params,
    cache_stats,
)
from cassmantle_tpu.models.moe import HeldExperts
from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair
from tests.test_qwen3_next import (
    counters,
    decode_through_cache,
    dispatch_counts,
    error,
    sizes_of,
)

TINY = Lfm2MoeConfig.tiny()
#: logits are of order 4. float32 differs from the reference by the order
#: of its sums alone (the cache changes no arithmetic: a window of two
#: inputs, k/v as they were written), so the worst logit counts. bfloat16
#: rounds every matmul's activations (weights are the same bits), and at
#: this size a rounding that swaps an expert at a near-tie of score + bias
#: moves a position's logits by order 1: the median position counts
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.15}
PARTS = ["gate_b", "gate_c", "tap", "qk_norm", "selection_bias"]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    """(model, params, sizes) in one storage dtype; the tree is cast as
    the serving path casts it."""
    cfg = dataclasses.replace(TINY, dtype=request.param)
    model = Lfm2MoeLM(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: a.astype(request.param), params)
    return model, params, sizes_of(cfg)


def reference_of_row(params, sizes, prompt, generated, bucket, **kw):
    ids = np.concatenate([prompt, generated])[None]
    positions = np.concatenate(
        [np.arange(len(prompt)), bucket + np.arange(len(generated))])[None]
    logits = jax.jit(lambda p, i, q: plain.lfm2_logits(
        p, i, q, sizes, **kw))(params, jnp.asarray(ids),
                               jnp.asarray(positions))
    return np.asarray(logits)[0, len(prompt) - 1:-1]


PROMPTS = [np.arange(5, 12), np.arange(40, 52), np.arange(90, 93)]
GENERATED = np.random.RandomState(0).randint(0, 256, (3, 6))


def test_full_forward_against_the_plain_reference(lm):
    model, params, sizes = lm
    ids = np.random.RandomState(1).randint(0, 256, (2, 20))
    positions = np.broadcast_to(np.arange(20), (2, 20))
    got = jax.jit(model.apply)(params, jnp.asarray(ids))
    want = jax.jit(lambda p, i, q: plain.lfm2_logits(p, i, q, sizes))(
        params, jnp.asarray(ids), jnp.asarray(positions))
    assert float(jnp.abs(want).max()) > 1.0
    assert error(got, want, sizes["dtype"]) < TOLERANCE[sizes["dtype"]]


def test_prefill_then_decode_through_the_cache_against_the_reference(lm):
    """Rows of different ``prompt_len`` share one bucket; each row's
    logits are the full forward's over its own tokens, the generated ones
    at positions ``bucket + i``."""
    model, params, sizes = lm
    got, _ = decode_through_cache(model, params, PROMPTS, GENERATED, 16)
    for r, prompt in enumerate(PROMPTS):
        want = reference_of_row(params, sizes, prompt, GENERATED[r], 16)
        assert error(got[r], want, sizes["dtype"]) \
            < TOLERANCE[sizes["dtype"]], r


@pytest.mark.parametrize("part", PARTS)
def test_the_reference_without_a_part_of_the_layer_disagrees(lm, part):
    """What the comparison has to be able to see: a gate, the
    convolution's oldest tap, the q/k norms or the selection bias taken
    out of the reference moves its logits far past the tolerance."""
    model, params, sizes = lm
    got, _ = decode_through_cache(model, params, PROMPTS[:2], GENERATED[:2],
                                  16)
    worst = max(
        error(got[r], reference_of_row(params, sizes, PROMPTS[r],
                                       GENERATED[r], 16, without=(part,)),
              sizes["dtype"]) for r in range(2))
    assert worst > 4 * TOLERANCE[sizes["dtype"]]


def test_a_row_does_not_depend_on_its_company(lm):
    """The same row alone, among other rows, and beside another row: same
    rows, same logits (the property ``decode_ids_batch`` documents)."""
    model, params, sizes = lm
    # other shapes, other summation orders; no expert changes hands
    tol = {"float32": 1e-4, "bfloat16": 0.04}[sizes["dtype"]]
    together, _ = decode_through_cache(model, params, PROMPTS, GENERATED, 16)
    for r, prompt in enumerate(PROMPTS):
        alone, _ = decode_through_cache(model, params, [prompt],
                                        GENERATED[r:r + 1], 16)
        assert np.abs(alone[0] - together[r]).max() < tol, r
    other = [PROMPTS[0], np.arange(200, 215)]
    swapped, _ = decode_through_cache(model, params, other, GENERATED[:2], 16)
    assert np.abs(swapped[0] - together[0]).max() < tol


def test_a_row_in_a_wider_buckets_program_decodes_at_its_own_positions(lm):
    """Rows of bucket 16 in the program of a batch whose widest row is of
    bucket 32: each row's logits are the reference's with its generated
    tokens at ``own bucket + i``, and its solo decode's in its own
    bucket's program (the masked slots between differ, nothing else)."""
    model, params, sizes = lm
    tol = {"float32": 1e-4, "bfloat16": 0.04}[sizes["dtype"]]
    wide = np.arange(150, 170)
    prompts, own = PROMPTS[:2] + [wide], [16, 16, 32]
    mixed, _ = decode_through_cache(model, params, prompts, GENERATED, 32,
                                    own=own)
    for r, prompt in enumerate(prompts):
        want = reference_of_row(params, sizes, prompt, GENERATED[r], own[r])
        assert error(mixed[r], want, sizes["dtype"]) \
            < TOLERANCE[sizes["dtype"]], r
        alone, _ = decode_through_cache(model, params, [prompt],
                                        GENERATED[r:r + 1], own[r])
        assert np.abs(alone[0] - mixed[r]).max() < tol, r
    # the slot's position in the row's place reads otherwise
    slots, _ = decode_through_cache(model, params, prompts, GENERATED, 32)
    assert np.abs(slots[0] - mixed[0]).max() > 10 * tol


def test_pads_change_no_window(lm):
    """A convolution layer's window after prefill is the row's own last
    two gated inputs at its ``prompt_len``: the same in a wider bucket
    and whatever the pad positions hold, and so are the logits."""
    model, params, _ = lm

    def windows(bucket, pad_id):
        ids = np.full((1, bucket), pad_id, np.int32)
        ids[0, :7] = PROMPTS[0]
        logits, cache = jax.jit(make_apply_pair(model)[0], static_argnums=3)(
            params, jnp.asarray(ids), jnp.asarray([7]), bucket + 4)
        return np.asarray(logits), [
            np.asarray(e) for kind, e in zip(TINY.layer_types,
                                             cache["layers"])
            if kind == "conv"]

    base_logits, base = windows(8, 258)
    for bucket, pad_id in [(8, 7), (32, 258), (32, 0)]:
        logits, got = windows(bucket, pad_id)
        np.testing.assert_allclose(logits, base_logits, atol=2e-5)
        for w0, w1 in zip(base, got):
            # another bucket is another shape and another order of sums
            np.testing.assert_allclose(w1, w0, atol=2e-5)
    assert len(base) == 4 and base[0].shape == (1, 2, 32)
    assert float(np.abs(base[0]).max()) > 0


def test_a_prompt_shorter_than_the_window_starts_from_zeros(lm):
    """One real token: the window is a zero and that token's gated input,
    and the first step's logits are the reference's over two tokens."""
    model, params, sizes = lm
    got, cache = decode_through_cache(model, params, [np.asarray([65])],
                                      GENERATED[:1, :2], 16)
    window = np.asarray(cache["layers"][0])
    want = reference_of_row(params, sizes, np.asarray([65]),
                            GENERATED[0, :2], 16)
    assert error(got[0], want, sizes["dtype"]) < TOLERANCE[sizes["dtype"]]
    assert window.shape == (1, 2, 32)


# -- the routing rule ---------------------------------------------------------

def sigmoid_layer(**kw):
    args = dict(num_experts=8, experts_held=8, first_expert=0, top_k=2,
                intermediate=16, scoring="sigmoid", selection_bias=True,
                norm_eps=1e-6, dtype=jnp.float32)
    return HeldExperts(**dict(args, **kw))


def reference_layer(params, x, without=(), **kw):
    fields = dict(num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=16, norm_topk_prob=True,
                  use_expert_bias=True, routed_scaling_factor=1.0,
                  experts_held=8, first_expert=0)
    d = plain.Dims(**{k: dict(fields, **kw).get(k) for k in
                      plain.Dims._fields})
    p = params["params"]
    weight = plain.routing(p, x, d, without)
    return np.asarray(plain.experts(p["gate_up"], p["down"], x, weight)), \
        np.asarray(weight)


@pytest.fixture(scope="module")
def sigmoid_routed():
    layer = sigmoid_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    params = layer.init(jax.random.PRNGKey(6), x, jnp.ones((24,), bool), True)
    return layer, params, x


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "walk"])
def test_a_bias_that_changes_the_chosen_set_leaves_the_weights_unbiased(
        sigmoid_routed, dense):
    """Expert 5's bias lifts it into every token's top-2 whatever its
    score: it is chosen, and enters the sum with its own sigmoid score
    over the two chosen scores' sum + 1e-6, not with score + bias."""
    layer, params, x = sigmoid_routed
    p = params["params"]
    bias = np.asarray(p["expert_bias"]).copy()
    assert np.abs(bias).max() <= 0.1 and np.abs(bias).max() > 0
    bias[5] = 3.0
    lifted = {"params": dict(p, expert_bias=jnp.asarray(bias))}
    real = jnp.ones((24,), bool)
    out, stats = layer.apply(lifted, x, real, dense)
    want, weight = reference_layer(lifted, x)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    assert int(stats["load"][5]) == 24 and (weight[:, 5] > 0).all()
    other = np.where(weight > 0, scores, 0.0).sum(-1)  # both chosen scores
    np.testing.assert_allclose(weight[:, 5], scores[:, 5] / (other + 1e-6),
                               rtol=1e-5)
    # the bias did change the choice: unbiased, expert 5 is not everyone's
    unbiased, _ = reference_layer(lifted, x, without=("selection_bias",))
    assert np.abs(unbiased - want).max() > 0.01


def test_the_sum_the_weights_are_divided_by_carries_its_1e6(sigmoid_routed):
    """Scores of order 1e-6 (router logits near -14): the two chosen
    scores' sum is of the size of the 1e-6 added to it, so leaving it out
    of the reference moves the weights by tens of percent; the program
    has it."""
    layer, params, x = sigmoid_routed
    p = params["params"]
    router = np.asarray(p["router"]) * 0.01
    router[0, :] = -14.0 / 3.0
    x = np.asarray(x).copy()
    x[:, 0] = 3.0
    small = {"params": dict(p, router=jnp.asarray(router))}
    out, _ = layer.apply(small, jnp.asarray(x), jnp.ones((24,), bool), False)
    want, weight = reference_layer(small, jnp.asarray(x))
    without, _ = reference_layer(small, jnp.asarray(x),
                                 without=("norm_eps",))
    assert 0.5 < weight.sum(-1).max() < 0.8
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert np.abs(without - want).max() > 20 * 2e-5


def test_the_scaling_factor_multiplies_the_weights_last(sigmoid_routed):
    _, params, x = sigmoid_routed
    real = jnp.ones((24,), bool)
    one, _ = sigmoid_layer().apply(params, x, real, True)
    scaled, _ = sigmoid_layer(scaling=2.5).apply(params, x, real, True)
    want, _ = reference_layer(params, x, routed_scaling_factor=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(one),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(scaled), want, atol=5e-5)


# -- the serving path ---------------------------------------------------------

@pytest.fixture(scope="module")
def generator():
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    return PromptGenerator(configs.test_lfm2_config())


def test_prompt_generator_serves_the_family_and_publishes_its_routing(
        generator):
    assert generator.family.name == "lfm2_moe"
    before = counters()
    texts = generator.generate_batch(
        ["The quiet harbor at dawn", "A",
         "Clockwork birds over the old city walls and far beyond"])
    assert len(texts) == 3 and all(isinstance(t, str) and t for t in texts)
    after = counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # real rows only: (24 + 1 + 54 prompt tokens + 3 rows x 8 new tokens)
    # x 4 expert layers x top-2: the dense leading layer routes nothing,
    # and with every expert held every assignment lands
    tokens = 24 + 1 + 54 + 3 * 8
    assert delta["moe.assignments"] == tokens * 4 * 2
    assert delta["moe.assignments_held"] == delta["moe.assignments"]
    assert 0 < delta["moe.experts_touched"] <= delta["moe.assignments_held"]
    # three rows of a step (of 8 experts, top-2) choose the same expert
    # somewhere in 8 steps x 4 layers: the walk read it once for them
    assert 0 < delta["moe.walk_reads_saved"] < delta["moe.assignments_held"]


def test_a_one_row_dispatch_saves_no_read(generator):
    """A lone row's experts are distinct in every step: the walk reads
    each once, as many reads as assignments that landed."""
    before = counters()
    generator.decode_ids_batch(["The quiet harbor at dawn"])
    after = counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    assert delta["moe.assignments_held"] > 0
    assert delta["moe.walk_reads_saved"] == 0


def test_batched_rows_decode_as_they_would_alone(generator,
                                                 decode_dispatches):
    """Rows of prompt buckets 32, 32 and 64 are ONE dispatch, the widest
    row's program, and each decodes the tokens of its solo decode in its
    own bucket's program."""
    texts = ["The quiet harbor at dawn", "Salt wind",
             "Clockwork birds over the old city walls"]
    together, _ = generator.decode_ids_batch(texts)
    assert decode_dispatches == [((4, 64), [-32, -32, 0, 0])]
    for i, text in enumerate(texts):
        alone, _ = generator.decode_ids_batch([text])
        np.testing.assert_array_equal(np.asarray(alone[0]),
                                      np.asarray(together[i]))


def test_a_decode_program_counts_its_expert_layers_by_path(generator):
    """Off the TPU a decode program's prefill takes the dense form and
    its step the loop, once for each of the tiny model's four expert
    layers (on the chip the served program counts 8 ``walk_kernel`` and
    8 ``dense``); the counters come back with the tokens."""
    before = dispatch_counts()
    greedy_decode.lower(
        make_apply_pair(generator.model), generator.params,
        jax.ShapeDtypeStruct((3, 24), jnp.int32),
        jax.ShapeDtypeStruct((3,), jnp.int32), jax.random.PRNGKey(0), 5,
        257, 0.0, 40, row_mask=jax.ShapeDtypeStruct((3,), jnp.bool_),
        cache_stats=cache_stats,
        position_offset=jax.ShapeDtypeStruct((3,), jnp.int32))
    after = dispatch_counts()
    assert {k: after[k] - before.get(k, 0) for k in after} == {
        "dense": 4, "walk_xla": 4, **{k: 0 for k in after
                                      if k not in ("dense", "walk_xla")}}
    ids = jnp.asarray(np.full((2, 32), 65, np.int32))
    tokens, _, stats = greedy_decode(
        make_apply_pair(generator.model), generator.params, ids,
        jnp.asarray([5, 1]), jax.random.PRNGKey(0), 4, 257, 0.0, 40,
        row_mask=jnp.asarray([True, False]), cache_stats=cache_stats,
        position_offset=jnp.zeros((2,), jnp.int32))
    assert tokens.shape == (2, 4)
    assert int(stats["assignments"]) == (5 + 4) * 4 * 2


def test_token_flops_count_the_parameters_a_token_touches(generator):
    """Everything but the experts a token is not routed to: the embedding
    counts, since tied it is the head's matrix."""
    tree = generator.params
    dense = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    experts = 4 * 8 * 3 * 32 * 16
    assert active_params(tree, generator.mcfg) == dense - experts * (
        1 - 2 / 8)
    assert generator._token_flops() == 2.0 * active_params(
        tree, generator.mcfg)


def test_the_cut_configuration_holds_what_the_issue_reckoned():
    cfg = lfm2_game_config()
    m = cfg.models.lfm2_moe
    tree = jax.eval_shape(Lfm2MoeLM(m).init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    held = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    assert round(held / 1e9, 3) == 5.178
    assert round(active_params(tree, m) / 1e9, 2) == 0.65
    assert cfg.sampler.consistency and cfg.sampler.num_steps == 4
    assert m.layer_types == ("conv",) + (
        "full_attention", "conv", "conv", "conv") * 2
    assert (m.num_dense_layers, m.experts_held, m.num_experts) == (1, 64, 64)
    # published layers 0 and 2-9
    published = Lfm2MoeConfig().layer_types
    assert m.layer_types == published[:1] + published[2:10]


@pytest.mark.parametrize("change, match", [
    (dict(models=dict(lm_int8=True)), "lm_int8"),
    (dict(models=dict(lm_w8a8=True)), "lm_w8a8"),
    (dict(spec_decode=SpecDecodeConfig(mode="ngram")), "speculative"),
    (dict(weights_dir="/nonexistent/weights"), "converter"),
], ids=["lm_int8", "lm_w8a8", "spec_decode", "weights_dir"])
def test_what_the_family_does_not_serve_is_refused(change, match):
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    cfg = configs.test_lfm2_config()
    weights_dir = change.pop("weights_dir", None)
    if "models" in change:
        change["models"] = dataclasses.replace(cfg.models,
                                               **change["models"])
    with pytest.raises(ValueError, match=match):
        PromptGenerator(cfg.replace(**change), weights_dir)
