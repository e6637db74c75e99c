"""Qwen3-Next-family prompt LM at the tiny size (one period, 8 experts,
top-2): the program against its plain reference
(benchmarks/references/qwen3_next.py) on seeded weights, through the
cache, under bucket padding and batch company; the expert layer drops
nothing and its shares add up to the whole layer; PromptGenerator serves
the family and refuses what it does not serve.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import qwen3_next as plain
from cassmantle_tpu import config as configs
from cassmantle_tpu.config import (
    Qwen3NextConfig,
    SpecDecodeConfig,
    qwen3next_game_config,
)
from cassmantle_tpu.models import moe
from cassmantle_tpu.models.moe import HeldExperts
from cassmantle_tpu.models.qwen3_next import (
    Qwen3NextLM,
    active_params,
    cache_stats,
)
from cassmantle_tpu.ops import moe_walk
from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair
from cassmantle_tpu.utils.logging import metrics

TINY = Qwen3NextConfig.tiny()
#: logits are of order 4. float32 differs by summation order alone: the
#: worst logit counts. bfloat16 rounds every matmul's activations (weights
#: are the same bits), and at this size (top-2 of 8 experts, 32 wide) a
#: rounding that swaps an expert at a near-tie moves a position's logits by
#: order 1, and every later position's through the recurrent state: the
#: median position counts, which a wrong layer moves as far as any other
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.15}


def error(got, want, dtype: str) -> float:
    worst = np.abs(np.asarray(got) - np.asarray(want)).max(axis=-1)
    return float(worst.max() if dtype == "float32" else np.median(worst))


def sizes_of(cfg) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    """(model, params, sizes) in one storage dtype; the tree is cast as
    the serving path casts it."""
    cfg = dataclasses.replace(TINY, dtype=request.param)
    model = Qwen3NextLM(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: a.astype(request.param), params)
    return model, params, sizes_of(cfg)


def decode_through_cache(model, params, prompts, generated, bucket,
                         own=None):
    """Logits (rows, n_gen, V) that predict each generated token: prefill
    of the right-padded bucket, then cached steps into slot ``bucket + i``
    at position ``own[row] + i`` (``own``: each row's own bucket where the
    program's is wider, as ``decode_ids_batch`` runs a mixed batch)."""
    rows, n_gen = len(prompts), generated.shape[1]
    own = np.full((rows, 1), bucket) if own is None \
        else np.asarray(own)[:, None]
    ids = np.full((rows, bucket), 258, np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for r, prompt in enumerate(prompts):
        ids[r, :len(prompt)] = prompt
    max_len = bucket + n_gen
    prefill, step = (jax.jit(f, static_argnums=3) if i == 0 else jax.jit(f)
                     for i, f in enumerate(make_apply_pair(model)))
    logits, cache = prefill(params, jnp.asarray(ids), jnp.asarray(lens),
                            max_len)
    out = [logits]
    positions = np.arange(max_len)[None, :]
    for i in range(n_gen - 1):
        valid = (positions < lens[:, None]) | (
            (positions >= bucket) & (positions <= bucket + i))
        logits, cache = step(params, jnp.asarray(generated[:, i]),
                             jnp.int32(bucket + i), cache, jnp.asarray(valid),
                             jnp.asarray(own + i))
        out.append(logits)
    return np.stack([np.asarray(x) for x in out], axis=1), cache


def reference_of_row(params, sizes, prompt, generated, bucket, **kw):
    ids = np.concatenate([prompt, generated])[None]
    positions = np.concatenate(
        [np.arange(len(prompt)), bucket + np.arange(len(generated))])[None]
    logits = jax.jit(lambda p, i, q: plain.qwen3next_logits(
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
    want = jax.jit(lambda p, i, q: plain.qwen3next_logits(p, i, q, sizes))(
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


@pytest.mark.parametrize("part", ["conv_window", "decay"])
def test_the_reference_without_a_part_of_the_layer_disagrees(lm, part):
    """What the comparison has to be able to see: the convolution's
    window or the decay taken out of the reference moves its logits far
    past the tolerance."""
    model, params, sizes = lm
    got, _ = decode_through_cache(model, params, PROMPTS[:1],
                                  GENERATED[:1], 16)

    def without(p, x, positions, *, d):
        return plain.layer(p, x, positions, d, False, **{part: False})

    want = reference_of_row(params, sizes, PROMPTS[0], GENERATED[0], 16,
                            linear_layer=without)
    assert error(got[0], want, sizes["dtype"]) \
        > 4 * TOLERANCE[sizes["dtype"]]


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


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_a_decode_steps_rows_reach_the_matrix_at_float32s_precision(rows):
    """``stored_dot``: the rows of a decode step are multiplied with a
    bfloat16 matrix at float32's precision, one row as a float32 product
    and more as two bfloat16 operands, the float32 rows' product to
    2**-16 either way and under ``jit`` too (a compiler that drops excess
    rounding must not drop the split); prefill rounds its rows to the
    stored type as written."""
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, 256))
    kernel = jax.random.normal(jax.random.PRNGKey(7), (256, 128)
                               ).astype(jnp.bfloat16)
    exact = np.asarray(jnp.dot(x, kernel.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    scale = np.abs(exact).max()
    step = np.asarray(jax.jit(moe.stored_dot, static_argnums=2)(
        x, kernel, True))
    rounded = np.asarray(jax.jit(moe.stored_dot, static_argnums=2)(
        x, kernel, False))
    assert step.shape == exact.shape == rounded.shape
    assert np.abs(rounded - exact).max() > 1e-3 * scale
    assert np.abs(step - exact).max() < 2e-5 * scale
    # a float32 matrix needs no split
    np.testing.assert_allclose(
        np.asarray(moe.stored_dot(x, kernel.astype(jnp.float32), True)),
        exact, atol=1e-4 * scale)


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


def test_pads_change_no_state(lm):
    """A linear layer's recurrent state and convolution window after
    prefill are the row's own at its ``prompt_len``: the same in a wider
    bucket and whatever the pad positions hold."""
    model, params, _ = lm

    def linear_states(bucket, pad_id):
        ids = np.full((1, bucket), pad_id, np.int32)
        ids[0, :7] = PROMPTS[0]
        logits, cache = jax.jit(make_apply_pair(model)[0], static_argnums=3)(
            params, jnp.asarray(ids), jnp.asarray([7]), bucket + 4)
        states = [e for i, e in enumerate(cache["layers"])
                  if not TINY.is_full_attention(i)]
        return np.asarray(logits), states

    base_logits, base = linear_states(8, 258)
    for bucket, pad_id in [(8, 7), (32, 258), (32, 0)]:
        logits, states = linear_states(bucket, pad_id)
        np.testing.assert_allclose(logits, base_logits, atol=2e-5)
        for (s0, w0), (s1, w1) in zip(base, states):
            np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                                       atol=1e-6)
    assert len(base) == 3 and float(np.abs(np.asarray(base[0][0])).max()) > 0


# -- the expert layer ---------------------------------------------------------

def expert_layer(**kw):
    args = dict(num_experts=8, experts_held=8, first_expert=0, top_k=2,
                intermediate=16, shared_intermediate=16, dtype=jnp.float32)
    return HeldExperts(**dict(args, **kw))


def reference_block(params, x, **kw):
    fields = dict(num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=16,
                  shared_expert_intermediate_size=16, norm_topk_prob=True,
                  experts_held=8, first_expert=0)
    d = plain.Dims(**{k: dict(fields, **kw).get(k) for k in
                      plain.Dims._fields})
    return np.asarray(plain.sparse_block(params["params"], x, d))


@pytest.fixture(scope="module")
def whole_layer():
    layer = expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    params = layer.init(jax.random.PRNGKey(6), x, jnp.ones((24,), bool), True)
    return layer, params, x


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "walk"])
def test_no_assignment_is_dropped_under_a_router_biased_to_one_expert(
        whole_layer, dense):
    """Every token's first choice is expert 3 (a Switch layer with a
    capacity would drop most of them): all are computed, and counted."""
    layer, params, x = whole_layer
    router = np.asarray(params["params"]["router"]).copy()
    x_biased = np.asarray(x).copy()
    x_biased[:, 0] = 3.0
    router[0, :] = 0.0
    router[0, 3] = 50.0
    biased = {"params": dict(params["params"], router=jnp.asarray(router))}
    real = jnp.ones((24,), bool)
    out, stats = layer.apply(biased, jnp.asarray(x_biased), real, dense)
    want = reference_block(biased, jnp.asarray(x_biased))
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert int(stats["load"][3]) == 24
    assert int(stats["assignments"]) == int(stats["assignments_held"]) == 48
    assert int(stats["load"].sum()) == 48


def test_the_walk_and_the_dense_form_agree_and_padding_is_not_counted(
        whole_layer):
    layer, params, x = whole_layer
    real = jnp.arange(24) < 20
    dense, stats_d = layer.apply(params, x, real, True)
    walk, stats_w = layer.apply(params, x, real, False)
    np.testing.assert_allclose(np.asarray(walk)[:20], np.asarray(dense)[:20],
                               atol=2e-5)
    for name in set(stats_d) - {"walk_reads_saved"}:
        np.testing.assert_array_equal(np.asarray(stats_d[name]),
                                      np.asarray(stats_w[name]))
    assert int(stats_d["assignments"]) == 40
    assert int(stats_d["experts_touched"]) == int(
        (np.asarray(stats_d["load"]) > 0).sum())
    # the walk reads each touched expert once for all 20 rows; the dense
    # form reads every held expert and saves nothing
    assert int(stats_d["walk_reads_saved"]) == 0
    assert int(stats_w["walk_reads_saved"]) == 40 - int(
        stats_w["experts_touched"]) > 0


SIGMOID_RULE = dict(scoring="sigmoid", selection_bias=True, norm_eps=1e-6,
                    shared_intermediate=0)


def sigmoid_reference_block(params, x, **kw):
    """The plain reference of the other routing rule
    (benchmarks/references/lfm2_moe.py): sigmoid scores, the choice on
    score + bias, the unbiased scores over their sum + 1e-6."""
    from benchmarks.references import lfm2_moe as other

    fields = dict(num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=16, norm_topk_prob=True,
                  use_expert_bias=True, routed_scaling_factor=1.0,
                  experts_held=8, first_expert=0)
    d = other.Dims(**{k: dict(fields, **kw).get(k) for k in
                      other.Dims._fields})
    p = params["params"]
    weight = other.routing(p, x, d)[
        :, d.first_expert:d.first_expert + d.experts_held]
    return np.asarray(other.experts(p["gate_up"], p["down"], x, weight))


@pytest.mark.parametrize("rule", ["softmax_and_a_shared_expert",
                                  "sigmoid_and_a_selection_bias"])
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "walk"])
def test_the_four_shares_add_up_to_the_uncut_layer(whole_layer, dense, rule):
    """The share test, under each routing rule: four chips hold two
    experts each; their parts, with the shared expert (which every chip
    computes alike) counted once, add up to what the plain reference
    gives for the whole layer."""
    _, params, x = whole_layer
    rule_args, reference = {}, reference_block
    if rule == "sigmoid_and_a_selection_bias":
        rule_args, reference = SIGMOID_RULE, sigmoid_reference_block
        params = expert_layer(**rule_args).init(
            jax.random.PRNGKey(6), x, jnp.ones((24,), bool), True)
    p = params["params"]
    real = jnp.ones((24,), bool)
    want = reference(params, x)
    without_shared = reference(
        {"params": dict(p, gate_up=p["gate_up"][:0], down=p["down"][:0])},
        x, experts_held=0)
    total, held = np.zeros_like(want), 0
    for first in (0, 2, 4, 6):
        share = {"params": dict(p, gate_up=p["gate_up"][first:first + 2],
                                down=p["down"][first:first + 2])}
        part, stats = expert_layer(
            experts_held=2, first_expert=first, **rule_args).apply(
                share, x, real, dense)
        # the reference, given the same share, gives the same part
        np.testing.assert_allclose(
            np.asarray(part), reference(
                share, x, experts_held=2, first_expert=first), atol=2e-5)
        total += np.asarray(part) - without_shared
        held += int(stats["assignments_held"])
        assert int(stats["assignments"]) == 48
    np.testing.assert_allclose(total + without_shared, want, atol=5e-5)
    assert held == 48
    assert (np.abs(without_shared).max() > 0.01) == (
        rule == "softmax_and_a_shared_expert")


# -- the walk as one kernel (ops/moe_walk.py), interpreted -------------------

def routed_layer(dtype=jnp.float32, **kw):
    """Lane-aligned widths, top-10 of 32 experts of which 8 are held
    (ids 8..15): a row's ten assignments land here 2.5 times on average,
    as in the served cut."""
    args = dict(num_experts=32, experts_held=8, first_expert=8, top_k=10,
                intermediate=256, dtype=dtype)
    return HeldExperts(**dict(args, **kw))


#: the two served width sets (D 2048 with F 512 and F 1536) a quarter as
#: wide, each under a plan whose rings are shorter than an expert's
#: pieces (``gate_up`` 4 pieces through 3 slots, ``down`` 2 or 6 through
#: 2: more than one turn of a ring an expert, and slots that change hands
#: between assignments), and the narrower under the plan its widths give
#: (one piece a matrix, a ring that holds several experts)
KERNEL_CASES = {
    "f256_ring_of_pieces": (256, moe_walk.WalkPlan(128, 3, 128, 2)),
    "f768_ring_of_pieces": (768, moe_walk.WalkPlan(128, 3, 128, 2)),
    "f256_plan_of_the_widths": (256, None),
}


def dispatch_counts():
    return {labels[0][1]: value for name, labels, value
            in metrics.dump_state()["counters"] if name == "moe.dispatch"}


@pytest.fixture(scope="module", params=[
    (case, dtype) for case in KERNEL_CASES
    for dtype in ("float32", "bfloat16")], ids="-".join)
def routed(request):
    """(layer, params in the stored type, x (4, 512)); the case's plan
    is what ``as_on_the_chip`` hands the kernel."""
    case, dtype = request.param
    width, plan = KERNEL_CASES[case]
    layer = routed_layer(jnp.dtype(dtype), intermediate=width)
    x = jax.random.normal(jax.random.PRNGKey(13), (4, 512))
    params = layer.init(jax.random.PRNGKey(12), x, jnp.ones((4,), bool), True)
    PLANS[width] = plan
    return layer, jax.tree_util.tree_map(
        lambda a: a.astype(dtype), params), x


PLANS: dict = {}  # expert width -> the plan of the fixture's case


def as_on_the_chip(patch, layer):
    """The rule sees a TPU (the kernel itself still sees none and
    interprets), and the kernel takes the layer's test plan."""
    patch.setattr(moe, "on_tpu", lambda: True)
    patch.setattr(moe, "moe_walk", functools.partial(
        moe_walk.moe_walk, plan=PLANS[layer.intermediate]))


def three_forms(layer, params, x, real, monkeypatch):
    """{form: (out, stats)}: the dense form, the walk as XLA runs it and
    the walk as the kernel."""
    before = dispatch_counts()
    forms = {"dense": layer.apply(params, x, real, True),
             "walk_xla": layer.apply(params, x, real, False)}
    with monkeypatch.context() as patch:
        as_on_the_chip(patch, layer)
        forms["walk_kernel"] = layer.apply(params, x, real, False)
    after = dispatch_counts()
    assert {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)} == {
                "dense": 1, "walk_xla": 1, "walk_kernel": 1}
    return forms


def biased_router(params, x, experts):
    """Every row's first ``len(experts)`` choices are ``experts``, in that
    order (input 0 raised, the router's row 0 set)."""
    router = np.zeros_like(np.asarray(params["params"]["router"],
                                      np.float32))
    router[1:] = np.asarray(params["params"]["router"], np.float32)[1:]
    router[0, list(experts)] = 50.0 - np.arange(len(experts))
    x = np.asarray(x).copy()
    x[:, 0] = 3.0
    dtype = params["params"]["router"].dtype
    return ({"params": dict(params["params"],
                            router=jnp.asarray(router, dtype))},
            jnp.asarray(x))


#: what the two walks may differ by: float32 differs by the order of a
#: sum; in bfloat16 the loop rounds ``h`` to the stored type before
#: ``down`` where its compiler keeps the rounding (the kernel carries
#: ``h`` whole, as two operands). The dense form rounds ``h`` with the
#: routing weight already on it
WALK_TOLERANCE = {"float32": 2e-5, "bfloat16": 4e-3}
DENSE_TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


def tolerance(layer, form="walk_kernel"):
    table = DENSE_TOLERANCE if form == "dense" else WALK_TOLERANCE
    return table[jnp.dtype(layer.dtype).name]


def assert_forms_agree(layer, forms, rows=slice(None)):
    """Every form's ``rows`` against the loop's, and its ``stats`` equal
    to the loop's; but the reads the walk saved, which are the landed
    assignments less the experts touched in a walk and none in the
    dense form."""
    want, stats = forms["walk_xla"]
    for form, (out, form_stats) in forms.items():
        np.testing.assert_allclose(
            np.asarray(out)[rows], np.asarray(want)[rows],
            atol=tolerance(layer, form), err_msg=form)
        for name in set(stats) - {"walk_reads_saved"}:
            np.testing.assert_array_equal(np.asarray(form_stats[name]),
                                          np.asarray(stats[name]), form)
        saved = 0 if form == "dense" else (int(stats["assignments_held"])
                                           - int(stats["experts_touched"]))
        assert int(form_stats["walk_reads_saved"]) == saved, form


@pytest.mark.parametrize("rows", [1, 2, 4],
                         ids=["10_slots", "20_slots", "40_slots"])
def test_the_walk_kernel_agrees_with_the_loop_and_the_dense_form(
        routed, rows, monkeypatch):
    layer, params, x = routed
    forms = three_forms(layer, params, x[:rows], jnp.ones((rows,), bool),
                        monkeypatch)
    want, stats = forms["walk_xla"]
    assert 0 < int(stats["assignments_held"]) < rows * 10
    assert float(jnp.abs(want).max()) > 0.05
    assert_forms_agree(layer, forms)


@pytest.mark.parametrize("case", ["nothing_lands", "every_row_on_one_expert",
                                  "every_assignment_lands"])
def test_the_walk_kernel_at_the_ends_of_the_routing(routed, case,
                                                    monkeypatch):
    """No assignment lands (zeros, nothing counted, no loop trip); every
    row's first choice is held expert 11 (the same matrices copied trip
    after trip into alternating slots); all eight of every row land (as
    many trips as slots)."""
    layer, params, x = routed
    experts = {"nothing_lands": range(16, 26),
               "every_row_on_one_expert": [11] + list(range(16, 25)),
               "every_assignment_lands": [8, 9, 10, 11, 12, 13, 14, 15]}[case]
    if case == "every_assignment_lands":
        layer = routed_layer(layer.dtype, top_k=8,
                             intermediate=layer.intermediate)
    params, x = biased_router(params, x, experts)
    forms = three_forms(layer, params, x, jnp.ones((4,), bool), monkeypatch)
    stats = forms["walk_xla"][1]
    held = {"nothing_lands": 0, "every_row_on_one_expert": 4,
            "every_assignment_lands": 32}[case]
    assert int(stats["assignments_held"]) == held
    if case == "every_row_on_one_expert":
        assert int(stats["load"][11 - 8]) == 4
    assert_forms_agree(layer, forms)
    if not held:
        assert not any(np.asarray(out).any() for out, _ in forms.values())


@pytest.mark.parametrize("real", [[True, False, True, False],
                                  [False, False, False, True],
                                  [False, False, False, False]],
                         ids=["two_real", "last_real", "none_real"])
def test_the_walk_kernel_neither_computes_nor_counts_padding_rows(
        routed, real, monkeypatch):
    layer, params, x = routed
    real = jnp.asarray(real)
    forms = three_forms(layer, params, x, real, monkeypatch)
    assert int(forms["walk_xla"][1]["assignments"]) == 10 * int(real.sum())
    for form in ("walk_xla", "walk_kernel"):
        assert not np.asarray(forms[form][0])[~np.asarray(real)].any(), form
    # the dense form computes padding rows too: the real ones count
    assert_forms_agree(layer, forms, np.asarray(real))


@pytest.mark.parametrize("row", [0, 1, 2, 3])
def test_a_row_alone_in_the_walk_kernel_equals_the_row_in_company(
        routed, row, monkeypatch):
    """A row's sum is taken in its own order over its own assignments,
    whatever rows share the call (row 2 is one none of whose ten
    assignments land: zeros, alone and in company). The interpreter's
    matrix product over one row and over four blocks its sums otherwise,
    hence not to the bit here."""
    layer, params, x = routed
    as_on_the_chip(monkeypatch, layer)
    together, _ = layer.apply(params, x, jnp.ones((4,), bool), False)
    alone, stats = layer.apply(params, x[row:row + 1], jnp.ones((1,), bool),
                               False)
    assert bool(jnp.any(alone != 0)) == (int(stats["assignments_held"]) > 0)
    assert (int(stats["assignments_held"]) == 0) == (row == 2)
    np.testing.assert_allclose(
        np.asarray(alone[0]), np.asarray(together[row]),
        atol={"float32": 1e-6, "bfloat16": 4e-3}[jnp.dtype(layer.dtype).name])


def routed_by_row(params, x, choices):
    """Row r's ten choices are ``choices[r]``, in that order: the rows of
    one list raise one input column (the others' are 0) and that row of
    the router favours the list."""
    lists = sorted(set(map(tuple, choices)))
    router = np.asarray(params["params"]["router"], np.float32).copy()
    x = np.asarray(x).copy()
    router[:len(lists)] = 0.0
    x[:, :len(lists)] = 0.0
    for column, experts in enumerate(lists):
        router[column, list(experts)] = 50.0 - np.arange(len(experts))
    for row, experts in enumerate(choices):
        x[row, lists.index(tuple(experts))] = 3.0
    dtype = params["params"]["router"].dtype
    return ({"params": dict(params["params"],
                            router=jnp.asarray(router, dtype))},
            jnp.asarray(x))


ABSENT = list(range(16, 32))
#: {case: (each row's ten choices, assignments that land, experts they
#: touch)}; the layer holds 8..15. Rows list their held experts out of id
#: order, so a row adds them otherwise than it chose them
SHARING = {
    "all_four_rows_share": ([[11, 8, 10, 9] + ABSENT[:6]] * 4, 16, 4),
    "two_of_four_rows_share": ([[14, 9, 12] + ABSENT[:7]] * 2
                               + [[10, 8] + ABSENT[7:15],
                                  [15, 11, 13] + ABSENT[8:15]], 11, 8),
    "no_two_rows_share": ([[15, 8] + ABSENT[:8], [9, 14] + ABSENT[:8],
                           [13, 10] + ABSENT[:8], [11, 12] + ABSENT[:8]],
                          8, 8),
}


@pytest.mark.parametrize("sharing", list(SHARING))
def test_rows_that_chose_the_same_expert_share_one_read_of_it(
        routed, sharing, monkeypatch):
    """Each walk's loop runs once for every expert some row chose, in
    ascending id, whatever number of rows chose it (``experts_touched``
    trips, not ``assignments_held``); each row's column of weights is its
    routing weight or 0; the kernel, the loop and the dense form agree,
    and a row's output alone is its output in this company."""
    layer, params, x = routed
    choices, held, touched = SHARING[sharing]
    params, x = routed_by_row(params, x, choices)
    walks = []
    loop = HeldExperts._walk

    def loop_seen(self, xb, gate_up, down, experts, combine, count):
        walks.append(("walk_xla", experts, combine, count))
        return loop(self, xb, gate_up, down, experts, combine, count)

    def kernel_seen(xb, gate_up, down, experts, combine, count, **kw):
        walks.append(("walk_kernel", experts, combine, count))
        return moe_walk.moe_walk(xb, gate_up, down, experts, combine, count,
                                 plan=PLANS[layer.intermediate], **kw)

    monkeypatch.setattr(HeldExperts, "_walk", loop_seen)
    forms = three_forms(layer, params, x, jnp.ones((4,), bool), monkeypatch)
    stats = forms["walk_xla"][1]
    assert int(stats["assignments_held"]) == held
    assert int(stats["experts_touched"]) == touched
    assert int(stats["walk_reads_saved"]) == held - touched
    assert_forms_agree(layer, forms)
    with monkeypatch.context() as patch:
        as_on_the_chip(patch, layer)
        patch.setattr(moe, "moe_walk", kernel_seen)
        together, _ = layer.apply(params, x, jnp.ones((4,), bool), False)
        alone = [layer.apply(params, x[r:r + 1], jnp.ones((1,), bool),
                             False)[0][0] for r in range(4)]
    chosen = sorted({e - 8 for row in choices for e in row if 8 <= e < 16})
    assert [w[0] for w in walks] == ["walk_xla"] + ["walk_kernel"] * 5
    for form, experts, combine, count in walks[:2]:
        assert int(count) == touched, form
        np.testing.assert_array_equal(np.asarray(experts)[:touched], chosen)
        row_chose = np.zeros((4, 8), bool)
        for row, experts_of_row in enumerate(choices):
            row_chose[row, [e - 8 for e in experts_of_row if 8 <= e < 16]] = 1
        assert ((np.asarray(combine) > 0) == row_chose).all(), form
    np.testing.assert_allclose(
        np.asarray(together), forms["walk_kernel"][0], rtol=0, atol=0)
    # outputs of order 1 (the router gives a row's first choice 0.63 of
    # its weight): the interpreter's product over one row and over four
    # blocks its float32 sums otherwise, 1.5e-6 apart at most
    np.testing.assert_allclose(
        np.stack(alone), np.asarray(together),
        atol={"float32": 4e-6, "bfloat16": 4e-3}[jnp.dtype(layer.dtype).name])


def test_widths_the_kernel_does_not_tile_keep_the_loop(whole_layer,
                                                       monkeypatch):
    """The rule reads the platform, ``dense`` and the widths: a layer 32
    wide with experts 16 wide walks as XLA runs it on a TPU too."""
    layer, params, x = whole_layer
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    before = dispatch_counts()
    layer.apply(params, x, jnp.ones((24,), bool), False)
    after = dispatch_counts()
    assert after["walk_xla"] - before.get("walk_xla", 0) == 1
    assert after.get("walk_kernel", 0) == before.get("walk_kernel", 0)


# -- the serving path ---------------------------------------------------------

@pytest.fixture(scope="module")
def generator():
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    return PromptGenerator(configs.test_qwen3next_config())


def counters(prefix="moe."):
    from cassmantle_tpu.utils.logging import metrics

    return {name: value for name, _labels, value
            in metrics.dump_state()["counters"] if name.startswith(prefix)}


def test_prompt_generator_serves_the_family_and_publishes_its_routing(
        generator):
    before = counters()
    texts = generator.generate_batch(
        ["The quiet harbor at dawn", "A",
         "Clockwork birds over the old city walls and far beyond"])
    assert len(texts) == 3 and all(isinstance(t, str) and t for t in texts)
    after = counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # real rows only: (24 + 1 + 54 prompt tokens + 3 rows x 8 new tokens)
    # x 4 layers x top-2; the batch bucket's padding rows are not counted
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


def test_greedy_decode_hands_back_the_cache_counters(generator):
    model, params = generator.model, generator.params
    ids = jnp.asarray(np.full((2, 32), 65, np.int32))
    lens = jnp.asarray([5, 1])
    tokens, _, stats = greedy_decode(
        make_apply_pair(model), params, ids, lens, jax.random.PRNGKey(0), 4,
        257, 0.0, 40, row_mask=jnp.asarray([True, False]),
        cache_stats=cache_stats, position_offset=jnp.zeros((2,), jnp.int32))
    assert tokens.shape == (2, 4)
    assert int(stats["assignments"]) == (5 + 4) * 4 * 2


def test_a_decode_program_counts_its_expert_layers_by_path(generator):
    """``moe.dispatch{path}`` once a site a trace: off the TPU a decode
    program's prefill takes the dense form and its step the loop, once
    for each of the tiny model's four layers (on the chip the served
    program counts 8 ``walk_kernel`` and 8 ``dense``)."""
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


def test_token_flops_count_the_parameters_a_token_touches(generator):
    tree = generator.params
    dense = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    active = active_params(tree, generator.mcfg)
    experts = 4 * 8 * 3 * 32 * 16
    embedding = 300 * 32
    assert active == dense - embedding - experts * (1 - 2 / 8)
    assert generator._token_flops() == 2.0 * active


def test_the_cut_configuration_holds_what_the_issue_reckoned():
    cfg = qwen3next_game_config()
    m = cfg.models.qwen3_next
    tree = jax.eval_shape(Qwen3NextLM(m).init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    held = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    assert round(held / 1e9, 3) == 3.667
    assert round(active_params(tree, m) / 1e9, 2) == 0.43
    assert cfg.sampler.consistency and cfg.sampler.num_steps == 4
    assert [m.is_full_attention(i) for i in range(8)] == [
        False, False, False, True] * 2


@pytest.mark.parametrize("change, match", [
    (dict(models=dict(lm_int8=True)), "lm_int8"),
    (dict(models=dict(lm_w8a8=True)), "lm_w8a8"),
    (dict(spec_decode=SpecDecodeConfig(mode="ngram")), "speculative"),
    (dict(weights_dir="/nonexistent/weights"), "converter"),
], ids=["lm_int8", "lm_w8a8", "spec_decode", "weights_dir"])
def test_what_the_family_does_not_serve_is_refused(change, match):
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    cfg = configs.test_qwen3next_config()
    weights_dir = change.pop("weights_dir", None)
    if "models" in change:
        change["models"] = dataclasses.replace(cfg.models,
                                               **change["models"])
    with pytest.raises(ValueError, match=match):
        PromptGenerator(cfg.replace(**change), weights_dir)
