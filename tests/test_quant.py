"""Weights-only int8 quantization (ops/quant.py): reconstruction error,
tree transforms, jit/pytree compatibility, and the quantized LM serving
path end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.ops.quant import (
    QTensor,
    default_predicate,
    dequantize_tree,
    quantization_error,
    quantize_tensor,
    quantize_tree,
    quantized_apply,
    tree_nbytes,
)


def test_quantize_tensor_roundtrip_error():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    q = quantize_tensor(w)
    assert q.data.dtype == jnp.int8
    assert q.scale.shape == (1, 512)          # per-out-channel
    # int8 symmetric quantization of a gaussian: ~0.2-0.7% relative L2
    assert quantization_error(w) < 0.01


def test_quantize_exact_for_scaled_ints():
    # values that are exact multiples of absmax/127 reconstruct exactly
    base = jnp.asarray(np.arange(-127, 128, dtype=np.float32))[:, None]
    w = jnp.tile(base, (1, 4)) * 0.037
    q = quantize_tensor(w)
    np.testing.assert_allclose(np.asarray(q.dequantize(jnp.float32)),
                               np.asarray(w), rtol=1e-6)


def test_matmul_semantics_per_channel():
    # x @ dequant(W) must equal (x @ W8) * s: per-output-channel scales
    rng = jax.random.PRNGKey(1)
    w = jax.random.normal(rng, (64, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 64), jnp.float32)
    q = quantize_tensor(w)
    lhs = x @ q.dequantize(jnp.float32)
    rhs = (x @ q.data.astype(jnp.float32)) * q.scale[0][None, :]
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-5, atol=1e-5)


def test_zero_channel_safe():
    w = jnp.zeros((16, 8), jnp.float32)
    q = quantize_tensor(w)
    assert np.all(np.isfinite(np.asarray(q.scale)))
    np.testing.assert_array_equal(np.asarray(q.dequantize(jnp.float32)), 0)


def test_tree_transform_selects_kernels_only():
    tree = {
        "dense": {"kernel": jnp.ones((512, 512)), "bias": jnp.ones((512,))},
        "emb": {"embedding": jnp.ones((1000, 512))},
        "tiny": {"kernel": jnp.ones((4, 4))},
        "ln": {"scale": jnp.ones((512,))},
    }
    qt = quantize_tree(tree)
    assert isinstance(qt["dense"]["kernel"], QTensor)
    assert not isinstance(qt["emb"]["embedding"], QTensor)   # embeddings stay
    assert not isinstance(qt["tiny"]["kernel"], QTensor)     # too small
    assert not isinstance(qt["ln"]["scale"], QTensor)
    # footprint: the big kernel shrinks ~4x (fp32 -> int8 + scales);
    # untouched leaves (embedding here) keep their bytes
    assert tree_nbytes(qt["dense"]) < 0.3 * tree_nbytes(tree["dense"])
    assert tree_nbytes(qt["emb"]) == tree_nbytes(tree["emb"])
    back = dequantize_tree(qt, jnp.float32)
    assert back["dense"]["kernel"].dtype == jnp.float32
    assert back["dense"]["kernel"].shape == (512, 512)


def test_default_predicate_paths():
    big = jnp.ones((512, 512))
    assert default_predicate(("layer", "kernel"), big)
    assert not default_predicate(("layer", "bias"), jnp.ones((512,)))
    assert default_predicate((), big) is False  # empty path: no name


def test_qtensor_through_jit():
    # QTensor trees cross the jit boundary as pytrees; dequant inside
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 512), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 128), jnp.float32)
    tree = quantize_tree({"m": {"kernel": w}})

    @jax.jit
    def f(qt, x):
        d = dequantize_tree(qt, jnp.float32)
        return x @ d["m"]["kernel"]

    out = f(tree, x)
    ref = x @ quantize_tensor(w).dequantize(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_quantized_apply_wrapper():
    w = jax.random.normal(jax.random.PRNGKey(5), (300, 300), jnp.float32)
    tree = {"m": {"kernel": w}}

    def apply_fn(params, x):
        return x @ params["m"]["kernel"]

    x = jax.random.normal(jax.random.PRNGKey(6), (4, 300), jnp.float32)
    qout = quantized_apply(apply_fn, jnp.float32)(quantize_tree(tree), x)
    ref = apply_fn(tree, x)
    # w8a16 noise on a 300-dim contraction stays ~1%
    err = float(jnp.linalg.norm(qout - ref) / jnp.linalg.norm(ref))
    assert err < 0.02


def test_quantized_lm_decode_end_to_end(cfg, monkeypatch):
    """The serving path with lm_int8: quantized GPT-2 decodes sane tokens
    with int8 kernels in the tree. The test config's kernels sit below
    the production size threshold, so drop it for this test."""
    import dataclasses

    import cassmantle_tpu.ops.quant as quant
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    monkeypatch.setattr(
        quant, "default_predicate",
        lambda path, leaf: "kernel" in str(path[-1] if path else "")
        and getattr(leaf, "ndim", 0) >= 2)

    qcfg = cfg.replace(
        models=dataclasses.replace(cfg.models, lm_int8=True))
    gen_fp = PromptGenerator(cfg)
    gen_q = PromptGenerator(qcfg)

    toks_fp, len_fp = gen_fp.decode_ids("the storm rose", max_new_tokens=8)
    toks_q, len_q = gen_q.decode_ids("the storm rose", max_new_tokens=8)
    assert toks_q.shape == toks_fp.shape
    assert int(len_q[0]) >= 1
    # tiny random-init model: quantization noise may flip argmaxes, so
    # assert the mechanism (int8 storage) rather than token equality
    from cassmantle_tpu.ops.quant import QTensor as QT

    leaves = jax.tree_util.tree_leaves(
        gen_q.params, is_leaf=lambda x: isinstance(x, QT))
    assert any(isinstance(leaf, QT) for leaf in leaves)


def test_save_load_quantized_roundtrip(tmp_path):
    from cassmantle_tpu.ops.quant import load_quantized, save_quantized

    w = jax.random.normal(jax.random.PRNGKey(7), (300, 300))
    tree = quantize_tree({"a": {"kernel": w, "bias": jnp.ones((300,))}})
    path = str(tmp_path / "q.safetensors")
    save_quantized(tree, path)
    back = load_quantized(path)
    q0, q1 = tree["a"]["kernel"], back["a"]["kernel"]
    assert isinstance(q1, QTensor)
    np.testing.assert_array_equal(np.asarray(q0.data), np.asarray(q1.data))
    np.testing.assert_allclose(np.asarray(q0.scale), np.asarray(q1.scale))
    np.testing.assert_array_equal(np.asarray(back["a"]["bias"]),
                                  np.ones((300,)))


def test_prompt_generator_int8_checkpoint_boot(cfg, tmp_path, monkeypatch):
    """Quantize once, save, boot again from the int8 file: identical
    quantized params, no fp load."""
    import dataclasses

    import cassmantle_tpu.ops.quant as quant
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    monkeypatch.setattr(
        quant, "default_predicate",
        lambda path, leaf: "kernel" in str(path[-1] if path else "")
        and getattr(leaf, "ndim", 0) >= 2)
    qcfg = cfg.replace(models=dataclasses.replace(cfg.models, lm_int8=True))

    gen1 = PromptGenerator(qcfg, weights_dir=str(tmp_path))
    path = gen1.save_quantized()
    assert path.endswith("gpt2.int8.safetensors")

    gen2 = PromptGenerator(qcfg, weights_dir=str(tmp_path))
    l1 = jax.tree_util.tree_leaves(
        gen1.params, is_leaf=lambda x: isinstance(x, QTensor))
    l2 = jax.tree_util.tree_leaves(
        gen2.params, is_leaf=lambda x: isinstance(x, QTensor))
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        if isinstance(a, QTensor):
            assert isinstance(b, QTensor)
            np.testing.assert_array_equal(np.asarray(a.data),
                                          np.asarray(b.data))
    # and the loaded generator still decodes
    toks, n = gen2.decode_ids("the storm", max_new_tokens=4)
    assert toks.shape[1] == 4


def test_unet_int8_pipeline_generates():
    """unet_int8 config: the pipeline quantizes UNet kernels to int8
    QTensors (footprint shrinks), dequantizes inside the jit, and still
    generates images — including through img2img."""
    import dataclasses

    import numpy as np

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.ops.quant import QTensor, tree_nbytes
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    base = test_config()
    cfg = base.replace(models=dataclasses.replace(
        base.models, unet_int8=True))
    pipe = Text2ImagePipeline(cfg)
    q_leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        pipe.unet_params,
        is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(leaf, QTensor)]
    assert q_leaves, "expected quantized kernels in the int8 UNet tree"
    fp = Text2ImagePipeline(base)
    assert tree_nbytes(pipe.unet_params) < tree_nbytes(fp.unet_params)
    imgs = pipe.generate(["a tin lantern in fog"], seed=5)
    assert imgs.shape[-1] == 3 and imgs.dtype == np.uint8

    # img2img consumes the same quantized unet_apply via its own
    # denoiser construction — exercise that path too
    size = cfg.sampler.image_size
    src = np.zeros((1, size, size, 3), dtype=np.uint8)
    out = pipe.generate_img2img(src, ["a tin lantern"], strength=0.5,
                                seed=7)
    assert out.shape[-1] == 3 and out.dtype == np.uint8


def test_fp_arm_joining_int8_donor_reports_honest_weights_flag():
    """The fp-joins-int8-donor path re-loads its own UNet (dequant is
    lossy); the donor's loaded_real_weights flag must not vouch for a
    load the donor never did — if the checkpoint is gone by then, the
    fp arm is random-init and must report False (ADVICE r2)."""
    import dataclasses

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    base = test_config()
    int8_cfg = base.replace(models=dataclasses.replace(
        base.models, unet_int8=True))
    donor = Text2ImagePipeline(int8_cfg)
    donor.loaded_real_weights = True  # simulate a weights-provisioned donor
    fp = Text2ImagePipeline(base, share_params_with=donor)
    assert fp.loaded_real_weights is False

    # same-arch arm taking every tensor from the donor keeps its word
    clone = Text2ImagePipeline(int8_cfg, share_params_with=donor)
    assert clone.loaded_real_weights is True


def test_lm_int8_ab_tool_smoke(tmp_path):
    """tools/lm_int8_ab.py runs both arms end to end at tiny dims on
    CPU and emits one comparable JSON report (the on-hardware A/B the
    int8 claims are gated on uses the same code path)."""
    import json
    import subprocess
    import sys

    import os

    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "lm_int8_ab.py")
    out = tmp_path / "ab.json"
    proc = subprocess.run(
        [sys.executable, tool, "--tiny",
         "--platform", "cpu", "--tokens", "8", "--reps", "1",
         "--out", str(out)],
        capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(out.read_text())
    assert report["fp"]["tokens_per_sec"] > 0
    assert report["int8"]["tokens_per_sec"] > 0
    assert "speedup" in report and "param_shrink" in report
    # tiny dims: nothing meets the quantization size predicate, and the
    # report must SAY so rather than look like a measurement
    assert report["int8"]["quantized_leaves"] == 0
    assert report["tiny"] is True


def test_lm_int8_ab_quantizes_at_real_predicate(monkeypatch):
    """With the size predicate lowered to tiny dims, the int8 arm
    actually quantizes and the tree shrinks — the property the real
    GPT-2/Mistral run exercises at full size."""
    import dataclasses

    import cassmantle_tpu.ops.quant as quant

    orig = quant.default_predicate
    monkeypatch.setattr(
        quant, "default_predicate",
        lambda path, leaf: orig(path, leaf) or (
            "kernel" in str(path[-1]) and leaf.ndim >= 2
            and leaf.size >= 1024))

    from cassmantle_tpu.config import test_config
    from cassmantle_tpu.ops.quant import QTensor, tree_nbytes
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    base = test_config()
    fp_cfg = base
    q_cfg = base.replace(models=dataclasses.replace(
        base.models, lm_int8=True))
    fp = PromptGenerator(fp_cfg)
    q = PromptGenerator(q_cfg)
    q_leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        q.params, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(leaf, QTensor)]
    assert q_leaves
    assert tree_nbytes(q.params) < tree_nbytes(fp.params)
    text = q.generate("The storm", max_new_tokens=8)
    assert isinstance(text, str) and text
