"""Device time under names that are really in the program (ISSUE 26).

A ``jax.profiler.TraceAnnotation`` opened inside a jit-traced function
fires once, at trace time, and leaves nothing in the compiled program;
``jax.named_scope`` and a Pallas call's ``name=`` are op metadata, which
a device trace carries on every event. Each case lowers one served
program (or one kernel entry point) at the tiny test size and finds every
stage scope and kernel name as a component of some op's location, and the
program's module under its fixed name. Nothing compiles or runs.

The flash kernel is chosen by ``ops/attention.py`` asking the host which
backend it is on: the two sampler cases steer that onto the chip's branch
here, in the test, at an image size whose attention tiles (the kernel
itself then lowers in interpret mode, where its name is a scope of the
ops it expands into).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from cassmantle_tpu.config import test_config as tiny_config
from cassmantle_tpu.config import test_sdxl_config as tiny_sdxl_config

SAMPLER_SCOPES = {"clip_encode", "denoise_scan", "denoise_step",
                  "vae_decode", "flash_attention"}


def lower_sampler(pipe, image_size):
    """The pipeline's own sampler program, traced at ``image_size``: the
    pipeline is built at the tiny size (its weights do not depend on the
    image's) and reads its config when the program is traced, here."""
    pipe.cfg = pipe.cfg.replace(sampler=dataclasses.replace(
        pipe.cfg.sampler, image_size=image_size))
    ids = jnp.zeros((1, pipe.pad_len), jnp.int32)
    return pipe._sample.lower(pipe._params, ids, ids, jax.random.PRNGKey(0))


def sd15_sampler(monkeypatch):
    from cassmantle_tpu.ops import attention
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    # 256 px: 32x32 latents, 1024 tokens at the level that attends
    return lower_sampler(Text2ImagePipeline(tiny_config()), 256)


def sdxl_sampler(monkeypatch):
    from cassmantle_tpu.ops import attention
    from cassmantle_tpu.serving.sdxl import SDXLPipeline

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    # 512 px: the tiny SDXL UNet attends one level down, 32x32 there
    return lower_sampler(SDXLPipeline(tiny_sdxl_config()), 512)


def i2i_sampler(monkeypatch):
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    pipe = Text2ImagePipeline(tiny_config())
    pipe._ensure_encoder()
    size = pipe.cfg.sampler.image_size
    ids = jnp.zeros((1, pipe.pad_len), jnp.int32)
    return pipe._i2i_fn(2).lower(
        dict(pipe._params, vae_enc=pipe.enc_params), ids, ids,
        jnp.zeros((1, size, size, 3), jnp.float32), jax.random.PRNGKey(0))


def lm_decode(monkeypatch):
    from cassmantle_tpu.ops.decode import greedy_decode
    from cassmantle_tpu.serving.pipeline import PromptGenerator

    gen = PromptGenerator(tiny_config())
    return greedy_decode.lower(
        (gen._prefill, gen._step), gen.params,
        jnp.zeros((1, 32), jnp.int32), jnp.ones((1,), jnp.int32),
        jax.random.PRNGKey(0), 8, 255, 0.0, 40,
        position_offset=jnp.zeros((1,), jnp.int32))


def scorer_encode(monkeypatch):
    from cassmantle_tpu.ops.scorer import EmbeddingScorer

    scorer = EmbeddingScorer(tiny_config().models.minilm, table=None)
    ids = jnp.zeros((4, 16), jnp.int32)
    return scorer._encode.lower(scorer.params, ids, ids)


def fused_conv_kernel(monkeypatch):
    from cassmantle_tpu.ops.fused_conv import gn_silu_conv3x3

    x = jnp.zeros((1, 8, 8, 128), jnp.float32)
    affine = jnp.zeros((1, 128), jnp.float32)
    return jax.jit(lambda x, a, b, k, bias: gn_silu_conv3x3(
        x, a, b, k, bias, interpret=True)).lower(
            x, affine, affine, jnp.zeros((3, 3, 128, 128), jnp.float32),
            jnp.zeros((128,), jnp.float32))


def int8_matmul_kernel(monkeypatch):
    from cassmantle_tpu.ops.quant_matmul import int8_matmul

    return jax.jit(lambda x, w, rs, cs: int8_matmul(
        x, w, rs, cs, interpret=True)).lower(
            jnp.zeros((32, 128), jnp.int8), jnp.zeros((128, 128), jnp.int8),
            jnp.ones((32, 1), jnp.float32), jnp.ones((1, 128), jnp.float32))


def int8_conv_kernel(monkeypatch):
    from cassmantle_tpu.ops.quant_matmul import int8_conv3x3

    return jax.jit(lambda x, k, cs, bias: int8_conv3x3(
        x, k, cs, bias, interpret=True)).lower(
            jnp.zeros((1, 8, 8, 128), jnp.int8),
            jnp.zeros((3, 3, 128, 128), jnp.int8),
            jnp.ones((128,), jnp.float32), jnp.zeros((128,), jnp.float32))


# case -> (builder, module name or None for a bare kernel, scopes)
PROGRAMS = {
    "t2i_sample": (sd15_sampler, "jit_t2i_sample", SAMPLER_SCOPES),
    "sdxl_sample": (sdxl_sampler, "jit_sdxl_sample", SAMPLER_SCOPES),
    "i2i_sample": (i2i_sampler, "jit_i2i_sample",
                   {"clip_encode", "denoise_scan", "denoise_step",
                    "vae_decode"}),
    "lm_decode": (lm_decode, "jit_lm_decode",
                  {"lm_prefill", "lm_decode_step"}),
    "scorer_encode": (scorer_encode, "jit_scorer_encode",
                      {"scorer_encode"}),
    "fused_conv3x3": (fused_conv_kernel, None, {"fused_conv3x3"}),
    "int8_matmul": (int8_matmul_kernel, None, {"int8_matmul"}),
    "int8_conv3x3": (int8_conv_kernel, None, {"int8_conv3x3"}),
}


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_program_carries_its_name_and_every_scope(case, monkeypatch):
    build, module, scopes = PROGRAMS[case]
    text = build(monkeypatch).as_text(debug_info=True)
    if module is not None:
        assert re.search(rf"^module @{module}\b", text, re.M), \
            text[:200]
    components = set()
    for location in re.findall(r'loc\("([^"]+)"', text):
        components.update(location.split("/"))
    assert scopes <= components, sorted(scopes - components)
    # Flax names every module call below the stage scopes
    if case.endswith("_sample"):
        assert {"UNet", "VAEDecoder"} <= components


def test_no_host_annotation_inside_the_traced_code():
    """The in-jit annotations are gone for good: nothing under ops/ or
    models/ opens a TraceAnnotation, and the one door to it is
    ``utils.profiling.host_region`` (spans, block_timer, host_span)."""
    import os

    import cassmantle_tpu

    root = os.path.dirname(cassmantle_tpu.__file__)
    opened = []
    for folder, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                source = f.read()
            if re.search(r"TraceAnnotation\(|\bannotate\(", source):
                opened.append(os.path.relpath(path, root))
    assert opened == ["utils/profiling.py"]
