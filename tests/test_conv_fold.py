"""The 3x3 convolution with one spatial axis in the batch (ISSUE 30).

``models/layers.py::conv3x3_same`` hands a stride-1 3x3 site to the
compiler either as ``nn.Conv`` does or, on the TPU under 8 batch rows,
as a 1-D convolution with H or W folded into the batch. Here, on the CPU:
the folded form computes ``lax.conv_general_dilated``'s numbers, the rule
is the table it is documented as, the param tree does not depend on the
form, and every site counts itself once a trace under
``conv.dispatch{form=...}``. What the chip's compiler makes of each form
is ``tests/test_tpu_compile.py``.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassmantle_tpu.config import FrameworkConfig, sdxl_config
from cassmantle_tpu.models import layers
from cassmantle_tpu.models.unet import ResBlock, UNet
from cassmantle_tpu.utils.logging import metrics

FORMS = ("rows_folded", "xla_2d")


def reference(x, kernel):
    return jax.lax.conv_general_dilated(
        x, kernel, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


# (B, H, W, C, F): square and not, odd widths, channels that are no
# multiple of 128 (the 320 and 960 of level 0), batches 1, 2 and 4
SHAPES = [(2, 16, 16, 32, 48), (1, 16, 24, 320, 64), (4, 12, 8, 16, 16),
          (2, 7, 9, 24, 8), (1, 5, 16, 960, 32), (2, 3, 1, 8, 8)]


@pytest.mark.parametrize("axis", [1, 2], ids=["fold_h", "fold_w"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_folded_convolution_is_the_2d_convolution(shape, axis):
    b, h, w, c, f = shape
    kx, kk = jax.random.split(jax.random.PRNGKey(h * w + c))
    x = jax.random.normal(kx, (b, h, w, c), jnp.float32)
    kernel = jax.random.normal(kk, (3, 3, c, f), jnp.float32) / (9 * c) ** .5
    with jax.default_matmul_precision("highest"):
        y = layers.conv3x3_rows_folded(x, kernel, axis=axis)
    assert y.shape == (b, h, w, f) and y.dtype == x.dtype
    np.testing.assert_allclose(y, reference(x, kernel), atol=1e-5, rtol=0)


# the rule as a table: (on the TPU, B, H, W) -> form
RULE = [
    ((False, 2, 16, 16), "xla_2d"),      # off the TPU: nn.Conv, always
    ((False, 2, 8, 8), "xla_2d"),
    ((True, 8, 16, 16), "xla_2d"),       # 8 rows: the direct form as it is
    ((True, 8, 8, 8), "xla_2d"),
    ((True, 16, 32, 32), "xla_2d"),
    ((True, 2, 64, 64), "xla_2d"),       # a ninth of padding or less
    ((True, 2, 128, 128), "xla_2d"),
    ((True, 1, 512, 512), "xla_2d"),
    ((True, 2, 4, 4), "xla_2d"),         # never timed: left alone
    ((True, 2, 8, 8), "rows_folded"),    # B·H rows to the sublanes
    ((True, 1, 8, 8), "rows_folded"),
    ((True, 2, 16, 16), "rows_folded"),  # and no W/8 + 1
    ((True, 1, 16, 16), "rows_folded"),
    ((True, 4, 16, 16), "rows_folded"),
    ((True, 2, 32, 32), "rows_folded"),
    ((True, 6, 32, 32), "rows_folded"),
    ((True, 2, 16, 24), "rows_folded"),
    ((True, 2, 2, 16), "xla_2d"),        # B·H under 8 rows: nothing gained
]


@pytest.mark.parametrize("call,form", RULE, ids=lambda v: str(v))
def test_the_rule_reads_platform_batch_and_size_only(call, form):
    assert layers.conv3x3_form(*call) == form


def form_counts():
    counters = metrics.dump_state()["counters"]
    return {form: sum(value for name, labels, value in counters
                      if name == "conv.dispatch"
                      and dict(labels).get("form") == form)
            for form in FORMS}


def unet_shapes(cfg, latent_hw, batch=2):
    args = [jnp.zeros((batch, latent_hw, latent_hw, 4), jnp.bfloat16),
            jnp.zeros((batch,), jnp.int32),
            jnp.zeros((batch, 77, cfg.context_dim), jnp.bfloat16)]
    if cfg.addition_embed_dim:
        args.append(jnp.zeros((batch, cfg.addition_embed_dim), jnp.bfloat16))
    unet = UNet(cfg)
    return unet, args, jax.eval_shape(unet.init, jax.random.PRNGKey(0), *args)


def sites_by_level(cfg, latent_hw):
    """{spatial size: stride-1 3x3 sites that reach ``conv3x3_same``}."""
    levels = len(cfg.channel_mults)
    sites = {}
    for lvl in range(levels):
        hw = latent_hw >> lvl
        blocks = 2 * cfg.blocks_per_level + 1 + (2 if lvl == levels - 1
                                                 else 0)
        sites[hw] = sites.get(hw, 0) + 2 * blocks
        if lvl:  # up_{lvl}_upsample convolves at the level above's size
            sites[hw * 2] = sites.get(hw * 2, 0) + 1
    return sites


CENSUS = {
    "sd15_512": (lambda: FrameworkConfig().models.unet, 64, 2, 47),
    "sd15_512_four_images": (lambda: FrameworkConfig().models.unet, 64, 8, 47),
    "sdxl_1024": (lambda: sdxl_config().models.unet, 128, 2, 36),
}


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
@pytest.mark.parametrize("case", list(CENSUS))
def test_every_site_counts_itself_once_a_trace(case, tpu, monkeypatch):
    make, latent_hw, batch, total = CENSUS[case]
    cfg = make()
    monkeypatch.setattr(layers, "on_tpu", lambda: tpu)
    unet, args, params = unet_shapes(cfg, latent_hw, batch)
    before = form_counts()
    jax.eval_shape(unet.apply, params, *args)
    after = form_counts()
    counted = {form: int(after[form] - before[form]) for form in FORMS}
    by_level = sites_by_level(cfg, latent_hw)
    assert sum(by_level.values()) == total
    folded = sum(n for hw, n in by_level.items()
                 if layers.conv3x3_form(tpu, batch, hw, hw) == "rows_folded")
    assert counted == {"rows_folded": folded, "xla_2d": total - folded}
    if not tpu or batch >= 8:
        assert folded == 0


def tree_shapes(tree):
    return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("case", ["sd15_512", "sdxl_1024"])
def test_the_unet_param_tree_does_not_depend_on_the_form(case, monkeypatch):
    """Path for path and shape for shape ``nn.Conv``'s tree, so a
    converted checkpoint, the init cache and the benchmark's
    ``harness/weights.py`` load into either."""
    make, latent_hw, batch, _ = CENSUS[case]
    monkeypatch.setattr(layers, "on_tpu", lambda: False)
    plain = tree_shapes(unet_shapes(make(), latent_hw, batch)[2])
    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    folded = tree_shapes(unet_shapes(make(), latent_hw, batch)[2])
    assert folded == plain
    assert plain["['params']['up_1_res_0']['conv1']['kernel']"][0][:2] == (3, 3)
    assert "['params']['up_1_upsample']['bias']" in plain


class NnConvResBlock(nn.Module):
    """The ResBlock as it stood before ISSUE 30: ``nn.Conv`` at both
    3x3 sites."""

    out_channels: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, temb):
        h = nn.silu(layers.GroupNorm32(name="norm1")(x))
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv1")(h)
        t = nn.Dense(self.out_channels, dtype=self.dtype,
                     name="time_proj")(nn.silu(temb))
        h = nn.silu(layers.GroupNorm32(name="norm2")(h + t[:, None, None, :]))
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                        name="skip")(x)
        return x + h


@pytest.mark.parametrize("tpu", [True, False], ids=["folded", "nn_conv"])
def test_resblock_inits_and_computes_as_with_nn_conv(tpu, monkeypatch):
    """Same init values leaf for leaf (the RNG fold path is the module
    path, which the form does not change) and the same output: to the
    bit off the TPU, where the call is ``nn.Conv``'s, and to float32
    rounding in the folded form."""
    monkeypatch.setattr(layers, "on_tpu", lambda: tpu)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 64))
    temb = jax.random.normal(jax.random.PRNGKey(2), (2, 32))
    before, after = NnConvResBlock(32, jnp.float32), ResBlock(32, jnp.float32)
    params = before.init(jax.random.PRNGKey(0), x, temb)
    counted = form_counts()
    mine = after.init(jax.random.PRNGKey(0), x, temb)
    assert form_counts()["rows_folded"] - counted["rows_folded"] == 2 * tpu
    assert tree_shapes(mine) == tree_shapes(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    want = before.apply(params, x, temb)
    with jax.default_matmul_precision("highest"):
        got = after.apply(params, x, temb)
    if tpu:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    else:
        np.testing.assert_array_equal(got, want)
