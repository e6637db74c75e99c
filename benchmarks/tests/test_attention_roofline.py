"""``flash_roofline_pct``: a call's shapes from its HLO text (as a chip's
trace wrote it, PR 26's probe), its site from the plain reference, the
floor by hand, and the reader over a table of instructions written by
hand. A call whose site cannot be told is an error."""

import pytest

from benchmarks.harness import rooflines
from benchmarks.readers import attention_roofline

LAYOUT = "{2,1,0:T(8,128)(2,1)S(1)}"
TAIL = ('), custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[2,4096,320]{2,1,0}}, frontend_attributes={kernel_metadata={}}')


def call(b, sq, sk, width, kind="bf16"):
    q, kv = f"{kind}[{b},{sq},{width}]", f"{kind}[{b},{sk},{width}]"
    return (f"%flash_attention.7 = {q}{LAYOUT} custom-call({q}{LAYOUT} "
            f"%bitcast.1, {kv}{LAYOUT} %pad.2, {kv}{LAYOUT} %pad.3" + TAIL)


#: SD1.5's image at 512x512 under CFG (tools/count_flops.py prints them)
SITES = {(1, 4096, 4096, 512), (2, 77, 77, 768), (2, 256, 256, 1280),
         (2, 256, 77, 1280), (2, 1024, 1024, 640), (2, 1024, 77, 640),
         (2, 4096, 4096, 320), (2, 4096, 77, 320)}
KIND = "TPU v5 lite"


def test_shapes_of_an_instruction_without_layouts_or_attributes():
    results, operands = rooflines.call_shapes(call(2, 4096, 128, 320))
    assert results == [("bf16", (2, 4096, 320))]
    assert operands == [("bf16", (2, 4096, 320)), ("bf16", (2, 128, 320)),
                        ("bf16", (2, 128, 320))]


@pytest.mark.parametrize("hlo, site", [
    (call(2, 4096, 4096, 320), (2, 4096, 4096, 320)),
    (call(2, 4096, 128, 320), (2, 4096, 77, 320)),    # keys padded to 128
    (call(2, 1024, 128, 640), (2, 1024, 77, 640)),
    (call(1, 4096, 4096, 512), (1, 4096, 4096, 512)),  # the VAE's one head
], ids=["self", "cross_padded", "cross_level_1", "vae"])
def test_a_call_is_given_the_site_of_its_shapes(hlo, site):
    assert rooflines.attention_call(hlo, SITES) == (site, 2)


@pytest.mark.parametrize("b, site", [
    (8, (2, 4096, 4096, 320)),      # four images' CFG pairs in one call
    (4, (2, 4096, 77, 320)),        # two, keys padded to 128
    (2, (1, 4096, 4096, 512)),      # the VAE on two images
], ids=["self_x4", "cross_x2", "vae_x2"])
def test_a_gathered_call_reads_that_multiple_of_its_sites_floor(b, site):
    """An image batch over 1 (ROADMAP A1): the call's B is a whole
    multiple of the site's, and the floor that multiple of one image's."""
    sk_call = 128 if site[2] == 77 else site[2]
    got, element_bytes = rooflines.attention_call(
        call(b, site[1], sk_call, site[3]), SITES)
    assert got == (b,) + site[1:] and element_bytes == 2
    assert rooflines.attention_floor_s(got, 2, KIND) == pytest.approx(
        (b // site[0]) * rooflines.attention_floor_s(site, 2, KIND))


@pytest.mark.parametrize("hlo, sites", [
    (call(2, 2048, 2048, 320), SITES),                 # no such site
    (call(3, 4096, 4096, 320), SITES),                 # no whole multiple
    (call(4, 4096, 4096, 320),
     SITES | {(4, 4096, 4096, 320)}),                  # two sites divide it
    (call(2, 4096, 64, 320), SITES),                   # fewer keys than any
    (call(2, 4096, 8192, 320), SITES),                 # two sites below it
    ("%flash_attention.1 = bf16[16,4096,40]{2,1,0} custom-call(bf16[16,4096,"
     "40]{2,1,0} %a, bf16[16,4096,40]{2,1,0} %b, bf16[16,4096,40]{2,1,0} %c)",
     SITES),                                           # heads folded away
    ("%flash_attention.1 = (bf16[2,4096,320]{2,1,0}, f32[2,4096]{1,0}) "
     "custom-call(bf16[2,4096,320]{2,1,0} %a)", SITES),  # not (q, k, v) -> o
    (call(2, 4096, 4096, 320, kind="c64"), SITES),     # no size on record
], ids=["unknown", "no_multiple", "two_batches", "too_short", "ambiguous",
        "folded", "not_qkv", "dtype"])
def test_a_call_whose_site_cannot_be_told_is_an_error(hlo, sites):
    with pytest.raises(ValueError):
        rooflines.attention_call(hlo, sites)


def test_the_floor_is_the_larger_of_compute_and_memory():
    # level-0 self-attention: 4 * 2 * 4096 * 4096 * 320 FLOPs over 197 T/s
    # bounds it; q, k, v and the output are 21 MB, 0.026 ms at 819 GB/s
    self_s = rooflines.attention_floor_s((2, 4096, 4096, 320), 2, KIND)
    assert self_s == pytest.approx(4 * 2 * 4096 * 4096 * 320 / 197e12)
    assert self_s == pytest.approx(0.218e-3, rel=2e-3)
    # cross-attention on 77 keys (not the 128 the call pads to) is bound
    # by moving q and the output: 0.8 GFLOP is 0.004 ms
    cross_s = rooflines.attention_floor_s((2, 4096, 77, 320), 2, KIND)
    assert cross_s == pytest.approx(
        (2 * 2 * 4096 * 320 + 2 * 2 * 77 * 320) * 2 / 819e9)
    with pytest.raises(KeyError):
        rooflines.attention_floor_s((2, 4096, 77, 320), 2, "TPU v9")


def table(*rows):
    return {"trace": {"instructions": [
        {"name": name, "hlo": hlo, "scope": "", "seconds": s, "calls": n}
        for name, hlo, s, n in rows]},
        "trees": None, "sizes": None, "names": None, "device_kind": KIND}


def test_the_reader_sums_floors_over_device_seconds(monkeypatch):
    monkeypatch.setattr(rooflines, "attention_sites", lambda *a: SITES)
    ctx = table(("flash_attention", call(2, 4096, 4096, 320), 3.73e-3, 5),
                ("flash_attention", call(2, 4096, 128, 320), 0.58e-3, 5),
                ("fusion", "%fusion.1 = f32[8]{0} fusion(...)", 9.0, 50))
    want = 100 * 5 * (4 * 2 * 4096 * 4096 * 320 / 197e12
                      + (4 * 4096 * 320 + 4 * 77 * 320) * 2 / 819e9) \
        / (3.73e-3 + 0.58e-3)
    got = attention_roofline.read(ctx, {"op": "flash_attention"})
    assert got == pytest.approx(want) and 0 < got < 100
    # a kernel named otherwise, an empty trace, an untraced run: nothing
    assert attention_roofline.read(ctx, {"op": "_flash_bhsd"}) is None
    assert attention_roofline.read(dict(ctx, trace={}), {"op": "x"}) is None
    assert attention_roofline.read({}, {"op": "flash_attention"}) is None
    # and a call that fits no site stops the run, it is not left out
    ctx["trace"]["instructions"].append(
        {"name": "flash_attention", "hlo": call(2, 2048, 2048, 320),
         "scope": "", "seconds": 1e-3, "calls": 1})
    with pytest.raises(ValueError):
        attention_roofline.read(ctx, {"op": "flash_attention"})


def test_sites_come_from_the_plain_reference_at_the_sizes_that_run():
    """The program's tiny test size: 64 px, so a 32x32 latent; UNet of 32
    and 64 channels with attention at level 0 and in the middle, 16 text
    positions; the VAE's middle block at 64 channels on one image."""
    import jax

    from benchmarks.harness import compare as cmp
    from benchmarks.harness.manifest import Cell, load_manifest
    from benchmarks.harness.stack import framework_config, program_sizes
    from cassmantle_tpu.models.clip_text import ClipTextEncoder
    from cassmantle_tpu.models.unet import UNet
    from cassmantle_tpu.models.vae import VAEDecoder

    manifest = load_manifest()
    config = Cell(manifest, manifest["workloads"][0]["name"]).config
    cfg = framework_config(config, True)
    sizes = program_sizes(cfg, config)
    names = cmp.named(config, sizes)
    key, i32 = jax.random.PRNGKey(0), "int32"
    trees = {
        "clip_text": jax.eval_shape(
            ClipTextEncoder(cfg.models.clip_text).init, key,
            jax.ShapeDtypeStruct((1, 16), i32)),
        "unet": jax.eval_shape(
            UNet(cfg.models.unet).init, key,
            jax.ShapeDtypeStruct((1, 32, 32, 4), "float32"),
            jax.ShapeDtypeStruct((1,), i32),
            jax.ShapeDtypeStruct((1, 16, 64), "float32")),
        "vae": jax.eval_shape(
            VAEDecoder(cfg.models.vae).init, key,
            jax.ShapeDtypeStruct((1, 32, 32, 4), "float32")),
    }
    assert rooflines.attention_sites(trees, sizes, names) == {
        (2, 16, 16, 64), (2, 1024, 1024, 32), (2, 1024, 16, 32),
        (2, 256, 256, 64), (2, 256, 16, 64), (1, 1024, 1024, 64)}
