"""``round_anatomy``'s reduction on a trace written by hand: scopes, two
programs and a gap, against values worked out by hand; and its reader of
the trace file against the profiler's own."""

import glob
import os

import pytest

from benchmarks.tools.round_anatomy import (
    anatomy,
    block_of,
    covering_span,
    load_planes,
    render,
    scope_path,
)

STEP = "jit(t2i_sample)/denoise_scan/while/body/denoise_step/UNet/"
# device ops (start, duration, name, scope), ns; the slice is 0..2000
OPS = [
    (0, 1000, "while", "jit(t2i_sample)/denoise_scan/while:"),  # container
    (0, 300, "flash_attention", STEP + "down_0_attn_0/block_0/self_attn/"
     "jit(_flash_bhsd)/flash_attention/pallas_call:"),
    (300, 200, "fusion", STEP + "down_0_attn_0/block_0/ff/proj/dot_general:"),
    (500, 250, "fusion", STEP + "down_1_res_0/conv1/conv_general_dilated:"),
    (750, 150, "copy", STEP + "mid_attn/proj_in/dot_general:"),
    (900, 100, "copy", ""),                                     # no scope
    (1000, 100, "convolution", "jit(t2i_sample)/vae_decode/VAEDecoder/"
     "up_0_res_0/conv1/conv_general_dilated:"),
    # idle 1100..1400, then the LM's program
    (1400, 50, "fusion", "jit(lm_decode)/lm_prefill/GPT2LM/h_0/attn/"
     "dot_general:"),
    (1450, 150, "fusion", "jit(lm_decode)/while/body/lm_decode_step/GPT2LM/"
     "h_0/mlp/dot_general:"),
    (1640, 360, "fusion", STEP + "up_0_res_1/conv2/conv_general_dilated:"),
]
PROGRAMS = [(0, 1100, "jit_t2i_sample"), (1400, 200, "jit_lm_decode"),
            (1640, 900, "jit_t2i_sample")]           # the last one is cut
HOST = [(0, 2000, "round.content"), (1090, 330, "pipeline.prompt_s"),
        (1100, 1500, "pipeline.image_lock_wait")]


def test_scope_paths_keep_the_programs_names_only():
    assert scope_path(OPS[1][3]) == [
        "t2i_sample", "denoise_scan", "denoise_step", "UNet",
        "down_0_attn_0", "block_0", "self_attn", "flash_attention"]
    assert scope_path("jit(lm_decode)/while/body/lm_decode_step/GPT2LM/"
                      "h_0/mlp/dot_general:") == [
        "lm_decode", "lm_decode_step", "GPT2LM", "h_0", "mlp"]
    assert scope_path("") == [] and scope_path("q:") == []


@pytest.mark.parametrize("scope, want", [
    (OPS[1][3], "down_0 attn.self_attn"),
    (OPS[2][3], "down_0 attn.ff"),
    (OPS[3][3], "down_1 res"),
    (OPS[4][3], "mid attn.norm_proj"),
    (STEP + "up_1_upsample/conv/conv_general_dilated:", "up_1 upsample"),
    (STEP + "time_fc1/dot_general:", "UNet time_fc1"),
    (OPS[6][3], "t2i_sample/vae_decode"),
    ("", "(no scope)"),
])
def test_unet_level_and_block_kind(scope, want):
    assert block_of(scope_path(scope)) == want


def test_device_time_a_dispatch_by_scope_program_and_gap():
    a = anatomy(OPS, PROGRAMS, HOST, window=(0, 2000))
    assert a["window_s"] == pytest.approx(2000e-9)
    assert a["busy_s"] == pytest.approx(1660e-9)
    # one whole sampler dispatch and one whole LM dispatch; the sampler
    # dispatch the capture cut, and the container, are not counted
    assert a["by_program"] == [
        ["jit_t2i_sample", 1, pytest.approx(1100e-9)],
        ["jit_lm_decode", 1, pytest.approx(200e-9)]]
    assert a["device_s"] == pytest.approx(1300e-9)
    assert a["scoped_share"] == pytest.approx(1200 / 1300)
    scopes = dict(a["by_scope"])
    assert scopes["t2i_sample"] == pytest.approx(1000e-9)
    assert scopes["t2i_sample/denoise_scan"] == pytest.approx(900e-9)
    assert scopes["t2i_sample/denoise_scan/denoise_step"] == \
        pytest.approx(900e-9)
    assert scopes["t2i_sample/vae_decode"] == pytest.approx(100e-9)
    assert scopes["lm_decode/lm_decode_step"] == pytest.approx(150e-9)
    assert scopes["lm_decode/lm_prefill"] == pytest.approx(50e-9)
    blocks = dict(a["by_block"])
    assert blocks["down_0 attn.self_attn"] == pytest.approx(300e-9)
    assert blocks["down_1 res"] == pytest.approx(250e-9)
    assert blocks["(no scope)"] == pytest.approx(100e-9)
    assert "up_0 res" not in blocks
    # the XLA names, split by where the program says they are
    fusion = next(row for row in a["by_op"] if row[0] == "fusion")
    assert fusion[1] == pytest.approx(650e-9)
    assert dict(fusion[2])["down_1 res"] == pytest.approx(250e-9)
    assert dict(fusion[2])["lm_decode/lm_decode_step"] == \
        pytest.approx(150e-9)
    # no gap of 50 us at this scale; with the bar lowered, two
    assert a["gaps"] == []
    b = anatomy(OPS, PROGRAMS, HOST, window=(0, 2000), min_gap_ns=30)
    assert [(g[1], g[2]) for g in b["gaps"]] == [
        (pytest.approx(300e-9), "pipeline.prompt_s"),
        (pytest.approx(40e-9), "pipeline.image_lock_wait")]
    text = render(b)
    assert "pipeline.prompt_s" in text and "down_0 attn.ff" in text
    assert "the UNet by level" in text and "attn.self_attn" in text
    assert anatomy([], PROGRAMS, HOST) == {}


def test_two_whole_dispatches_read_as_one():
    """A capture that holds the second sampler dispatch whole too: the
    tables are a dispatch, whatever the capture's length."""
    a = anatomy(OPS, PROGRAMS, HOST, window=(0, 3000))
    assert a["by_program"][0] == ["jit_t2i_sample", 2,
                                  pytest.approx(1000e-9)]
    scopes = dict(a["by_scope"])
    assert scopes["t2i_sample"] == pytest.approx((1000 + 360) / 2 * 1e-9)
    assert scopes["lm_decode"] == pytest.approx(200e-9)
    assert dict(a["by_block"])["up_0 res"] == pytest.approx(180e-9)


def test_no_whole_program_says_so():
    a = anatomy(OPS, PROGRAMS, HOST, window=(100, 1000))
    assert a["device_s"] == 0 and "longer slice" in render(a)


def test_a_gap_is_named_after_the_innermost_span_that_covers_it():
    spans = [(0, 1000, "round.content"), (100, 300, "pipeline.prompt_s"),
             (450, 20, "pipeline.image_host")]
    assert covering_span(spans, 150, 250) == "pipeline.prompt_s"
    assert covering_span(spans, 500, 600) == "round.content"
    assert covering_span(spans, 440, 500) == "round.content"
    assert covering_span(spans, 445, 475) == "pipeline.image_host"
    assert covering_span(spans, 2000, 2100) == "no_program_span"
    assert covering_span([(0, 40, "a.x")], 0, 100) == "a.x"


def test_the_file_reader_agrees_with_the_profilers_own(tmp_path):
    """A CPU profiler session with one annotation: planes, lines, event
    names, starts and durations as ``jax.profiler.ProfileData`` gives
    them (it leaves out the metadata's statistics, where a device
    event's op name is, hence a reader of our own)."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("pipeline.some_span"):
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    ours = load_planes(path)
    theirs = jax.profiler.ProfileData.from_file(path)
    seen = 0
    for plane, ref in zip(ours, theirs.planes):
        assert plane["name"] == ref.name
        ref_lines = list(ref.lines)
        assert [ln["name"] for ln in plane["lines"]] == [
            ln.name for ln in ref_lines]
        for line, ref_line in zip(plane["lines"], ref_lines):
            ref_events = list(ref_line.events)
            assert len(line["events"]) == len(ref_events)
            for (start, duration, name, stats), ev in zip(
                    line["events"], ref_events):
                assert name == ev.name
                assert abs(start - ev.start_ns) <= 1
                assert abs(duration - ev.duration_ns) <= 1
                assert set(dict(ev.stats)) <= set(stats)
                seen += name == "pipeline.some_span"
    assert seen == 1
