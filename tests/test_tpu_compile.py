"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside jax and compiles for a v5e that is
described, not attached (``jax.experimental.topologies``). Interpret-mode
tests run a kernel's arithmetic but never reach the TPU lowering or
Mosaic, which is where every kernel family here was once refused (a
float in a CostEstimate, a (1, C) block of a (B, C) array, a VMEM stack
past the scoped limit, a kernel GSPMD was asked to partition). Each case
below compiles one kernel of ``ops/`` at the widths SD1.5-512 serves
(CFG batch 8 = the 4-image bucket) and asserts the compiled program
holds the Mosaic call. Nothing runs: a pass says the chip's compiler
accepts the kernel, not that its numbers are right (the interpret-mode
parity tests and the chip smoke say that).

A kernel the compiler still refuses is a strict xfail carrying the
compiler's message, so the day it compiles the case must be promoted.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from cassmantle_tpu.ops import attention  # noqa: E402
from cassmantle_tpu.ops import flash_attention as fa  # noqa: E402
from cassmantle_tpu.ops import fused_conv as fc  # noqa: E402
from cassmantle_tpu.ops import quant_matmul as qm  # noqa: E402

BF16, I8, F32 = jnp.bfloat16, jnp.int8, jnp.float32


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e host, with the persistent compile cache off
    around the module: an entry compiled for a described chip is written
    but cannot be read back without one (jax warns and recompiles)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu on this host: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _flash(b, s, h, d, s_k=None):
    """The flash kernel as the model reaches it: q/k/v in the projections'
    (B, S, H·D) layout, split into heads by a free reshape, under the
    shape rule's own plan (``s_k``: ragged text context, padded in)."""
    def attend(q, k, v):
        q, k, v = (t.reshape(t.shape[:-1] + (h, d)) for t in (q, k, v))
        assert fa.flash_plan(q, k) is not None
        return fa.flash_attention(q, k, v, interpret=False).reshape(
            (b, s, h * d))

    return (attend, [((b, s, h * d), BF16)]
            + [((b, s_k or s, h * d), BF16)] * 2)


def _fused_conv(b, hw, c, f, pad):
    return (lambda x, a, b_, k, bias: fc.gn_silu_conv3x3(
        x, a, b_, k, bias, pad_to=pad, interpret=False),
        [((b, hw, hw, c), BF16), ((b, c), F32), ((b, c), F32),
         ((3, 3, c, f), BF16), ((f,), BF16)])


def _int8_matmul(m, k, n):
    return (lambda x, w, rs, cs: qm.int8_matmul(
        x, w, rs, cs, out_dtype=BF16, interpret=False),
        [((m, k), I8), ((k, n), I8), ((m, 1), F32), ((1, n), F32)])


def _int8_conv(b, hw, c, f):
    return (lambda x, k, cs, bias: qm.int8_conv3x3(
        x, k, cs, bias, out_dtype=BF16, interpret=False),
        [((b, hw, hw, c), I8), ((3, 3, c, f), I8), ((f,), F32),
         ((f,), F32)])


UNALIGNED_DMA = pytest.mark.xfail(
    strict=True, raises=Exception,
    reason="Mosaic: 'Slice shape along dimension 3 must be aligned to "
           "tiling (128), but is 320' — the halo DMA slices the channel "
           "dim of an HBM array whose layout pads 320 to 384. Only the "
           "unpadded form of a 320/960-wide level; every preset that "
           "turns fused_conv on pads to 128 (ROADMAP A3)")

KERNEL_CASES = {
    # the one flash kernel at every site it serves, at CFG batch 2 (one
    # image) and batch 8 (the 4-image bucket). SD1.5-512: UNet
    # self-attention at levels 0/1, ragged text cross-attention (S_k=77)
    # at the same levels, the VAE mid block's one wide head (batch 1
    # and 4: no CFG in the decoder)
    "flash_self_l0": _flash(8, 4096, 8, 40),
    "flash_self_l1": _flash(8, 1024, 8, 80),
    "flash_cross77_l0": _flash(8, 4096, 8, 40, s_k=77),
    "flash_cross77_l1": _flash(8, 1024, 8, 80, s_k=77),
    "flash_wide_vae_mid": _flash(4, 4096, 1, 512),
    "flash_self_l0_b2": _flash(2, 4096, 8, 40),
    "flash_self_l1_b2": _flash(2, 1024, 8, 80),
    "flash_cross77_l0_b2": _flash(2, 4096, 8, 40, s_k=77),
    "flash_cross77_l1_b2": _flash(2, 1024, 8, 80, s_k=77),
    "flash_wide_vae_mid_b1": _flash(1, 4096, 1, 512),
    # SDXL-1024: 10 heads of 64 over 4096 tokens, 20 heads of 64 over
    # 1024, the VAE's head over 16,384
    "flash_sdxl_self_h10": _flash(8, 4096, 10, 64),
    "flash_sdxl_self_h20": _flash(8, 1024, 20, 64),
    "flash_sdxl_cross77_h10": _flash(8, 4096, 10, 64, s_k=77),
    "flash_sdxl_cross77_h20": _flash(8, 1024, 20, 64, s_k=77),
    "flash_sdxl_wide_vae_mid": _flash(4, 16384, 1, 512),
    "flash_sdxl_self_h10_b2": _flash(2, 4096, 10, 64),
    "flash_sdxl_self_h20_b2": _flash(2, 1024, 20, 64),
    "flash_sdxl_cross77_h10_b2": _flash(2, 4096, 10, 64, s_k=77),
    "flash_sdxl_cross77_h20_b2": _flash(2, 1024, 20, 64, s_k=77),
    "flash_sdxl_wide_vae_mid_b1": _flash(1, 16384, 1, 512),
    # fused GN+SiLU+conv3x3 (fusedconv/w8a8 presets, conv_pad_to=128):
    # the four level shapes, then the widest skip-concat at each end
    "fused_h64_c320": _fused_conv(8, 64, 320, 320, 128),
    "fused_h32_c640": _fused_conv(8, 32, 640, 640, 128),
    "fused_h16_c1280": _fused_conv(8, 16, 1280, 1280, 128),
    "fused_h8_c1280": _fused_conv(8, 8, 1280, 1280, 128),
    "fused_h64_c960_concat": _fused_conv(8, 64, 960, 320, 128),
    "fused_h8_c2560_concat": _fused_conv(8, 8, 2560, 1280, 128),
    "fused_h64_c320_unpadded": _fused_conv(8, 64, 320, 320, 0),
    # W8A8: QKV / GEGLU / text-context projections and a decode-width
    # LM matmul, then the int8 conv at three levels
    "int8_mm_qkv_l0": _int8_matmul(32768, 320, 960),
    "int8_mm_geglu_l1": _int8_matmul(8192, 640, 5120),
    "int8_mm_context": _int8_matmul(616, 768, 320),
    "int8_mm_lm_decode": _int8_matmul(4, 768, 2304),
    "int8_conv_h64_c384": _int8_conv(8, 64, 384, 384),
    "int8_conv_h32_c640": _int8_conv(8, 32, 640, 640),
    "int8_conv_h16_c1280": _int8_conv(8, 16, 1280, 1280),
}
STILL_REFUSED = {"fused_h64_c320_unpadded": UNALIGNED_DMA}


@pytest.fixture(scope="module")
def compiled_kernels(v5e):
    """{case: compiled text, or the exception the compiler raised}. All
    cases compile side by side (the compiler runs outside the GIL): ~8 s
    of wall clock instead of ~30, in a tier-1 window that is tight."""
    from concurrent.futures import ThreadPoolExecutor

    one_chip = SingleDeviceSharding(v5e.devices[0])

    def compile_case(case):
        fn, shapes = case
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        try:
            return jax.jit(fn).lower(*args).compile().as_text()
        except Exception as exc:  # handed to the case's own test
            return exc

    with ThreadPoolExecutor(max_workers=8) as pool:
        return dict(zip(KERNEL_CASES,
                        pool.map(compile_case, KERNEL_CASES.values())))


@pytest.mark.parametrize("case", [
    pytest.param(name, marks=STILL_REFUSED.get(name, ()))
    for name in KERNEL_CASES])
def test_kernel_compiles_for_v5e(compiled_kernels, case):
    compiled = compiled_kernels[case]
    if isinstance(compiled, Exception):
        raise compiled
    assert "tpu_custom_call" in compiled


@pytest.mark.parametrize("sharded_region", [True, False],
                         ids=["per_batch_shard", "left_to_gspmd"])
def test_flash_dispatch_under_a_batch_sharded_mesh(v5e, monkeypatch,
                                                   sharded_region):
    """The dp serving mesh: a jit whose q/k/v arrive batch-sharded over
    four chips. Inside ``batch_sharded_kernels`` (what
    ``dp_sharded_sampler`` traces under) the attention dispatch hands
    each chip its rows through shard_map and the program compiles, one
    Mosaic call per partition and no collective; left to GSPMD the same
    dispatch is refused — the crash the default server had at first
    compile on any multi-chip host."""
    # the dispatch asks the host which backend it is on: steer it onto
    # the chip's branch here, in the test
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(v5e.devices).reshape(4), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    q = jax.ShapeDtypeStruct((8, 4096, 8, 40), BF16, sharding=rows)

    def attend(q, k, v):
        if not sharded_region:
            return attention.multi_head_attention(q, k, v)
        with attention.batch_sharded_kernels(mesh, "dp"):
            return attention.multi_head_attention(q, k, v)

    lower = jax.jit(attend, out_shardings=rows).lower
    if not sharded_region:
        with pytest.raises(NotImplementedError,
                           match="cannot be automatically partitioned"):
            lower(q, q, q)
        return
    text = lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in text
    assert "num_partitions=4" in text
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute"))


def _prefetches_in_the_decode_step(text: str) -> int:
    """` slice-start(` instructions of the computation that holds the
    decode step (the one with the most ``lm_decode_step`` lines): the
    compiler's own reads of weight pieces into on-chip memory ahead of
    the product that uses them."""
    counts, name = {}, None
    for line in text.splitlines():
        if line[:1] not in (" ", "", "}") and line.rstrip().endswith("{"):
            name = line
            counts[name] = [0, 0]
        elif name is not None:
            counts[name][0] += "lm_decode_step" in line
            counts[name][1] += " slice-start(" in line
    return max(counts.values())[1]


def _rows_rounded_in_the_decode_step(text: str, rows: int) -> set:
    """The scopes, below their layer, of the decode step's instructions
    whose result is a ``bf16[rows, N]``: the places where a step's
    activation is rounded to the stored type."""
    found = set()
    for line in text.splitlines():
        hit = re.search(r'= bf16\[%d,\d+\]\S* .*op_name="([^"]*)"' % rows,
                        line)
        if hit and "lm_decode_step" in hit.group(1):
            found.add(re.sub(r".*decode_step/(layer_\d+/)?", "",
                             hit.group(1)))
    return found


def _sparse_cut(name: str):
    """(model, bytes of its weights in bfloat16 (low, high), the shapes of
    an expert's two matrices in the compiled text, ``cache_stats``, the
    least prefetches its decode step keeps: 172 and 92 were read, the
    most its temporaries may take) of a served cut of a sparse prompt LM.
    ``lfm2_game``'s largest program read 245 MB of temporaries; 537 MB
    more when the embedding's look-up widened the whole tied table to
    float32 in every decode step (PR 34: a step of 3.15 ms for 1.58).
    ``qwen3next_game``'s read 235 MB, and 427 while its steps of two rows
    and more did the same to its table (PR 37: 0.78 ms of a 3 ms step)."""
    from cassmantle_tpu import config as configs
    from cassmantle_tpu.models import lfm2_moe, qwen3_next

    if name == "qwen3next":
        cfg = configs.qwen3next_game_config().models.qwen3_next
        return (qwen3_next.Qwen3NextLM(cfg), (7.3e9, 7.4e9),
                ("[2048,1024]", "[512,2048]"), qwen3_next.cache_stats, 100,
                0.3e9)
    cfg = configs.lfm2_game_config().models.lfm2_moe
    return (lfm2_moe.Lfm2MoeLM(cfg), (10.3e9, 10.4e9),
            ("[2048,3072]", "[1536,2048]"), lfm2_moe.cache_stats, 80, 0.5e9)


@pytest.mark.parametrize("cut", ["qwen3next", "lfm2"])
@pytest.mark.parametrize("rows, bucket", [(4, 64), (2, 32), (1, 32)],
                         ids=["batch4_bucket64", "batch2_bucket32",
                              "batch1_bucket32"])
def test_the_sparse_prompt_lm_compiles_for_one_v5e_at_its_served_size(
        v5e, monkeypatch, rows, bucket, cut):
    """``greedy_decode`` over a sparse prompt LM at its served cut
    (``qwen3next_game``: published widths, 8 layers, 128 of 512 experts of
    6.3 MB; ``lfm2_game``: 1 + 8 layers, all 64 experts of 18.9 MB, more
    than on-chip memory holds of one), the largest and the smallest served
    program: it fits one chip beside the image stack, and each expert
    layer of the decode step walks its routed assignments in one
    ``moe_walk`` kernel (ops/moe_walk.py) that reads an expert's matrices
    where they lie: no loop of dependent products under ``moe_experts``,
    no copy of a matrix beside the kernel, and the kernel's rings of
    pieces within what the compiler gives a kernel. The compiler still
    prefetches the step's other weights into on-chip memory (132 pieces
    a step around the loop; none at all around a kernel that states no
    cost, which made a dispatch 12 ms slower on the chip, PR 32). The
    rows' position offsets are an operand, as the serving path always
    hands them over: a batch of mixed prompt buckets runs this very
    program. And a row decodes in company as alone: at one row the
    compiler keeps a step's activation in float32 into every projection
    (it drops the rounding the source wrote), and at two and four rows no
    projection takes a ``bf16[rows, N]`` activation either
    (models/moe.py ``stored_dot``; before, the mixers' and the shared
    expert's did, and no prompt was served the same tokens in a pair as
    alone). What is rounded to the stored type is what is rounded at one
    row too: the row the walk kernel multiplies one assignment at a time,
    the cache's new entries, the embedding's stored row. (The rule asks
    ``on_tpu``; a described chip is not attached, so the test answers
    for it.)"""
    from cassmantle_tpu.models import moe
    from cassmantle_tpu.ops import moe_walk
    from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair

    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(moe_walk, "on_tpu", lambda: True)
    chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    (model, weight_bytes, matrices, cache_stats, prefetches,
     temporaries) = _sparse_cut(cut)
    tree = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, BF16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    compiled = greedy_decode.lower(
        make_apply_pair(model), tree, on_chip((rows, bucket), jnp.int32),
        on_chip((rows,), jnp.int32), on_chip((2,), jnp.uint32), 96, 257, 0.0,
        40, row_mask=on_chip((rows,), jnp.bool_),
        cache_stats=cache_stats,
        position_offset=on_chip((rows,), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    low, high = weight_bytes
    assert low < memory.argument_size_in_bytes < high
    assert memory.temp_size_in_bytes < temporaries
    text = compiled.as_text()
    assert _prefetches_in_the_decode_step(text) >= prefetches
    step_experts = [line for line in text.splitlines()
                    if "lm_decode_step" in line and "/moe_experts/" in line]
    kernels = [line for line in step_experts
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 8 and all(
        line.lstrip().startswith("%moe_walk") for line in kernels), kernels
    scoped = {int(size) for line in kernels for size in re.findall(
        r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', line)}
    assert scoped == {moe_walk.VMEM_LIMIT_BYTES} and max(scoped) < 16 << 20
    assert not [line for line in step_experts if " while(" in line]
    copies = [line for line in step_experts
              if " copy(" in line and any(m in line for m in matrices)]
    assert not copies, copies[:2]
    rounded = _rows_rounded_in_the_decode_step(text, rows)
    assert "moe/convert_element_type" in rounded
    assert not [scope for scope in rounded if not (
        scope == "moe/convert_element_type" or scope.startswith("embed/")
        or scope.endswith("_attn/mixer/convert_element_type"))], rounded


@pytest.mark.parametrize("d, f", [(2048, 512), (2048, 1536)],
                         ids=["qwen3next_experts", "lfm2_experts"])
def test_the_walk_kernels_rings_are_sized_by_piece_not_by_expert(d, f):
    """At both served width sets the plan's rings stay under what the
    kernel asks of on-chip memory, with room for a piece beside them,
    whatever an expert weighs (6.3 and 18.9 MB); widths without whole
    lanes have no plan, and ``moe_walk_fits`` says so."""
    from cassmantle_tpu.ops import moe_walk

    plan = moe_walk.walk_plan(d, f, 2)
    scratch = plan.scratch_bytes(d, f, 2)
    assert scratch + moe_walk.PIECE_BYTES <= moe_walk.VMEM_LIMIT_BYTES
    assert moe_walk.moe_walk_fits(d, f)
    assert d % plan.gate_up_rows == 0 and f % plan.down_rows == 0
    assert min(plan.gate_up_slots, plan.down_slots) >= 2
    if f == 1536:  # an expert does not fit: it streams through
        assert 3 * d * f * 2 > moe_walk.VMEM_LIMIT_BYTES > scratch
    assert not moe_walk.moe_walk_fits(d, f + 64)
    assert moe_walk.walk_plan(d, f, 2, piece_bytes=1024) is None


# (B, H = W, C, F): the ResBlock with the widest conv1 of each SD1.5
# level at CFG batch 2, and the 16x16 one at the 4-image bucket
RESBLOCK_SITES = {
    "sd15_16x16_b2": (2, 16, 2560, 1280),
    "sd15_32x32_b2": (2, 32, 1920, 640),
    "sd15_64x64_b2": (2, 64, 960, 320),
    "sd15_16x16_b8": (8, 16, 2560, 1280),
    "sd15_8x8_b2": (2, 8, 2560, 1280),
}


@pytest.mark.parametrize("site", list(RESBLOCK_SITES))
def test_resblock_convolutions_multiply_no_padding(v5e, monkeypatch, site):
    """Under 8 batch rows the TPU compiler rewrites a 3x3 convolution
    space-to-batch, W cut into 8 chunks of W/8 + 1 columns, one of them
    padding: a ResBlock then counts x1.46 (16x16), x1.23 (32x32) and
    x1.12 (64x64) the FLOPs its shapes need (ISSUE 30's reading of the
    parent). Where ``conv3x3_form`` folds H into the batch the program
    holds no 3x3 window and counts the needed FLOPs. From 8 rows up the
    compiler's direct form is kept, which pads nothing; at 64x64 the
    padded form is kept knowingly (PERF.md section 5: folding there lost
    between its neighbours), and its ninth of padding is pinned here."""
    from cassmantle_tpu.models import layers
    from cassmantle_tpu.models.unet import ResBlock

    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    b, hw, c, f = RESBLOCK_SITES[site]
    chip = SingleDeviceSharding(v5e.devices[0])
    block = ResBlock(f, BF16)
    x = jax.ShapeDtypeStruct((b, hw, hw, c), BF16, sharding=chip)
    temb = jax.ShapeDtypeStruct((b, 1280), BF16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, BF16, sharding=chip),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x, temb))
    compiled = jax.jit(block.apply).lower(params, x, temb).compile()
    # conv1, conv2, the 1x1 skip, time_proj
    needed = 2 * b * (hw * hw * (9 * c * f + 9 * f * f + c * f) + 1280 * f)
    counted = compiled.cost_analysis()["flops"] / needed
    windows_3x3 = [line for line in compiled.as_text().splitlines()
                   if " convolution(" in line and "window={size=3x3" in line]
    if layers.conv3x3_form(True, b, hw, hw) == "rows_folded":
        assert b < 8 and hw <= 32
        assert not windows_3x3, windows_3x3[:1]
        assert counted <= 1.05
    elif b >= 8:
        assert len(windows_3x3) == 2
        assert all("dim_labels=b01f_01io->b01f" in line
                   for line in windows_3x3), windows_3x3
        assert counted <= 1.05
    else:
        assert hw >= 64 and len(windows_3x3) == 2
        assert 1.05 < counted < 1.15
