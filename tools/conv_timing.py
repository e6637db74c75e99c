#!/usr/bin/env python3
"""Time one 3x3 convolution site alone on the chip, in each form.

For each stride-1 3x3 site of the served UNets (the rows of ``SITES``:
bf16, CFG batch 2, NHWC) this times ``nn.Conv``'s 2-D convolution
(``xla_2d``), ``models/layers.py::conv3x3_rows_folded`` with H or W in
the batch (``h_taps``, ``w_taps``: one convolution a tap of that axis,
summed) and the same with the three taps stacked on the channels instead
(``h_stacked``, ``w_stacked``), bias added, and checks each against the
2-D form in float32 on the same device.
``--fused`` also times the parked Pallas kernel (``ops/fused_conv.py``,
GroupNorm affine and SiLU included: a reading for its own verdict).

    python tools/conv_timing.py --sites sd15_16_2560_1280,sd15_32_1920_640

``--unet`` times one whole SD1.5 UNet forward at CFG batch 2 instead,
under each rule of ``UNET_RULES`` (which spatial sizes fold, and in which
form) put in ``conv3x3_form``'s place here, in the tool: what a
site costs between its neighbours, which fuse and lay out differently
from a site alone.

A site is timed inside one jitted ``fori_loop`` of ``--calls`` dependent
calls (one element of the output is added to the next call's input),
warm, median of ``--repeats``: what a call costs the device, not what it
costs to launch. One JSON object a line, also appended to
``chiprun_out/conv_timing.jsonl``. Needs a TPU unless ``--rehearse``
(the control flow at tiny sizes: no time it prints is a measurement).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cassmantle_tpu.models import layers  # noqa: E402

#: site -> (H = W, C, F): every distinct stride-1 3x3 shape of the SD1.5
#: UNet's ResBlocks and upsamplers at 512x512 above 8x8, then the three
#: widest of the 1024x1024 two-tower pipeline's UNet
SITES = {
    "sd15_64_320_320": (64, 320, 320),      # down conv1, every conv2
    "sd15_64_640_320": (64, 640, 320),      # up_0 res_1/res_2 conv1
    "sd15_64_960_320": (64, 960, 320),      # up_0 res_0 conv1
    "sd15_64_640_640": (64, 640, 640),      # up_1_upsample
    "sd15_32_320_640": (32, 320, 640),      # down_1 res_0 conv1
    "sd15_32_640_640": (32, 640, 640),      # down conv1, every conv2
    "sd15_32_960_640": (32, 960, 640),      # up_1 res_2 conv1
    "sd15_32_1280_640": (32, 1280, 640),    # up_1 res_1 conv1
    "sd15_32_1920_640": (32, 1920, 640),    # up_1 res_0 conv1
    "sd15_32_1280_1280": (32, 1280, 1280),  # up_2_upsample
    "sd15_16_640_1280": (16, 640, 1280),    # down_2 res_0 conv1
    "sd15_16_1280_1280": (16, 1280, 1280),  # conv2, up_3_upsample
    "sd15_16_1920_1280": (16, 1920, 1280),  # up_2 res_2 conv1
    "sd15_16_2560_1280": (16, 2560, 1280),  # up_2 res_0/res_1 conv1
    "sd15_8_2560_1280": (8, 2560, 1280),    # up_3 conv1: the rule's 2-D
    "sd15_8_1280_1280": (8, 1280, 1280),    # the other 11 sites at 8x8
    "sdxl_128_960_320": (128, 960, 320),
    "sdxl_64_1920_640": (64, 1920, 640),
    "sdxl_32_2560_1280": (32, 2560, 1280),
    # models/vae.py's ResBlocks: --batch 1 --dtype float32
    "vae_64_512_512": (64, 512, 512),
    "vae_128_512_512": (128, 512, 512),
    "vae_256_256_256": (256, 256, 256),
}

def stacked_form(x, kernel, axis):
    """The arrangement the program does not use: the three taps of the
    folded axis stacked on the input channels, one 1-D convolution over
    3·C channels. Alone it is within 5% of ``conv3x3_rows_folded`` at
    most sites; between its neighbours the 3x copy of the input costs
    what the fold gains (PERF.md section 5, PR 30)."""
    if axis == 2:
        x, kernel = jnp.swapaxes(x, 1, 2), jnp.swapaxes(kernel, 0, 1)
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0), (0, 0)))
    x3 = jnp.concatenate([xp[:, dh:dh + h] for dh in range(3)],
                         axis=-1).reshape(b * h, w, 3 * c)
    k3 = jnp.transpose(kernel, (1, 0, 2, 3)).reshape(3, 3 * c, -1)
    y = jax.lax.conv_general_dilated(
        x3, k3, (1,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"))
    y = y.reshape(b, h, w, -1)
    return jnp.swapaxes(y, 1, 2) if axis == 2 else y


FORMS = {
    "xla_2d": lambda x, k: jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")),
    "h_taps": functools.partial(layers.conv3x3_rows_folded, axis=1),
    "w_taps": functools.partial(layers.conv3x3_rows_folded, axis=2),
    "h_stacked": functools.partial(stacked_form, axis=1),
    "w_stacked": functools.partial(stacked_form, axis=2),
}

#: --unet: rule -> (spatial sizes folded, the folded form)
UNET_RULES = {
    "all_2d": ((), FORMS["h_taps"]),
    "fold_16_h_taps": ((16,), FORMS["h_taps"]),
    "fold_16_32_h_taps": ((16, 32), FORMS["h_taps"]),
    "fold_8_16_32_h_taps": ((8, 16, 32), FORMS["h_taps"]),
    "fold_16_32_64_h_taps": ((16, 32, 64), FORMS["h_taps"]),
    "fold_8_16_32_64_h_taps": ((8, 16, 32, 64), FORMS["h_taps"]),
    "fold_16_32_64_h_stacked": ((16, 32, 64), FORMS["h_stacked"]),
    "fold_16_32_64_w_taps": ((16, 32, 64), FORMS["w_taps"]),
}


def median_ms(run, args, calls, repeats):
    run(*args).block_until_ready()
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        run(*args).block_until_ready()
        times.append(time.perf_counter() - start)
    return 1e3 * statistics.median(times) / calls


def time_site(conv, x, kernel, bias, calls, repeats):
    """ms a call of ``conv(x, kernel) + bias`` and seconds to compile."""
    def step(_, x):
        y = conv(x, kernel) + bias
        # the next call's input depends on this call's output, so no
        # call can be hoisted out of the loop or run beside another
        return x.at[0, 0, 0, 0].add(y[0, 0, 0, 0])

    start = time.perf_counter()
    run = jax.jit(lambda x: jax.lax.fori_loop(0, calls, step, x)
                  ).lower(x).compile()
    compile_s = time.perf_counter() - start
    return median_ms(run, (x,), calls, repeats), compile_s


def fused_form(x, kernel):
    """The parked Pallas kernel at this site: GroupNorm's affine (here
    the identity), SiLU and the nine shifted matmuls in one call."""
    from cassmantle_tpu.ops.fused_conv import gn_silu_conv3x3

    b, c = x.shape[0], x.shape[-1]
    return gn_silu_conv3x3(
        x, jnp.ones((b, c), jnp.float32), jnp.zeros((b, c), jnp.float32),
        kernel, jnp.zeros((kernel.shape[-1],), x.dtype), pad_to=128)


def unet_forward_ms(rule, batch, latent_hw, calls, repeats, tiny):
    """ms a forward of the SD1.5 UNet under ``rule``; the trace's
    ``conv.dispatch`` census; seconds to compile."""
    from cassmantle_tpu.config import FrameworkConfig, test_config
    from cassmantle_tpu.models.unet import UNet
    from cassmantle_tpu.utils.logging import metrics

    cfg = (test_config() if tiny else FrameworkConfig()).models.unet
    unet = UNet(cfg)
    lat = jnp.zeros((batch, latent_hw, latent_hw, cfg.sample_channels),
                    jnp.bfloat16)
    ts = jnp.full((batch,), 500, jnp.int32)
    ctx = jnp.zeros((batch, 77, cfg.context_dim), jnp.bfloat16)
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        unet.init(key, lat, ts, ctx)))(jax.random.PRNGKey(0))

    def chain(params, lat):
        # the weights are an argument: closed over, 1.7 GB of them would
        # be constants of the program
        def step(_, lat):
            eps = unet.apply(params, lat, ts, ctx)
            return lat + 0.01 * eps.astype(lat.dtype)

        return jax.lax.fori_loop(0, calls, step, lat)

    def census():
        counters = metrics.dump_state()["counters"]
        return {dict(labels)["form"]: value for name, labels, value
                in counters if name == "conv.dispatch"}

    sizes, folded_form = rule
    was, before = (layers.conv3x3_form, layers.conv3x3_rows_folded), census()
    layers.conv3x3_form = lambda tpu, b, h, w: (
        "rows_folded" if h in sizes else "xla_2d")
    layers.conv3x3_rows_folded = folded_form
    try:
        start = time.perf_counter()
        run = jax.jit(chain).lower(params, lat).compile()
        compile_s = time.perf_counter() - start
    finally:
        layers.conv3x3_form, layers.conv3x3_rows_folded = was
    after = census()
    sites = {form: int(after[form] - before.get(form, 0))
             for form in after if after[form] != before.get(form, 0)}
    return median_ms(run, (params, lat), calls, repeats), sites, compile_s


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sites", default=",".join(SITES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--fused", action="store_true",
                    help="also time ops/fused_conv.py at each site")
    ap.add_argument("--unet", default=None, nargs="?", const=",".join(
        UNET_RULES), help="time a whole UNet forward under these rules "
                          "instead of single sites")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip; channels cut to a sixteenth")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({device.platform}): nothing to time",
              file=sys.stderr)
        return 1
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def emit(line):
        line["device"] = f"{device.platform}:{device.device_kind}"
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, "conv_timing.jsonl"), "a") as sink:
            sink.write(text + "\n")

    if args.unet is not None:
        for name in filter(None, args.unet.split(",")):
            ms, sites, compile_s = unet_forward_ms(
                UNET_RULES[name], args.batch, 32 if args.rehearse else 64,
                args.calls, args.repeats, args.rehearse)
            emit({"unet_forward": name, "batch": args.batch, "ms": ms,
                  "conv_dispatch": sites, "compile_s": compile_s})
        return 0

    forms = {name: FORMS[name]
             for name in filter(None, args.forms.split(","))}
    if args.fused:
        forms["fused_conv"] = fused_form
    for site in filter(None, args.sites.split(",")):
        hw, c, f = SITES[site]
        if args.rehearse:
            hw, c, f = min(hw, 16), c // 16, f // 16
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        dtype = jnp.dtype(args.dtype)
        x = jax.random.normal(keys[0], (args.batch, hw, hw, c), dtype)
        kernel = (jax.random.normal(keys[1], (3, 3, c, f), jnp.float32)
                  / (9 * c) ** 0.5).astype(dtype)
        bias = jax.random.normal(keys[2], (f,), dtype)
        ref = FORMS["xla_2d"](x.astype(jnp.float32),
                              kernel.astype(jnp.float32))
        for name, conv in forms.items():
            line = {"site": site, "shape": [args.batch, hw, hw, c, f],
                    "dtype": args.dtype, "form": name}
            try:
                line["ms"], line["compile_s"] = time_site(
                    conv, x, kernel, bias, args.calls, args.repeats)
                if name != "fused_conv":  # that one activates its input
                    line["max_abs_gap_to_2d_f32"] = float(jnp.max(jnp.abs(
                        conv(x, kernel).astype(jnp.float32) - ref)))
            except Exception as exc:  # a shape the compiler refuses
                line["error"] = str(exc)[:400]
            emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
