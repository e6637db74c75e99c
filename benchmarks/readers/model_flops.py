"""The whole round's share of the chip's bf16 peak: model FLOPs of the
rounds completed in the window (the benchmark's own count from the plain
reference's shapes) over window seconds times the peak of the device kind
from the benchmark's own table."""

from benchmarks.harness import flops, peaks


def read(ctx: dict, args: dict):
    if ctx["rounds"] <= 0:
        return None
    sizes, trees, names = ctx["sizes"], ctx["trees"], ctx["names"]
    per_round = flops.image_flops(trees, sizes, names)["image"] \
        + flops.lm_flops(trees, names, args["lm_prompt_tokens"],
                         sizes["sampler"]["max_new_tokens"])
    total = per_round * ctx["rounds"]
    device_rows = ctx["window"].counter("scorer.embed_cache_misses")
    if device_rows:
        total += device_rows * flops.scorer_row_flops(trees, sizes)
    peak = peaks.peak(ctx["device_kind"], "bf16_flops_per_s")
    return 100.0 * total / (ctx["window_s"] * peak)
