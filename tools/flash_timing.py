#!/usr/bin/env python3
"""Time the flash-attention kernel alone, one call at a time, on the chip.

For each attention site of the served models (the rows of ``SITES``: bf16,
CFG batch 2, q/k/v in the projections' own (B, S, H·D) layout) this times
one call of ``ops/flash_attention.py::flash_attention`` under the shape
rule's plan, checks it against ``xla_attention`` on the same device, and,
with ``--old-root DIR`` (another checkout of this repo, say the parent
commit unpacked by ``git archive`` into ``chip_checkout/parent``), times
that checkout's kernel on the same arrays: its entry points as the model
called them, head-folding transposes included. ``--sweep`` times further
block choices for the sites it names.

    python tools/flash_timing.py --old-root chip_checkout/parent \
        --sweep 'sd15_l0_self:512x4096,128x4096;sd15_l1_self:512x1024'

A call is timed inside one jitted ``fori_loop`` of ``--calls`` dependent
calls (the output is the next call's q), best of ``--repeats``: what a
call costs the device, not what it costs to launch. One JSON object a
line, also appended to ``chiprun_out/flash_timing.jsonl``. Needs a TPU
unless ``--interpret`` (a rehearsal of the control flow at tiny sizes:
no time it prints is a measurement).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cassmantle_tpu.ops import flash_attention as fa  # noqa: E402
from cassmantle_tpu.ops.attention import xla_attention  # noqa: E402

#: site -> (S_q, S_k, heads, head_dim): every shape the kernel is served
SITES = {
    "sd15_l0_self": (4096, 4096, 8, 40),
    "sd15_l0_cross": (4096, 77, 8, 40),
    "sd15_l1_self": (1024, 1024, 8, 80),
    "sd15_l1_cross": (1024, 77, 8, 80),
    "sd15_vae_mid": (4096, 4096, 1, 512),
    "sdxl_l1_self": (4096, 4096, 10, 64),
    "sdxl_l1_cross": (4096, 77, 10, 64),
    "sdxl_l2_self": (1024, 1024, 20, 64),
    "sdxl_l2_cross": (1024, 77, 20, 64),
    "sdxl_vae_mid": (16384, 16384, 1, 512),
}


def load_old(root: str):
    """``ops/flash_attention.py`` of another checkout, under its own
    module name (it imports only ``ops/platform.py``, taken from here)."""
    path = os.path.join(root, "cassmantle_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location("old_flash_attention", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def old_entry(old, q, k):
    """The other checkout's entry point for this shape, as its dispatch in
    ``ops/attention.py`` chose it; None where it left the shape to XLA.
    A checkout from before PR 27 has three predicates and two entry
    points; one from after it has ``flash_plan``."""
    if hasattr(old, "flash_plan"):
        return None if old.flash_plan(q, k) is None else old.flash_attention
    if old.flash_attention_ok(q, k):
        return old.flash_attention
    if old.flash_wide_ok(q, k):
        return lambda q, k, v, **kw: old.flash_attention(
            q, k, v, block_q=old.WIDE_BLOCK, block_k=old.WIDE_BLOCK, **kw)
    if old.flash_cross_ok(q, k):
        return old.flash_cross_attention
    return None


def time_call(attend, q, k, v, heads, calls, repeats):
    """ms a call of ``attend`` on (B, S, H·D) arrays, and seconds to
    compile. The arrays take the model's route: a free reshape to
    (B, S, H, D) before the call and back after it."""
    def split(t):
        return t.reshape(t.shape[:-1] + (heads, t.shape[-1] // heads))

    def step(_, qkv):
        # k and v trade places each call, so that nothing done to them
        # beside the kernel can be hoisted out of the loop
        q, k, v = qkv
        return attend(split(q), split(k), split(v)).reshape(q.shape), v, k

    def chain(q, k, v):
        return jax.lax.fori_loop(0, calls, step, (q, k, v))[0]

    start = time.perf_counter()
    run = jax.jit(chain).lower(q, k, v).compile()
    compile_s = time.perf_counter() - start
    run(q, k, v).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        run(q, k, v).block_until_ready()
        best = min(best, time.perf_counter() - start)
    return 1e3 * best / calls, compile_s


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sites", default=",".join(SITES))
    ap.add_argument("--old-root", default=None)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sweep", default="",
                    help="further block choices to time, as "
                         "'site:BQxBK,BQxBK;site:...'")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearsal off the chip; S cut to 1024")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        print(f"no TPU here ({device.platform}): nothing to time",
              file=sys.stderr)
        return 1
    old = load_old(args.old_root) if args.old_root else None
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "flash_timing.jsonl"), "a")

    def emit(line):
        line["device"] = f"{device.platform}:{device.device_kind}"
        text = json.dumps(line)
        print(text, flush=True)
        sink.write(text + "\n")
        sink.flush()

    sweep = {}
    for part in filter(None, args.sweep.split(";")):
        site, _, specs = part.partition(":")
        sweep[site] = [tuple(int(n) for n in spec.split("x"))
                       for spec in specs.split(",")]
    kw = {"interpret": True} if args.interpret else {}
    for site in filter(None, args.sites.split(",")):
        sq, sk, heads, d = SITES[site]
        if args.interpret:
            sq, sk = min(sq, 1024), min(sk, 1024)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q, k, v = (
            jax.random.normal(key, (args.batch, s, heads * d), jnp.bfloat16)
            for key, s in zip(keys, (sq, sk, sk)))
        q4, k4, v4 = (t.reshape(t.shape[:-1] + (heads, d))
                      for t in (q, k, v))
        # the reference holds the whole score matrix in f32: not at 16k²
        ref = None if sq * sk > 1 << 25 else xla_attention(
            *(t.astype(jnp.float32) for t in (q4, k4, v4)))

        def gap(out):
            if ref is None:
                return None
            return float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))

        base = {"site": site, "shape": [args.batch, sq, sk, heads, d]}
        plan = fa.flash_plan(q4, k4)
        plans = [plan] + [
            plan._replace(block_q=bq, block_k=min(bk, -(-sk // 128) * 128))
            for bq, bk in sweep.get(site, [])]
        entry = old_entry(old, q4, k4) if old else None
        if entry is not None:
            line = dict(base, kernel="old")
            attend = lambda q, k, v: entry(q, k, v, **kw)  # noqa: E731
            line["ms"], line["compile_s"] = time_call(
                attend, q, k, v, heads, args.calls, args.repeats)
            line["max_abs_gap_to_xla_f32"] = gap(attend(q4, k4, v4))
            emit(line)
        for p in plans:
            line = dict(base, kernel="new", plan=list(p))
            try:
                attend = lambda q, k, v, p=p: fa.flash_attention(  # noqa: E731
                    q, k, v, plan=p, **kw)
                line["ms"], line["compile_s"] = time_call(
                    attend, q, k, v, heads, args.calls, args.repeats)
                line["max_abs_gap_to_xla_f32"] = gap(attend(q4, k4, v4))
            except Exception as exc:  # a block choice the compiler refuses
                line["error"] = str(exc)[:400]
            emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
