#!/usr/bin/env python3
"""Time the sparse LM's decode walk alone on the chip, in each form.

One expert layer of a served cut, its widths taken from the configuration
(``--config qwen3next``: ``qwen3next_game_config``, 128 held experts of
512, D 2048, F 512, top-10; ``--config lfm2``: ``lfm2_game_config``, all
64 held, D 2048, F 1536, top-4; bfloat16) at 1, 2 and 4 rows: the
XLA form (``models/moe.py::HeldExperts._walk``, a ``fori_loop`` of
dependent products, ``xla``) against the Pallas kernel
(``ops/moe_walk.py``, ``kernel``), what the scan and the routing around
them cost with no walk at all (``scan``), and every slot's expert in
float32 at full precision (``reference``: put first, it is what the
others' outputs are held against). Every step routes each row to
``top_k`` distinct experts of all, as the router does: under
``qwen3next`` about a quarter of the assignments land and the count
differs from step to step, under ``lfm2`` every one lands.

    python tools/moe_walk_timing.py --rows 1,4 --forms reference,xla,kernel

A form is timed inside one jitted ``scan`` of ``--steps`` dependent layer
calls (the output is added to the next call's input), warm, median of
``--repeats``: what a call costs the device between its neighbours, not
what it costs to launch. Each form's outputs are also held against the
first form's on the same inputs on the same device. One JSON object a
line, also appended to ``chiprun_out/moe_walk_timing.jsonl``. Needs a TPU unless
``--rehearse`` (the control flow at a tiny size with the kernel
interpreted: no time it prints is a measurement).

``--dispatch`` times one whole ``greedy_decode`` dispatch of the served
LM instead (its layers, 96 new tokens, seeded weights, ``--rows`` rows in
the ``--bucket`` prompt bucket, row r prompted with line r of
``data/seeds.txt``), the expert layers' rule answered here, in
the tool: what the walk costs between its neighbours, which the compiler
schedules around it otherwise than around a layer alone (PR 32: the
kernel won 13 us a layer call alone and lost 12 ms a dispatch until the
compiler was told what a call reads).
"""

from __future__ import annotations

import argparse
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
import numpy as np  # noqa: E402

from cassmantle_tpu import config as configs  # noqa: E402
from cassmantle_tpu.models.moe import HeldExperts, walk_operands  # noqa: E402
from cassmantle_tpu.ops.moe_walk import moe_walk, walk_plan  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmarks/harness/peaks.py)


def family_of(args):
    """(FrameworkConfig, its prompt LM's ``LMFamily``) of ``--config``,
    at the tiny size under ``--rehearse``: the family is found as
    ``PromptGenerator`` finds it."""
    from cassmantle_tpu.serving.pipeline import _lm_families

    game, tiny = {
        "qwen3next": (configs.qwen3next_game_config,
                      configs.test_qwen3next_config),
        "lfm2": (configs.lfm2_game_config, configs.test_lfm2_config),
    }[args.config]
    cfg = (tiny if args.rehearse else game)()
    return cfg, next(f for f in _lm_families()
                     if getattr(cfg.models, f.name) is not None)


def layer_of(args) -> tuple:
    """(the expert layer, D)."""
    if args.rehearse:
        return HeldExperts(num_experts=32, experts_held=8, first_expert=0,
                           top_k=4, intermediate=256,
                           dtype=jnp.bfloat16), 512
    cfg, family = family_of(args)
    m = getattr(cfg.models, family.name)
    return HeldExperts(
        num_experts=m.num_experts, experts_held=m.experts_held,
        first_expert=m.first_expert, top_k=m.num_experts_per_tok,
        intermediate=m.moe_intermediate_size,
        dtype=jnp.dtype(m.dtype)), m.hidden_size


def routing(layer: HeldExperts, rows: int, steps: int, seed: int):
    """(expert, weight, landed), each (steps, rows · top_k): ``top_k``
    distinct experts of all a row, weights normalised over them."""
    rs = np.random.RandomState(seed)
    top_i = np.stack([
        np.stack([rs.permutation(layer.num_experts)[:layer.top_k]
                  for _ in range(rows)]) for _ in range(steps)])
    top_p = rs.rand(steps, rows, layer.top_k).astype(np.float32) + 0.1
    top_p /= top_p.sum(-1, keepdims=True)
    local = top_i - layer.first_expert
    landed = (local >= 0) & (local < layer.experts_held)
    flat = (steps, rows * layer.top_k)
    return (jnp.asarray(np.clip(local, 0, layer.experts_held - 1).reshape(
        flat), jnp.int32), jnp.asarray(top_p.reshape(flat)),
        jnp.asarray(landed.reshape(flat)))


def walk_of(layer: HeldExperts, form: str, rehearse: bool):
    """(x, gate_up, down, expert, weight, landed) -> (T, D) float32; the
    walk's operands are built from the slots as ``HeldExperts`` builds
    them (``walk_operands``), inside the timed call in every form."""
    def walk(x, gate_up, down, expert, weight, landed):
        rows, held = x.shape[0], layer.experts_held
        load = jnp.zeros((held,), jnp.int32).at[expert].add(
            landed.astype(jnp.int32))
        experts, combine = walk_operands(
            *(a.reshape(rows, -1) for a in (expert, weight, landed)), load)
        count = jnp.sum(load > 0)
        if form == "scan":
            return x.astype(jnp.float32) * (combine[:, experts[0]][:, None]
                                            + count)
        if form == "reference":
            # every slot's expert in float32 at full precision, the
            # landed ones summed: what both forms are held against
            hi = jax.lax.Precision.HIGHEST
            rows = jnp.repeat(x.astype(jnp.float32), layer.top_k, axis=0)
            gu = jnp.einsum("nd,ndf->nf", rows, gate_up[expert].astype(
                jnp.float32), precision=hi)
            f = layer.intermediate
            h = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
            y = jnp.einsum("nf,nfd->nd", h.astype(jnp.float32),
                           down[expert].astype(jnp.float32), precision=hi)
            y = jnp.where(landed[:, None], weight[:, None] * y, 0.0)
            return y.reshape(x.shape[0], layer.top_k, -1).sum(axis=1)
        if form == "xla":
            return layer._walk(x, gate_up, down, experts, combine, count)
        return moe_walk(x, gate_up, down, experts, combine, count,
                        top_k=layer.top_k, interpret=rehearse)

    return walk


def dispatches(args, out_path: str) -> int:
    """One whole LM dispatch under each form of the walk."""
    from cassmantle_tpu.models import moe
    from cassmantle_tpu.ops.decode import greedy_decode, make_apply_pair

    cfg, family = family_of(args)
    mcfg = getattr(cfg.models, family.name)
    model, cache_stats = family.model(mcfg), family.cache_stats
    dtype = jnp.dtype(mcfg.dtype)
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(dtype), model.init(
            key, jnp.zeros((1, 8), jnp.int32))))(
                jax.random.PRNGKey(args.seed))
    new_tokens = cfg.sampler.max_new_tokens
    # a row its own title, as the rooms of a served dispatch have: rows
    # of one title route alike and share every expert read
    with open(os.path.join(ROOT, "data", "seeds.txt"), "rb") as fh:
        titles = [np.frombuffer(line.strip(), np.uint8)
                  for line in fh if line.strip()]
    pair = make_apply_pair(model)
    for rows in (int(r) for r in args.rows.split(",")):
        ids = np.full((rows, args.bucket), 258, np.int32)
        rows_titles = [t[:args.bucket] for t in titles[:rows]]
        for row, title in enumerate(rows_titles):
            ids[row, :len(title)] = title
        lens = [len(t) for t in rows_titles]
        operands = (
            jnp.asarray(ids), jnp.asarray(lens, jnp.int32),
            jax.random.PRNGKey(0), new_tokens, 257, 0.0, 40)
        for form in args.forms.split(","):
            moe.on_tpu = lambda form=form: form == "kernel"
            jax.clear_caches()

            def dispatch():
                return jax.block_until_ready(greedy_decode(
                    pair, params, *operands,
                    row_mask=jnp.ones((rows,), bool),
                    cache_stats=cache_stats,
                    # the served program: the offsets are an operand
                    position_offset=jnp.zeros((rows,), jnp.int32)))

            t0 = time.perf_counter()
            _, _, stats = dispatch()
            compile_s = time.perf_counter() - t0
            seconds = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                dispatch()
                seconds.append(time.perf_counter() - t0)
            line = {
                "config": args.config, "form": form, "rows": rows,
                "bucket": args.bucket,
                "new_tokens": new_tokens,
                "ms_a_dispatch": 1e3 * statistics.median(seconds),
                "ms_min_max": [1e3 * min(seconds), 1e3 * max(seconds)],
                "assignments_held": int(stats["assignments_held"]),
                "experts_touched": int(stats["experts_touched"]),
                "walk_reads_saved": int(stats["walk_reads_saved"]),
                "compile_s": compile_s,
                "device": jax.devices()[0].device_kind,
                "rehearsal": args.rehearse,
            }
            print(json.dumps(line), flush=True)
            with open(out_path, "a") as fh:
                fh.write(json.dumps(line) + "\n")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", choices=["qwen3next", "lfm2"],
                        default="qwen3next")
    parser.add_argument("--dispatch", action="store_true")
    parser.add_argument("--bucket", type=int, default=64)
    parser.add_argument("--rows", default="1,2,4")
    parser.add_argument("--forms", default="xla,kernel,scan")
    parser.add_argument("--steps", type=int, default=64)
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    if not args.rehearse and jax.default_backend() != "tpu":
        print("needs a TPU (or --rehearse)", file=sys.stderr)
        return 2
    out_path = os.path.join(ROOT, "chiprun_out", "moe_walk_timing.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    if args.dispatch:
        return dispatches(args, out_path)
    layer, d = layer_of(args)
    held, f = layer.experts_held, layer.intermediate
    plan = walk_plan(d, f, jnp.dtype(layer.dtype).itemsize)
    k_gu, k_dn, k_x = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    gate_up = (jax.random.normal(k_gu, (held, d, 2 * f), jnp.float32)
               * d ** -0.5).astype(layer.dtype)
    down = (jax.random.normal(k_dn, (held, f, d), jnp.float32)
            * f ** -0.5).astype(layer.dtype)
    assignment_bytes = (gate_up[0].size + down[0].size) * gate_up.itemsize

    for rows in (int(r) for r in args.rows.split(",")):
        expert, weight, landed = routing(layer, rows, args.steps, args.seed)
        x0 = jax.random.normal(k_x, (rows, d), jnp.float32).astype(
            layer.dtype)
        want = None
        for form in args.forms.split(","):
            walk = walk_of(layer, form, args.rehearse)

            @jax.jit
            def chain(x, gate_up, down, expert, weight, landed, walk=walk):
                def step(x, per):
                    out = walk(x, gate_up, down, *per)
                    return (x + 0.01 * out).astype(x.dtype), out
                return jax.lax.scan(step, x, (expert, weight, landed))

            operands = (x0, gate_up, down, expert, weight, landed)
            t0 = time.perf_counter()
            _, outs = jax.block_until_ready(chain(*operands))
            compile_s = time.perf_counter() - t0
            seconds = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(chain(*operands))
                seconds.append(time.perf_counter() - t0)
            # the first step's inputs are the same in every form; later
            # steps' differ by what the form's rounding fed back
            first, whole = np.asarray(outs[0]), np.asarray(outs)
            if want is None:
                want, want_whole = first, whole
            mean_landed = float(jnp.mean(jnp.sum(landed, axis=1)))
            # distinct experts a call: what a walk has to read
            mean_read = float(np.mean([
                len(set(np.asarray(e)[np.asarray(l)]))
                for e, l in zip(expert, landed)]))
            call_us = 1e6 * statistics.median(seconds) / args.steps
            line = {
                "config": args.config, "form": form, "rows": rows,
                "steps": args.steps, "plan": plan and list(plan),
                "landed_a_call": mean_landed,
                "experts_a_call": mean_read,
                "us_a_call": call_us,
                "us_an_expert_read": call_us / mean_read,
                "floor_us_a_call": 1e6 * mean_read * assignment_bytes
                / HBM_BYTES_PER_S,
                "max_abs_diff_from_first_form": float(
                    np.abs(first - want).max()),
                "max_abs_diff_over_the_chain": float(
                    np.abs(whole - want_whole).max()),
                "out_abs_max": float(np.abs(want_whole).max()),
                "compile_s": compile_s,
                "device": jax.devices()[0].device_kind,
                "rehearsal": args.rehearse,
            }
            print(json.dumps(line), flush=True)
            with open(out_path, "a") as fh:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
