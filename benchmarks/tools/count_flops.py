#!/usr/bin/env python3
"""Print the benchmark's own FLOP counts for a configuration, from shapes
alone (nothing runs, no weights are made):

    JAX_PLATFORMS=cpu python3 benchmarks/tools/count_flops.py sd15_game
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    from benchmarks.harness import compare, flops, rooflines
    from benchmarks.harness.manifest import load_json
    from benchmarks.harness.stack import framework_config
    from benchmarks.harness.weights import WeightBook
    from cassmantle_tpu.ops import scorer
    from cassmantle_tpu.serving import pipeline, sdxl

    name = sys.argv[1] if len(sys.argv) > 1 else "sd15_game"
    config = load_json("benchmarks", "configs", name + ".json")
    cfg = framework_config(config, False)
    book = WeightBook(0)

    def shapes_only(model, rng_seed, *args, cache_path=None, **_kw):
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), *args)
        book.trees[book.name_of(cache_path)] = tree
        return tree

    pipeline.init_params_cached = sdxl.init_params_cached = shapes_only
    scorer.init_params_cached = shapes_only
    if cfg.models.clip_text_2 is not None:
        sdxl.SDXLPipeline._publish_params = lambda self: None
        sdxl.SDXLPipeline(cfg, None)
    else:
        pipeline.Text2ImagePipeline._publish_params = lambda self: None
        pipeline.Text2ImagePipeline(cfg, None)
    pipeline.PromptGenerator(cfg, None)
    scorer.EmbeddingScorer(cfg.models.minilm, table=None)
    sizes = config["sizes"]
    names = compare.named(config, sizes)
    trees = compare.reference_trees(book.trees, sizes, names)
    out = flops.image_flops(trees, sizes, names)
    out["lm_24_prompt_96_new"] = flops.lm_flops(
        trees, names, 24, sizes["sampler"]["max_new_tokens"])
    out["scorer_row"] = flops.scorer_row_flops(trees, sizes)
    out["attention_sites_b_sq_sk_hd"] = sorted(
        rooflines.attention_sites(trees, sizes, names))
    out["params"] = {k: sum(int(x.size) for x in jax.tree_util.tree_leaves(v))
                     for k, v in trees.items()}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
