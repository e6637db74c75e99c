#!/usr/bin/env python3
"""Rehearsal 3 for a configuration's image sampler: compile the pipeline's
whole sampler program at the real size, batch 1, for a described v5e chip.
Nothing runs and no chip is needed; what the chip's compiler would refuse,
it refuses here.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_sdxl_v5e.py [factory]

``factory`` names the FrameworkConfig, ``cassmantle_tpu.config:sdxl_config``
unless given (``cassmantle_tpu.config:FrameworkConfig`` is SD1.5).

Prints ``memory_analysis()`` and the count of ``tpu_custom_call`` (the
Pallas kernels) in the compiled program. The param trees are shapes from
``jax.eval_shape``; kernel dispatch is steered to its TPU branch here, in
the script, not through an option of the program.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.stack import framework_config
    from cassmantle_tpu.ops import (
        attention,
        flash_attention,
        fused_conv,
        quant_matmul,
    )
    from cassmantle_tpu.serving import pipeline, sdxl

    name = (sys.argv[1] if len(sys.argv) > 1
            else "cassmantle_tpu.config:sdxl_config")
    cfg = framework_config({"factory": name}, False)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shapes_only(model, rng_seed, *args, cache_path=None, cast_to=None,
                    transform=None):
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), *args)

        def leaf(a):
            dtype = (jnp.dtype(cast_to) if cast_to and jnp.issubdtype(
                a.dtype, jnp.floating) else a.dtype)
            return jax.ShapeDtypeStruct(a.shape, dtype, sharding=chip)

        return jax.tree_util.tree_map(leaf, tree)

    for module in (attention, flash_attention, fused_conv, quant_matmul):
        module.on_tpu = lambda: True
    pipeline.init_params_cached = sdxl.init_params_cached = shapes_only
    if cfg.models.clip_text_2 is not None:
        pipe = sdxl.SDXLPipeline(cfg, None, mesh=None)
    else:
        pipe = pipeline.Text2ImagePipeline(cfg, None, mesh=None)
    ids = jax.ShapeDtypeStruct((1, pipe.pad_len), jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    t0 = time.perf_counter()
    lowered = pipe._sample.lower(pipe._params, ids, ids, rng)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "config": name,
        "compile_seconds": round(time.perf_counter() - t0, 1),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "generated_code_bytes": mem.generated_code_size_in_bytes,
        "tpu_custom_call": text.count("tpu_custom_call"),
        "tpu_custom_call_lowered": lowered.as_text().count(
            "tpu_custom_call"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
