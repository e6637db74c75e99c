"""Weights from ``--seed``, made on the device in one jitted call a model.

The program makes random weights with ``init_params_cached``: the model's
``init`` run op by op on the host from a fixed seed, an fp32 copy written
to ``.param_cache`` (3.4 GB for SD1.5's UNet, 14 GB for SDXL), read back
and cast on every start. A benchmark run wants the weights to follow
``--seed``, set-up short and little written to disk. So while the serving
stack is built, the name ``init_params_cached`` in the two pipeline modules
points here: the same ``model.init``, jitted onto the default device with a
key folded from ``--seed``, cast to the served dtype inside the jit.

The scorer's weights are made the same way, and its int8 word table is
built again from them at set-up (runner.Run.build_word_table): the
committed table (data/embed_table.bin) arms on a signature that names the
init's seed and not its values, and under the installed jax its rows are
uncorrelated with what that init now makes (PERF.md section 7).

Every tree made is kept in ``WeightBook`` under the model's name, so the
plain reference reads the weights from the benchmark and not from the
program's objects.
"""

from __future__ import annotations

import contextlib
import os
import zlib

import jax
import jax.numpy as jnp


class WeightBook:
    """name -> param tree, as handed to the program."""

    def __init__(self, seed: int) -> None:
        self.seed = int(seed)
        self.trees: dict = {}

    def name_of(self, cache_path) -> str:
        # "<dir>/unet-0123abcd.safetensors" -> "unet"
        base = os.path.basename(cache_path or "model")
        return base.rsplit("-", 1)[0]

    def seeded_init(self, model, rng_seed, *sample_args, cache_path=None,
                    cast_to=None, transform=None):
        """Stand-in for ``init_params_cached``: same signature."""
        from cassmantle_tpu.ops.attention import xla_only

        name = self.name_of(cache_path)
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.seed % (2 ** 31)),
            zlib.crc32(f"{name}:{rng_seed}".encode()) % (2 ** 31))

        def make(k):
            tree = model.init(k, *sample_args)
            if cast_to:
                dtype = jnp.dtype(cast_to)
                tree = jax.tree_util.tree_map(
                    lambda a: a.astype(dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)
            return tree

        with xla_only():
            tree = jax.jit(make)(key)
        if transform is not None:
            tree = transform(tree)
        self.trees[name] = tree
        return tree

    @contextlib.contextmanager
    def installed(self):
        """Point the pipelines' ``init_params_cached`` at this book while
        the serving stack is built."""
        from cassmantle_tpu.ops import scorer
        from cassmantle_tpu.serving import pipeline, sdxl

        saved = [(m, m.init_params_cached) for m in (pipeline, sdxl, scorer)]
        for module, _ in saved:
            module.init_params_cached = self.seeded_init
        try:
            yield self
        finally:
            for module, fn in saved:
                module.init_params_cached = fn
