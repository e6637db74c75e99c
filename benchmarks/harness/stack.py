"""The system under test: the serving stack as ``server/app.py`` builds it,
with the benchmark's recorders at the two seams the program offers."""

from __future__ import annotations

import dataclasses
import importlib
import threading
import time

import jax
import numpy as np


class Abandoned(Exception):
    """An image dispatch reached after the window closed."""


class Book:
    """What the timed calls produced, kept on the benchmark's side."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.images: list = []    # (t_done, prompts, seed, uint8 batch)
        self.decodes: list = []   # (t_done, texts, tokens, lengths)
        self.scores: list = []    # (due, pairs, scores | None, latency_s,
        #                            on_device)
        self.closed = False


class RecordingT2I:
    """Thin wrapper handed to ``TPUContentBackend(t2i=...)``: records each
    image dispatch's (prompts, seed) and output, names it in the
    profiler's trace, and turns away dispatches after the close."""

    def __init__(self, inner, book: Book) -> None:
        self.inner = inner
        self.book = book

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def generate(self, prompts, seed: int = 0, deadline_s=None):
        if self.book.closed:
            raise Abandoned("window closed")
        with jax.profiler.TraceAnnotation("bench.image_dispatch"):
            out = self.inner.generate(prompts, seed=seed,
                                      deadline_s=deadline_s)
        with self.book.lock:
            self.book.images.append(
                (time.perf_counter(), list(prompts), int(seed), out))
        return out


def record_decodes(prompt_gen, book: Book) -> None:
    """Record token ids where they are produced: the queue's handler only
    returns text, and the byte tokenizer's decode drops ids >= 256."""
    inner = prompt_gen.decode_ids_batch

    def decode_ids_batch(seed_texts, max_new_tokens=None, seed=None):
        with jax.profiler.TraceAnnotation("bench.lm_dispatch"):
            tokens, lengths = inner(seed_texts, max_new_tokens, seed)
            host = (np.asarray(tokens), np.asarray(lengths))
        with book.lock:
            book.decodes.append(
                (time.perf_counter(), list(seed_texts)) + host)
        return tokens, lengths

    prompt_gen.decode_ids_batch = decode_ids_batch


def framework_config(config: dict, rehearsal: bool):
    """The FrameworkConfig a configuration file names."""
    target = config["rehearsal_factory" if rehearsal else "factory"]
    module, _, attr = target.partition(":")
    cfg = getattr(importlib.import_module(module), attr)()
    for group, fields in config.get("overrides", {}).items():
        cfg = cfg.replace(**{group: dataclasses.replace(
            getattr(cfg, group), **fields)})
    return cfg


def program_sizes(cfg) -> dict:
    """The program's configuration in the shape of a config file's
    ``sizes``: what the reference is checked against, never what it uses."""
    m, s = cfg.models, cfg.sampler

    def fields(obj, names):
        out = {}
        for n in names:
            v = getattr(obj, n)
            out[n] = list(v) if isinstance(v, tuple) else v
        return out

    sizes = {
        "clip_text": fields(m.clip_text, (
            "vocab_size", "hidden_size", "intermediate_size", "num_layers",
            "num_heads", "max_positions", "hidden_act")),
        "unet": fields(m.unet, (
            "base_channels", "channel_mults", "attention_levels",
            "transformer_depth", "blocks_per_level", "num_heads",
            "context_dim", "time_embed_dim", "addition_embed_dim", "dtype")),
        "vae": fields(m.vae, (
            "base_channels", "channel_mults", "blocks_per_level",
            "scaling_factor", "dtype")),
        "gpt2": fields(m.gpt2, (
            "vocab_size", "hidden_size", "num_layers", "num_heads",
            "max_positions", "dtype")),
        "minilm": fields(m.minilm, (
            "vocab_size", "hidden_size", "intermediate_size", "num_layers",
            "num_heads", "max_positions", "dtype")),
        "sampler": fields(s, (
            "kind", "num_steps", "guidance_scale", "eta", "image_size",
            "negative_prompt", "max_new_tokens", "prompt_pad_len",
            "text_temperature")),
        "param_dtype": m.param_dtype,
    }
    if m.clip_text_2 is not None:
        sizes["clip_text_2"] = fields(m.clip_text_2, tuple(
            sizes["clip_text"]))
    return sizes


def check_sizes(stated: dict, running: dict, path: str = "") -> list:
    """Every size the file states has to be the one that runs."""
    wrong = []
    for key, want in stated.items():
        if key not in running:
            continue  # benchmark-side sizes (seq_len, buckets)
        have = running[key]
        if isinstance(want, dict):
            wrong += check_sizes(want, have, f"{path}{key}.")
        elif want != have:
            wrong.append(f"{path}{key}: file {want!r}, program {have!r}")
    return wrong


def build_service(cfg, book: Book, weights):
    """InferenceService(cfg) with the recording seams; weights from the
    benchmark's book while it is built."""
    from cassmantle_tpu.serving.pipeline import (
        Text2ImagePipeline,
        TPUContentBackend,
    )
    from cassmantle_tpu.serving.service import (
        InferenceService,
        default_serving_mesh,
    )
    from cassmantle_tpu.utils import jit_sentinel

    jit_sentinel.enable_sentinel()
    mesh = default_serving_mesh(cfg)
    with weights.installed():
        if cfg.models.clip_text_2 is not None:
            from cassmantle_tpu.serving.sdxl import SDXLPipeline

            pipe = SDXLPipeline(cfg, None, mesh=mesh)
        else:
            pipe = Text2ImagePipeline(cfg, None, mesh=mesh)
        backend = TPUContentBackend(cfg, mesh=mesh,
                                    t2i=RecordingT2I(pipe, book))
        service = InferenceService(cfg, mesh=mesh, backend=backend)
    # InferenceService hangs its supervisor on backend.t2i: pass it on
    pipe.supervisor = service.supervisor
    record_decodes(backend.prompt_gen, book)
    return service
