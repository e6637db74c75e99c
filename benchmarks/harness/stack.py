"""The system under test: the serving stack as ``server/app.py`` builds it,
with the benchmark's recorders at the two seams the program offers."""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from .manifest import resolve


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
    cfg = resolve(config["rehearsal_factory" if rehearsal else "factory"])()
    for group, fields in config.get("overrides", {}).items():
        cfg = cfg.replace(**{group: dataclasses.replace(
            getattr(cfg, group), **fields)})
    return cfg


def program_sizes(cfg, config: dict) -> dict:
    """The program's configuration under the keys the file's ``sizes``
    state, each read from the program's dataclass by that name: what the
    file is checked against, and what a rehearsal at the tiny size runs
    on. A group is the attribute of ``cfg.models`` of its name (the prompt
    LM's: the one ``prompt_lm.program_config`` names; ``sampler``:
    ``cfg.sampler``). A stated key the program lacks is an error, unless
    the file lists it under ``own_sizes`` as the benchmark's own: those
    are taken from the file."""
    lm = config["prompt_lm"]
    own = set(config.get("own_sizes", ()))

    def read(obj, key: str, path: str):
        if not hasattr(obj, key):
            raise SystemExit(
                f"the configuration's file states {path}, which the "
                f"program's {type(obj).__name__} lacks; if it is the "
                f"benchmark's own, list it under own_sizes")
        value = getattr(obj, key)
        return list(value) if isinstance(value, tuple) else value

    def holder(group: str):
        if group == "sampler":
            return cfg.sampler
        return read(cfg.models, lm["program_config"]
                    if group == lm["sizes"] else group, group)

    sizes = {}
    for group, stated in config["sizes"].items():
        if group in own:
            sizes[group] = stated
        elif isinstance(stated, dict):
            obj = holder(group)
            sizes[group] = {
                key: value if f"{group}.{key}" in own
                else read(obj, key, f"{group}.{key}")
                for key, value in stated.items()}
        else:
            sizes[group] = read(cfg.models, group, group)
    return sizes


def check_sizes(stated: dict, running: dict, path: str = "") -> list:
    """Every size the file states has to be the one that runs."""
    wrong = []
    for key, want in stated.items():
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
