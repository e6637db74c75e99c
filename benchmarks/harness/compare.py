"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
what the timed calls returned, drawn from the seed, is held against the
plain reference (harness/reference.py) run on the same inputs with the
benchmark's own weights:

  image_mean_abs_diff   served uint8 image against the reference's, mean
                        absolute difference in levels of 255, worst image
  lm_logit_gap          widest gap by which a served greedy token's logit
                        lies below the reference's best, over the prompts
                        with their served tokens
  score_abs_diff        served similarity of a phrase guess (encoded on
                        the device) against the reference cosine, worst call
  table_score_abs_diff  the same for single-word guesses, which the int8
                        word table serves on the host; its control is the
                        reference's embeddings rounded to int4
  compiles_in_window    jit compiles the window saw; the limit is 0

Each has its limit in the configuration's file (``limits``), set between
the program's readings and the control's (PERF.md section 2). The control
is the same code with ``precision_mode("fp8")``.

The prompt LM's reference and the image's trajectory are the functions the
configuration's file names (``named``): no family of LM and no kind of
sampler is known here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as ref
from .manifest import resolve


def _tree(trees: dict, prefix: str):
    for name in sorted(trees):
        if name == prefix or (prefix == "vae" and name.startswith("vae")
                              and "enc" not in name):
            return trees[name]
    raise KeyError(f"no weights booked for {prefix!r}: {sorted(trees)}")


def named(config: dict, sizes: dict) -> dict:
    """What a configuration's file gives by name, resolved at set-up.

    ``prompt_lm``: ``sizes`` (the key of the LM's sizes under ``sizes``),
    ``weights`` (the name its tree is booked under) and ``reference``, a
    ``module:function`` with the contract ``f(params, ids (B, S),
    positions (B, S), sizes) -> logits (B, S, V)``.
    ``image_trajectory``: sampler kind -> ``module:function`` with the
    contract ``f(guided, x_T, sampler) -> x_0``; reference.py holds one
    of each and says what they owe.
    A sampler kind for which the file names none is an error here, not
    another kind's reference under its name."""
    lm = config["prompt_lm"]
    kind = sizes["sampler"]["kind"]
    if kind not in config["image_trajectory"]:
        raise SystemExit(
            f"the configuration's file names no image trajectory for "
            f"sampler kind {kind!r}: it has "
            f"{sorted(config['image_trajectory'])}")
    return {"lm_sizes": sizes[lm["sizes"]], "lm_weights": lm["weights"],
            "lm_logits": resolve(lm["reference"]),
            "trajectory": resolve(config["image_trajectory"][kind])}


def reference_trees(trees: dict, sizes: dict, names: dict) -> dict:
    out = {"clip_text": _tree(trees, "clip_text"),
           "vae": _tree(trees, "vae"),
           "lm": _tree(trees, names["lm_weights"]),
           "minilm": _tree(trees, "minilm")}
    if "clip_text_2" in sizes:
        out["clip_text_2"] = _tree(trees, "clip_text_2")
        out["unet"] = _tree(trees, "unet_xl")
    else:
        out["unet"] = _tree(trees, "unet")
    return out


class Reference:
    """Jitted pieces of the plain reference in one precision mode."""

    def __init__(self, trees: dict, sizes: dict, names: dict,
                 mode: str = "f32") -> None:
        self.trees, self.sizes, self.mode = trees, sizes, mode
        self.names = names
        self._jits: dict = {}

    def _jit(self, name, fn):
        """``fn`` in this reference's precision, block by block: the model
        runs eagerly around its repeated blocks, each a program of its
        own (reference.block_by_block)."""

        def run(*args):
            with ref.precision_mode(self.mode), \
                    ref.block_by_block(self._jits):
                return fn(*args)

        return run

    # -- image ----------------------------------------------------------------
    def _conditioning(self, prompt: str):
        """(context (2, S, D), addition (2, A) | None), uncond row first."""
        sz, s = self.sizes, self.sizes["sampler"]
        texts = [s["negative_prompt"], prompt]
        ids = ref.clip_ids(texts, s["prompt_pad_len"],
                           sz["clip_text"]["vocab_size"])
        enc1 = self._jit("clip1", lambda p, i: ref.clip_text(
            p, i, sz["clip_text"]))(self.trees["clip_text"], ids)
        if "clip_text_2" not in sz:
            return enc1["hidden"], None
        enc2 = self._jit("clip2", lambda p, i: ref.clip_text(
            p, i, sz["clip_text_2"]))(self.trees["clip_text_2"], ids)
        context = jnp.concatenate(
            [enc1["penultimate"], enc2["penultimate"]], axis=-1)
        size = float(s["image_size"])
        time_ids = jnp.asarray([size, size, 0.0, 0.0, size, size], ref.F32)
        dim = (sz["unet"]["addition_embed_dim"]
               - sz["clip_text_2"]["hidden_size"]) // 6
        emb = ref.timestep_embedding(time_ids, dim).reshape(1, -1)
        addition = jnp.concatenate(
            [enc2["pooled"], jnp.broadcast_to(emb, (2, emb.shape[1]))],
            axis=-1)
        return context, addition

    def image(self, prompt: str, seed: int) -> np.ndarray:
        sz, s = self.sizes, self.sizes["sampler"]
        context, addition = self._conditioning(prompt)
        lat_hw = ref.latent_hw(sz)
        x = jax.random.normal(jax.random.PRNGKey(seed),
                              (1, lat_hw, lat_hw, 4), ref.F32)

        def guided(x, t):
            eps = ref.unet(self.trees["unet"], jnp.concatenate([x, x]),
                           jnp.full((2,), t), context, sz["unet"],
                           addition=addition)
            return eps[:1] + s["guidance_scale"] * (eps[1:] - eps[:1])

        x = self._jit("trajectory", self.names["trajectory"])(guided, x, s)
        decode = self._jit("vae", lambda p, z: ref.to_uint8(
            ref.vae_decode(p, z, sz["vae"])))
        return np.asarray(decode(self.trees["vae"], x))[0]

    # -- LM -------------------------------------------------------------------
    def lm_logits(self, prompt_tokens, served_tokens, bucket: int):
        """Logits that predict each served token, (n_served, V)."""
        n_p, n_g = len(prompt_tokens), len(served_tokens)
        # one shape a bucket: the tail is padding, which a causal model
        # does not let the positions before it see
        pad = bucket + self.sizes["sampler"]["max_new_tokens"] - n_p - n_g
        ids = np.asarray([list(prompt_tokens) + list(served_tokens)
                          + [0] * pad], np.int32)
        positions = np.asarray(
            [list(range(n_p)) + [bucket + i for i in range(n_g)]
             + [0] * pad], np.int32)
        fn = self._jit("lm", lambda p, i, q: self.names["lm_logits"](
            p, i, q, self.names["lm_sizes"]))
        logits = fn(self.trees["lm"], ids, positions)[0]
        return logits[n_p - 1: n_p - 1 + n_g]

    # -- scorer ---------------------------------------------------------------
    def embed(self, texts) -> np.ndarray:
        m = self.sizes["minilm"]
        ids, mask = ref.minilm_ids(texts, m["seq_len"], m["vocab_size"])
        fn = self._jit("minilm", lambda p, i, k: ref.minilm_embed(p, i, k, m))
        return np.asarray(fn(self.trees["minilm"], ids, mask))


# -- what is compared ---------------------------------------------------------

def lm_case(sizes: dict, names: dict, text: str, tokens, length: int):
    """(prompt tokens, served tokens that were really decoded, bucket): the
    served path truncates the prompt, pads it to its bucket and decodes
    token i at position bucket + i; past an end-of-text the tokens are
    forced, not decoded, and are left out."""
    g, s = names["lm_sizes"], sizes["sampler"]
    limit = g["max_positions"] - s["max_new_tokens"] - 1
    prompt = [t % g["vocab_size"] for t in ref.byte_tokens(text)[-limit:]]
    bucket = next((b for b in sizes["lm_prompt_buckets"]
                   if len(prompt) <= b
                   and b + s["max_new_tokens"] <= g["max_positions"]), limit)
    n = min(int(length) + 1, len(tokens))
    return prompt or [ref.BYTE_PAD % g["vocab_size"]], list(tokens[:n]), bucket


def logit_gaps(logits, served) -> np.ndarray:
    logits = np.asarray(logits, np.float64)
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def int4_row(unit_row: np.ndarray) -> np.ndarray:
    """The table's control: a unit embedding rounded to 4 bits a value
    (one scale a row, as the int8 table has), made a unit row again."""
    scale = np.abs(unit_row).max() / 7.0
    q = np.round(unit_row / scale) * scale
    return q / np.linalg.norm(q)


def sample(items: list, n: int, rng, keep_last: bool = True) -> list:
    if len(items) <= n:
        return list(items)
    picked = set(rng.choice(len(items) - 1, n - 1, replace=False).tolist()) \
        if keep_last else set(rng.choice(len(items), n, replace=False).tolist())
    if keep_last:
        picked.add(len(items) - 1)
    return [items[i] for i in sorted(picked)]


def compare(book, window, trees: dict, sizes: dict, names: dict, plan: dict,
            seed: int, served=None) -> dict:
    """name -> value for every number this cell compares. ``served`` is
    None for a run's own check (the book's records are the served side);
    the control passes a Reference in a lower precision, put in the
    program's place on the same inputs."""
    t0, t1 = window
    rng = np.random.RandomState(seed % (2 ** 32))
    refm = Reference(reference_trees(trees, sizes, names), sizes, names,
                     "f32")
    ctrl = served
    out: dict = {}

    images = [r for r in book.images if t0 <= r[0] <= t1]
    diffs = []
    for _, prompts, img_seed, batch in sample(images, plan["images"], rng):
        want = refm.image(prompts[0], img_seed).astype(np.float64)
        got = (ctrl.image(prompts[0], img_seed) if ctrl is not None
               else np.asarray(batch[0])).astype(np.float64)
        diffs.append(float(np.abs(got - want).mean()))
    if diffs:
        out["image_mean_abs_diff"] = max(diffs)

    rows = [(text, toks[i], int(lens[i]))
            for t, texts, toks, lens in book.decodes if t0 <= t <= t1
            for i, text in enumerate(texts)]
    rows.sort(key=lambda r: len(r[0]) + r[2])  # the longest comes last
    gaps = []
    for text, toks, length in sample(rows, plan["decodes"], rng):
        prompt, served_toks, bucket = lm_case(sizes, names, text, toks,
                                              length)
        logits = refm.lm_logits(prompt, served_toks, bucket)
        if ctrl is not None:
            served_toks = np.asarray(
                ctrl.lm_logits(prompt, served_toks, bucket)).argmax(axis=-1)
        gaps.append(float(logit_gaps(logits, np.asarray(served_toks)).max()))
    if gaps:
        out["lm_logit_gap"] = max(gaps)

    calls = [c for c in book.scores if c[2] is not None]
    half = plan["scores"] // 2
    picked = {"score_abs_diff": sample(
                  [c for c in calls if c[4]], half, rng, keep_last=False),
              "table_score_abs_diff": sample(
                  [c for c in calls if not c[4]], plan["scores"] - half, rng,
                  keep_last=False)}
    texts = sorted({t for group in picked.values() for c in group
                    for pair in c[1] for t in pair})
    if texts:
        emb = dict(zip(texts, refm.embed(texts).astype(np.float64)))
        lower = {}
        if ctrl is not None:
            lower = {"score_abs_diff": dict(zip(
                         texts, ctrl.embed(texts).astype(np.float64))),
                     "table_score_abs_diff": {
                         t: int4_row(e) for t, e in emb.items()}}
        for name, group in picked.items():
            worst = 0.0
            for _, pairs, scores, _, _ in group:
                for (g, a), s in zip(pairs, scores):
                    got = (float(lower[name][g] @ lower[name][a])
                           if ctrl is not None else float(s))
                    worst = max(worst, abs(got - float(emb[g] @ emb[a])))
            if group:
                out[name] = worst
    return out


def required_numbers(config: dict, mix: dict) -> list:
    """The numbers a cell has to compare: the configuration's file names
    them by the part of the traffic mix that produces them."""
    return [name for part, names in config["required"].items()
            if mix.get(part) for name in names]


def verdict(values: dict, limits: dict, required=()) -> tuple:
    """(correct, checks). A value with no limit on file is an error; a
    required number that was not compared (nothing recorded, nothing due)
    is not correct."""
    checks, ok = {}, True
    for name in required:
        if name not in values:
            ok = False
            checks[name] = {"value": None, "limit": limits[name]}
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"no limit on file for {name!r}")
        limit = limits[name]
        passed = bool(np.isfinite(value)) and value <= limit
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
