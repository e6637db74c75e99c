"""CLIP-similarity parity harness (BASELINE.md quality gate).

Because RNG streams differ from any CUDA baseline, pixel-exact parity is
impossible; the meaningful check (SURVEY.md §7 hard part (a)) is that
generated images score comparably against their prompts under CLIP. This
harness computes image-text CLIP similarity fully on-device:

    sim = <normalize(vision(image))>, normalize(project(text(prompt)))>

With real CLIP weights in ``weights_dir`` this is the true metric; with
random init it still validates the plumbing end to end.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cassmantle_tpu.config import ClipTextConfig
from cassmantle_tpu.models.clip_text import ClipTextEncoder
from cassmantle_tpu.models.clip_vision import (
    ClipVisionConfig,
    ClipVisionEncoder,
    preprocess_for_clip,
)
from cassmantle_tpu.models.weights import (
    convert_clip_text,
    convert_clip_text_projection,
    convert_clip_vision,
    convert_tensors,
    init_params,
    load_checkpoint_tensors,
)
from cassmantle_tpu.utils.tokenizers import load_tokenizer


class ClipSimilarityHarness:
    def __init__(
        self,
        text_cfg: Optional[ClipTextConfig] = None,
        vision_cfg: Optional[ClipVisionConfig] = None,
        weights_dir: Optional[str] = None,
        pad_len: int = 77,
    ) -> None:
        self.text_cfg = text_cfg or ClipTextConfig()
        self.vision_cfg = vision_cfg or ClipVisionConfig()
        self.pad_len = min(pad_len, self.text_cfg.max_positions)
        self.tokenizer = load_tokenizer(
            weights_dir, "clip", self.text_cfg.vocab_size
        )

        # ONE read of the full CLIPModel checkpoint feeds all three
        # stages (text tower, vision tower, text projection)
        tensors = load_checkpoint_tensors(
            weights_dir, "clip_text.safetensors", "clip_full")

        self.text = ClipTextEncoder(self.text_cfg)
        ids = jnp.zeros((1, self.pad_len), dtype=jnp.int32)
        loaded_text = convert_tensors(
            tensors,
            lambda t: convert_clip_text(t, self.text_cfg.num_layers),
            "clip_text")
        self.text_params = (
            loaded_text if loaded_text is not None
            else init_params(self.text, 11, ids)
        )

        # the vision tower and both projections live in the SAME full
        # CLIPModel checkpoint as the text tower (clip_text.safetensors =
        # openai/clip-vit-large-patch14 model.safetensors) — no separate
        # vision file to fetch
        self.vision = ClipVisionEncoder(self.vision_cfg)
        img = jnp.zeros(
            (1, self.vision_cfg.image_size, self.vision_cfg.image_size, 3)
        )
        loaded_vision = convert_tensors(
            tensors,
            lambda t: convert_clip_vision(t, self.vision_cfg.num_layers),
            "clip_vision")
        self.vision_params = (
            loaded_vision if loaded_vision is not None
            else init_params(self.vision, 12, img)
        )

        # text projection into the shared space
        proj = convert_tensors(tensors, convert_clip_text_projection,
                               "clip_text_projection")
        # a real parity number needs EVERY stage loaded, not just some —
        # a partial load (e.g. vision conversion KeyError falling back to
        # random init) must not masquerade as a quality measurement
        self.loaded_real_weights = (
            loaded_text is not None
            and loaded_vision is not None
            and proj is not None
        )
        if proj is None:
            proj = jax.random.normal(
                jax.random.PRNGKey(13),
                (self.text_cfg.hidden_size, self.vision_cfg.projection_dim),
            ) * 0.02
        self.text_projection = proj
        # params as jit args (device buffers), not captured constants
        self._params = {"text": self.text_params,
                        "vision": self.vision_params,
                        "proj": self.text_projection}
        self._jit_sim = jax.jit(self._sim_impl)
        self._jit_pair_sim = jax.jit(self._pair_sim_impl)

    def _tokenize(self, prompts: Sequence[str]) -> np.ndarray:
        out = np.full((len(prompts), self.pad_len),
                      self.tokenizer.pad_id, dtype=np.int32)
        for i, p in enumerate(prompts):
            toks = self.tokenizer.encode(p)[: self.pad_len - 1]
            toks = toks + [self.tokenizer.eos_id]
            out[i, : len(toks)] = (
                np.asarray(toks) % self.text_cfg.vocab_size
            )
        return out

    def _sim_impl(self, params, ids, images_u8):
        pooled = self.text.apply(params["text"], ids)["pooled"]
        temb = pooled.astype(jnp.float32) @ params["proj"]
        temb = temb / (jnp.linalg.norm(temb, axis=-1, keepdims=True) + 1e-8)
        pre = preprocess_for_clip(images_u8, self.vision_cfg.image_size)
        vemb = self.vision.apply(params["vision"], pre)
        return jnp.sum(temb * vemb, axis=-1)

    def similarity(self, images_u8: np.ndarray,
                   prompts: Sequence[str]) -> np.ndarray:
        """(B,H,W,3) uint8 + B prompts -> (B,) CLIP similarities."""
        ids = jnp.asarray(self._tokenize(prompts))
        return np.asarray(
            self._jit_sim(self._params, ids, jnp.asarray(images_u8))
        )

    def parity_report(self, images_u8, prompts,
                      baseline_mean: Optional[float] = None) -> dict:
        sims = self.similarity(images_u8, prompts)
        report = {
            "clip_sim_mean": float(np.mean(sims)),
            "clip_sim_std": float(np.std(sims)),
            "n": int(len(sims)),
            # False => plumbing-only run (random init): NOT a quality claim
            "real_weights": self.loaded_real_weights,
        }
        if baseline_mean is not None:
            report["baseline_mean"] = float(baseline_mean)
            report["parity_ratio"] = float(np.mean(sims) / baseline_mean)
        return report

    def _pair_sim_impl(self, params, images_a_u8, images_b_u8):
        def embed(imgs):
            pre = preprocess_for_clip(imgs, self.vision_cfg.image_size)
            return self.vision.apply(params["vision"], pre)

        return jnp.sum(embed(images_a_u8) * embed(images_b_u8), axis=-1)

    def image_similarity(self, images_a_u8: np.ndarray,
                         images_b_u8: np.ndarray) -> np.ndarray:
        """(B,) cosine similarities between the CLIP-vision embeddings
        of two image batches — the image↔image counterpart of
        :meth:`similarity`, jitted once like it (``_jit_pair_sim``).
        Identical batches score 1.0 exactly (both arms embed through
        the same compiled tower), which is what makes a bit-exact
        revert leg of a quality gate a deterministic tier-1 assertion
        even on random init."""
        return np.asarray(self._jit_pair_sim(
            self._params, jnp.asarray(images_a_u8),
            jnp.asarray(images_b_u8)))


# Image-quality floor for few-step consistency serving: mean
# CLIP-vision similarity between the 4-step student's images and the
# teacher's SAME-SEED full-schedule images must stay above this — the
# student is a learned approximation of the whole trajectory
# (LCM-class quality, the `lcm` row of QualityGateConfig). Enforced
# only on real-weights runs, advisory on random init, like every other
# gate.
CONSISTENCY_IMAGE_SIM_FLOOR = 0.90


def consistency_quality_report(
    harness: ClipSimilarityHarness,
    images_student: np.ndarray,
    images_teacher: np.ndarray,
    prompts: Sequence[str],
    floor: float = CONSISTENCY_IMAGE_SIM_FLOOR,
) -> dict:
    """The few-step quality gate (ISSUE 15): same-seed student (4-step
    consistency) vs teacher (full-schedule) outputs compared in
    CLIP-vision space (robust, image↔image — no text-prompt noise
    term), plus both arms' prompt CLIP-sim for the record.
    ``passes_floor`` is the gate verdict; ``gate_enforced`` says
    whether it is a real-weights measurement or plumbing-only."""
    pair = harness.image_similarity(images_student, images_teacher)
    return {
        "image_sim_mean": float(np.mean(pair)),
        "image_sim_min": float(np.min(pair)),
        "floor": float(floor),
        "passes_floor": bool(np.mean(pair) >= floor),
        "exact": bool(np.array_equal(images_student, images_teacher)),
        "clip_sim_student": float(
            np.mean(harness.similarity(images_student, prompts))),
        "clip_sim_teacher": float(
            np.mean(harness.similarity(images_teacher, prompts))),
        "n": int(images_teacher.shape[0]),
        "real_weights": harness.loaded_real_weights,
        "gate_enforced": harness.loaded_real_weights,
    }


# Image-quality floor for W8A8 quantized serving (ISSUE 20): mean
# CLIP-vision similarity between the int8-kernel arm's images and the
# fp arm's SAME-SEED images. Higher than the consistency floor —
# quantization is a numerics approximation of the SAME trajectory
# (per-channel weight scales + calibrated activation scales), not a
# learned shortcut; the `w8a8`/`sdxl_w8a8` rows of QualityGateConfig
# carry the per-pipeline bars. Enforced only on real-weights runs,
# advisory on random init, like every other gate.
W8A8_IMAGE_SIM_FLOOR = 0.98


def w8a8_quality_report(
    harness: ClipSimilarityHarness,
    images_w8a8: np.ndarray,
    images_fp: np.ndarray,
    prompts: Sequence[str],
    floor: float = W8A8_IMAGE_SIM_FLOOR,
) -> dict:
    """The W8A8 quality gate: same-seed quantized vs fp outputs
    compared in CLIP-vision space (the consistency gate's structure
    applied to the int8 kernel path). ``passes_floor`` is the gate
    verdict; ``gate_enforced`` says whether it is a real-weights
    measurement or plumbing-only."""
    pair = harness.image_similarity(images_w8a8, images_fp)
    return {
        "image_sim_mean": float(np.mean(pair)),
        "image_sim_min": float(np.min(pair)),
        "floor": float(floor),
        "passes_floor": bool(np.mean(pair) >= floor),
        "exact": bool(np.array_equal(images_w8a8, images_fp)),
        "clip_sim_w8a8": float(
            np.mean(harness.similarity(images_w8a8, prompts))),
        "clip_sim_fp": float(
            np.mean(harness.similarity(images_fp, prompts))),
        "n": int(images_fp.shape[0]),
        "real_weights": harness.loaded_real_weights,
        "gate_enforced": harness.loaded_real_weights,
    }
