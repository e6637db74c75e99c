"""CLIP-similarity report across serving presets (the quality gate).

BASELINE.md's gate is "CLIP-similarity parity": the fast presets
(DPM-Solver++(2M) @ 25 steps, int8) only count as wins if their
images score on par with the fixed DDIM-50 config under CLIP. This tool
generates the same prompts with each preset, scores every image against
its prompt with the local CLIP harness (eval/clip_parity.py — both
towers + projections load from clip_text.safetensors), and writes one
JSON report with per-preset means and ratios vs the ddim50 anchor.

The reference never measures image quality — it trusts a hosted SDXL
endpoint's output (/root/reference/src/backend.py:270-295); this harness
is that trust made falsifiable. ``real_weights`` is false when any CLIP
stage fell back to random init: such a run validates plumbing only and
must not be quoted as a quality number.

Usage:
    python tools/clip_report.py [--weights weights] [--out CLIP_REPORT.json]
        [--platform cpu] [--presets ddim50,dpmpp25,int8]
        [--tiny]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

PROMPTS = [
    "A watercolor style piece depicting: a lighthouse over a stormy sea",
    "An art deco style piece depicting: a caravan crossing silver dunes",
    "A stained glass style piece depicting: an orchard under two moons",
    "A vaporwave style piece depicting: a night train between cities",
    "An ukiyo-e style piece depicting: cranes over a frozen river",
    "A chalk pastel style piece depicting: a market street in the rain",
    "A linocut style piece depicting: a fox asleep in a bell tower",
    "A gouache style piece depicting: terraced fields at first light",
]


def _with_unet_int8(cfg):
    import dataclasses

    return cfg.replace(
        models=dataclasses.replace(cfg.models, unet_int8=True))


def preset_factories(tiny: bool):
    if tiny:
        import dataclasses

        from cassmantle_tpu.config import test_config

        def tiny_kind(kind, **kw):
            def make():
                cfg = test_config()
                return cfg.replace(sampler=dataclasses.replace(
                    cfg.sampler, kind=kind, **kw))
            return make

        return {
            "ddim50": tiny_kind("ddim", num_steps=4),
            "dpmpp25": tiny_kind("dpmpp_2m", num_steps=2),
            "int8": lambda: _with_unet_int8(test_config()),
        }
    from cassmantle_tpu.config import FrameworkConfig, fast_serving_config

    return {
        "ddim50": FrameworkConfig,
        "dpmpp25": fast_serving_config,
        # quality arm of the sd15_int8 bench A/B: same DDIM-50
        # trajectory, int8 UNet weights
        "int8": lambda: _with_unet_int8(FrameworkConfig()),
    }


def apply_quality_gate(report: dict, gate_cfg=None) -> list:
    """Annotate each gated preset with {threshold, passed} and return
    the list of human-readable failures (config.QualityGateConfig).
    Pure on the report dict — unit-tested without pipelines."""
    if gate_cfg is None:
        # default thresholds come from the framework config, so a
        # FrameworkConfig(quality=...) override is the single source
        from cassmantle_tpu.config import FrameworkConfig

        gate_cfg = FrameworkConfig().quality
    failures = []
    anchor = report["presets"].get("ddim50")
    if anchor:
        floor = gate_cfg.ddim50_min_sim
        anchor["gate"] = {"min_sim": floor,
                          "passed": anchor["clip_sim_mean"] >= floor}
        if not anchor["gate"]["passed"]:
            failures.append(
                f"ddim50 anchor clip_sim_mean "
                f"{anchor['clip_sim_mean']:.4f} < floor {floor}")
    for name, entry in report["presets"].items():
        threshold = gate_cfg.threshold_for(name)
        if threshold is None or "parity_vs_ddim50" not in entry:
            continue
        entry["gate"] = {"threshold": threshold,
                         "passed": entry["parity_vs_ddim50"] >= threshold}
        if not entry["gate"]["passed"]:
            failures.append(
                f"{name} parity_vs_ddim50 "
                f"{entry['parity_vs_ddim50']:.4f} < {threshold}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    # default resolves against the repo (module-CLI runs from anywhere);
    # an explicit --weights keeps its shell meaning
    ap.add_argument("--weights",
                    default=os.path.join(REPO_ROOT, "weights"))
    ap.add_argument("--out", default=None,
                    help="report path; defaults to CLIP_REPORT.json, or "
                         "CLIP_REPORT.tiny.json under --tiny so a "
                         "plumbing smoke can never overwrite hardware "
                         "evidence (same split as bench.py's cpu-smoke "
                         "suite file)")
    ap.add_argument("--platform", default="auto", choices=["auto", "cpu"])
    ap.add_argument("--presets",
                    default="ddim50,dpmpp25,int8")
    ap.add_argument("--seeds", type=int, default=2,
                    help="image batches per preset (n = seeds * 8 prompts)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny configs (plumbing smoke, not a measurement)")
    ap.add_argument("--enforce", action="store_true",
                    help="fail the quality gate even on random-init "
                         "runs (tests the enforcement path)")
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.join(
            REPO_ROOT,
            "CLIP_REPORT.tiny.json" if args.tiny else "CLIP_REPORT.json")

    if args.platform == "cpu":
        from cassmantle_tpu.utils.xla_flags import pin_cpu_platform

        pin_cpu_platform(virtual_devices=False)

    from cassmantle_tpu.eval.clip_parity import ClipSimilarityHarness
    from cassmantle_tpu.serving.pipeline import Text2ImagePipeline

    # --tiny is a plumbing smoke: tiny-config models must never try to
    # ingest a real full-size checkpoint (layer-prefix conversion would
    # "succeed" then fail at apply with shape errors)
    weights_dir = (None if args.tiny
                   else args.weights if os.path.isdir(args.weights)
                   else None)
    if args.tiny:
        from cassmantle_tpu.config import ClipTextConfig
        from cassmantle_tpu.models.clip_vision import ClipVisionConfig

        harness = ClipSimilarityHarness(
            text_cfg=ClipTextConfig(
                vocab_size=512, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, max_positions=16),
            vision_cfg=ClipVisionConfig.tiny(),
            weights_dir=None, pad_len=16)
    else:
        harness = ClipSimilarityHarness(weights_dir=weights_dir)

    factories = preset_factories(args.tiny)
    wanted = [p.strip() for p in args.presets.split(",") if p.strip()]
    unknown = sorted(set(wanted) - set(factories))
    if unknown:
        sys.exit(f"unknown presets: {unknown}; have {sorted(factories)}")

    import numpy as np

    report: dict = {
        "real_weights": harness.loaded_real_weights,
        "prompts": len(PROMPTS), "seeds": args.seeds,
        "presets": {},
    }
    from cassmantle_tpu.serving.pipeline import share_compatible

    anchors = []  # one anchor pipeline per distinct architecture
    for name in wanted:
        cfg = factories[name]()
        share = next(
            (p for p in anchors
             if share_compatible(p.cfg.models, cfg.models)),
            None)
        pipe = Text2ImagePipeline(cfg, weights_dir=weights_dir,
                                  share_params_with=share)
        if share is None:
            anchors.append(pipe)
        sims = []
        for seed in range(args.seeds):
            images = pipe.generate(PROMPTS, seed=seed)
            sims.extend(harness.similarity(images, PROMPTS).tolist())
        entry = {
            "clip_sim_mean": float(np.mean(sims)),
            "clip_sim_std": float(np.std(sims)),
            "n": len(sims),
            "pipeline_real_weights": pipe.loaded_real_weights,
        }
        # the headline flag means "this whole report is a measurement":
        # scorer AND every generator loaded from checkpoints
        report["real_weights"] = (
            report["real_weights"] and pipe.loaded_real_weights
        )
        report["presets"][name] = entry
        print(f"[clip_report] {name}: mean={entry['clip_sim_mean']:.4f} "
              f"std={entry['clip_sim_std']:.4f} n={entry['n']}")

    anchor = report["presets"].get("ddim50")
    if anchor:
        for name, entry in report["presets"].items():
            if name != "ddim50" and anchor["clip_sim_mean"]:
                entry["parity_vs_ddim50"] = float(
                    entry["clip_sim_mean"] / anchor["clip_sim_mean"])

    # Quality-gate enforcement (config.QualityGateConfig): thresholds
    # are asserted whenever this report is a real measurement — random
    # init similarity is noise, so plumbing runs report advisory-only
    # unless --enforce forces the gate (CI of the enforcement path).
    enforce = report["real_weights"] or args.enforce
    failures = apply_quality_gate(report)
    report["gate_enforced"] = bool(enforce)
    report["gate_failures"] = failures

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[clip_report] wrote {args.out} "
          f"(real_weights={report['real_weights']})")
    if failures:
        verdict = "FAILED" if enforce else "advisory (random weights)"
        print(f"[clip_report] quality gate {verdict}:", file=sys.stderr)
        for f_ in failures:
            print(f"[clip_report]   {f_}", file=sys.stderr)
        if enforce:
            sys.exit(2)
    elif anchor:
        print("[clip_report] quality gate passed "
              f"({'enforced' if enforce else 'advisory'})")


if __name__ == "__main__":
    main()
