"""CLI surface (`python -m cassmantle_tpu`): dispatch + train smoke runs.

The reference has no CLI (launch is `uvicorn main:app`, reference
requirements.txt:2); this framework fronts every runnable surface through
one entry point, so the dispatch table and both training loops get tests.
Training smoke runs use the tiny test config on the virtual CPU devices.
"""

import numpy as np
import pytest

from cassmantle_tpu.__main__ import main


def test_usage_and_unknown_command(capsys):
    assert main([]) == 2
    assert main(["no-such-command"]) == 2
    assert main(["--help"]) == 0
    out = capsys.readouterr()
    assert "train-diffusion" in out.err


def test_version(capsys):
    assert main(["version"]) == 0
    from cassmantle_tpu import __version__

    assert __version__ in capsys.readouterr().out


def test_train_diffusion_smoke(tmp_path, capsys):
    rc = main([
        "train-diffusion", "--config", "test", "--steps", "3",
        "--batch", "8", "--image-size", "64", "--dp", "-1",
        "--log-every", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--checkpoint-every", "2",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[diffusion] step 2 loss" in out
    # resume path: a second run starts from the saved final step
    rc = main([
        "train-diffusion", "--config", "test", "--steps", "3",
        "--batch", "8", "--image-size", "64",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 0
    assert "resumed from step 3" in capsys.readouterr().out


def test_train_lm_smoke(capsys):
    rc = main([
        "train-lm", "--config", "test", "--steps", "2", "--batch", "8",
        "--seq-len", "32", "--log-every", "1",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[lm] step 1 loss" in out


def test_train_lm_token_file(tmp_path, capsys):
    stream = np.arange(8 * 32 * 2, dtype=np.int32) % 50
    path = tmp_path / "tokens.npy"
    np.save(path, stream)
    rc = main([
        "train-lm", "--config", "test", "--steps", "1", "--batch", "8",
        "--seq-len", "32", "--tokens", str(path), "--log-every", "1",
    ])
    assert rc == 0
    assert "[lm] step 0 loss" in capsys.readouterr().out


def test_quality_gate_thresholds():
    """config.QualityGateConfig enforcement (VERDICT r4 #3): the gate
    annotates per-preset verdicts, fails presets under threshold and a
    degraded anchor, and passes a clean report."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "clip_report_mod",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "clip_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def report(anchor_sim, parity):
        return {"presets": {
            "ddim50": {"clip_sim_mean": anchor_sim},
            "dpmpp25": {"clip_sim_mean": anchor_sim * parity,
                        "parity_vs_ddim50": parity},
        }}

    clean = report(0.30, 0.99)
    assert mod.apply_quality_gate(clean) == []
    assert clean["presets"]["dpmpp25"]["gate"]["passed"]
    assert clean["presets"]["ddim50"]["gate"]["passed"]

    low_parity = report(0.30, 0.90)  # dpmpp25 gates at 0.97
    fails = mod.apply_quality_gate(low_parity)
    assert len(fails) == 1 and "dpmpp25" in fails[0]
    assert not low_parity["presets"]["dpmpp25"]["gate"]["passed"]

    dead_anchor = report(0.05, 0.99)  # uniform degradation
    fails = mod.apply_quality_gate(dead_anchor)
    assert any("anchor" in f for f in fails)

    # a preset with no configured threshold is reported, never gated
    ungated = {"presets": {"ddim50": {"clip_sim_mean": 0.3},
                           "exotic": {"clip_sim_mean": 0.1,
                                      "parity_vs_ddim50": 0.33}}}
    assert mod.apply_quality_gate(ungated) == []


def test_weights_drill_requires_real_weights_for_round(tmp_path):
    """The drill's LM-decoded-round leg must refuse to 'pass' on random
    init at full config — a provisioned-host check, not a plumbing one
    (exit 5). --tiny remains the plumbing path (covered by the watcher
    smoke)."""
    from cassmantle_tpu.__main__ import main

    rc = main(["weights-drill", "--platform", "cpu",
               "--weights", str(tmp_path / "nope"),
               "--skip-fetch", "--skip-quantize", "--skip-clip",
               "--skip-lm-ab"])
    assert rc == 5
