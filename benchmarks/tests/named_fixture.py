"""Test fixtures that arrive as files alone: a configuration whose prompt
LM is the program's second family at its test size, and a second image
trajectory. They stand for no deployment and are in no ``configs/``
directory; no line of the harness, the readers or run.py knows of them.
"""

import dataclasses


def mistral_test_config():
    """The program's tiny test configuration with its second LM family
    (``MistralConfig.tiny()``) as the prompt LM."""
    from cassmantle_tpu.config import MistralConfig, test_config

    cfg = test_config()
    return cfg.replace(models=dataclasses.replace(
        cfg.models, mistral=MistralConfig.tiny()))
