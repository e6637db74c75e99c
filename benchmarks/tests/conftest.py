import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def guess_cell():
    """The committed cell's configuration under a mix with guesses, so that
    the generator's open loop and the scorer's two numbers stay tested.
    The mix is a fixture (guess_mix.json): its rate and phrase share
    exercise the code and stand for no deployment."""
    import json

    from benchmarks.harness.manifest import Cell, load_manifest

    manifest = load_manifest()
    cell = Cell(manifest, manifest["workloads"][0]["name"])
    with open(os.path.join(os.path.dirname(__file__),
                           "guess_mix.json")) as f:
        cell.traffic = json.load(f)
    return cell
