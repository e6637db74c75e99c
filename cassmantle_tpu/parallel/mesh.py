"""Device mesh construction + multi-host initialization.

The reference has NO distributed backend — Redis locks are its only
cross-process coordination (SURVEY.md §2 #16, §5.8). The TPU-native
equivalent: a logical `jax.sharding.Mesh` over the slice with named axes

- ``dp``  data parallel (batch sharding; gradients psum over ICI),
- ``tp``  tensor parallel (attention heads / MLP columns),
- ``sp``  sequence/context parallel (ring attention over tokens),

XLA GSPMD inserts the collectives; shardings are chosen so they ride ICI
within a slice. Multi-host (v5e-16 style) joins via
``jax.distributed.initialize`` before mesh construction, with host 0 alone
talking to the game-state store — mirroring how only the reference's lock
winner generates content.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cassmantle_tpu.config import MeshConfig
from cassmantle_tpu.utils.logging import get_logger

def pcast_varying(x, axis_name: str):
    """Cast a constant to device-varying over ``axis_name``: keeps scan
    carry types consistent under shard_map's check_vma."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


log = get_logger("mesh")


def maybe_init_distributed() -> bool:
    """Join a multi-host run if coordinator env vars are present."""
    if os.environ.get("CASSMANTLE_COORDINATOR"):
        jax.distributed.initialize(
            coordinator_address=os.environ["CASSMANTLE_COORDINATOR"],
            num_processes=int(os.environ.get("CASSMANTLE_NUM_PROCS", "1")),
            process_id=int(os.environ.get("CASSMANTLE_PROC_ID", "0")),
        )
        log.info("joined multi-host run: process %d/%d",
                 jax.process_index(), jax.process_count())
        return True
    return False


def resolve_axis_sizes(cfg: MeshConfig, n_devices: int) -> Sequence[int]:
    """Fill -1 axes with the remaining device count (row-major).

    Order matches ``cfg.axis_names``: (dp, pp, tp, sp, ep).
    """
    sizes = [cfg.dp, cfg.pp, cfg.tp, cfg.sp, cfg.ep]
    fixed = 1
    for s in sizes:
        if s > 0:
            fixed *= s
    assert n_devices % fixed == 0, (
        f"{n_devices} devices not divisible by fixed axes {fixed}"
    )
    remaining = n_devices // fixed
    out = []
    for s in sizes:
        if s > 0:
            out.append(s)
        else:
            out.append(remaining)
            remaining = 1
    assert int(np.prod(out)) == n_devices, (out, n_devices)
    return out


def make_mesh(cfg: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = resolve_axis_sizes(cfg, len(devices))
    arr = np.asarray(devices).reshape(sizes)
    mesh = Mesh(arr, cfg.axis_names)
    log.info("mesh: %s", dict(zip(cfg.axis_names, sizes)))
    return mesh


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Activations: batch over dp, replicated elsewhere."""
    return NamedSharding(mesh, P("dp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
