"""Production mesh construction (dry-run contract).

``make_production_mesh`` is a FUNCTION, not a module constant, so
importing this module never touches JAX device state.  The dry-run
entrypoint (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import; everything else sees the real device count.

Single pod (TPU v5e-256): mesh (16, 16) over ("data", "model").
Two pods (512 chips):      mesh (2, 16, 16) over ("pod", "data", "model").
Pipelined (pp > 1):        the data axis splits into ("pp", "data") --
                           e.g. pp=4: (4, 4, 16) over ("pp", "data",
                           "model") -- so each DP shard spans pp stage
                           groups (see docs/pipeline.md).

DP shards for the Batch Post-Balancing problem = product of the
("pod","data") axes; the node-wise ILP groups them by pod (ICI vs DCI =
the paper's NVLink vs InfiniBand split).  The ``pp`` axis is NOT a DP
axis: every stage of one pipeline sees the same post-balanced shard.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.specs import dp_axes_of, dp_shards_of

__all__ = ["make_mesh", "make_production_mesh", "dp_axes_of", "dp_shards_of",
           "pp_stages_of"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  The step functions place
    only their inputs and leave every intermediate to XLA's sharding
    propagation; ``jax.make_mesh`` otherwise returns ``Explicit`` axes,
    under which an unsharded intermediate meeting a sharded one is a
    type error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, pp: int = 1):
    if pp < 1 or 16 % pp:
        raise ValueError(f"pp must divide the 16-wide data axis, got {pp}")
    if pp == 1:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    else:
        shape = (2, pp, 16 // pp, 16) if multi_pod else (pp, 16 // pp, 16)
        axes = (("pod", "pp", "data", "model") if multi_pod
                else ("pp", "data", "model"))
    return make_mesh(shape, axes)


def pp_stages_of(mesh) -> int:
    return mesh.shape.get("pp", 1) if "pp" in mesh.axis_names else 1
