"""Mesh construction + parameter sharding rules for the bundled models.

The checkpointing core is sharding-agnostic (it reads layouts off
``jax.Array.sharding``); this module exists so the bundled benchmark models
and the multi-chip dry run exercise realistic dp/tp/sp layouts, the way the
reference's benchmarks exercise DDP/FSDP/torchrec layouts
(reference benchmarks/{ddp,fsdp,torchrec}/main.py).
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

import numpy as np


def ensure_cpu_devices(min_devices: int = 1) -> None:
    """Force the CPU platform — used by the examples and the virtual-mesh
    dry run.  Must run before the first backend use."""
    import os

    import jax

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_platforms", "cpu")


def build_mesh(n_devices: Optional[int] = None, tp: Optional[int] = None):
    """A 2-D ("dp", "tp") mesh over the first ``n_devices`` devices.

    tp defaults to min(2, n) when n is even — enough to exercise real
    tensor-parallel shardings in the dry run while leaving dp > 1.
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = n_devices or len(devices)
    devices = np.array(devices[:n])
    if tp is None:
        tp = 2 if n % 2 == 0 and n >= 2 else 1
    dp = n // tp
    return Mesh(devices[: dp * tp].reshape(dp, tp), ("dp", "tp"))


# (param-path regex, PartitionSpec factory) — megatron-style layout:
# column-parallel in, row-parallel out, replicated norms/embedding rows.
_RULES: Tuple[Tuple[str, Tuple], ...] = (
    (r".*embed.*", (None, "tp")),
    (r".*(wq|wk|wv|w1|gate).*", (None, "tp")),
    (r".*(wo|w2|proj_out).*", ("tp", None)),
    (r".*lm_head.*", (None, "tp")),
    (r".*(norm|scale|bias).*", (None,)),
)


def param_sharding_rules(path: str, shape: Tuple[int, ...]):
    """Map a flattened param path + shape to a PartitionSpec."""
    from jax.sharding import PartitionSpec as P

    for pattern, spec in _RULES:
        if re.fullmatch(pattern, path, flags=re.IGNORECASE):
            spec = tuple(spec[: len(shape)])
            # drop tp assignment when the dim isn't divisible — XLA would
            # reject; replication is always valid
            out = []
            for dim, ax in zip(shape, spec):
                out.append(None if ax is None else ax)
            return P(*out)
    return P(*([None] * len(shape)))


def shard_pytree(tree, mesh):
    """Place every array leaf of ``tree`` on ``mesh`` per the rules; the
    result's shardings are what the checkpointer later reads back."""
    import jax
    from jax.sharding import NamedSharding

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def place(path, leaf):
        if not hasattr(leaf, "shape"):
            return leaf
        path_str = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        spec = param_sharding_rules(path_str, tuple(leaf.shape))
        # divisibility guard: replicate dims the mesh can't split evenly
        fixed = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * len(leaf.shape)):
            if ax is not None and dim % mesh.shape[ax] != 0:
                ax = None
            fixed.append(ax)
        from jax.sharding import PartitionSpec as P

        return jax.device_put(leaf, NamedSharding(mesh, P(*fixed)))

    placed = [place(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, placed)
