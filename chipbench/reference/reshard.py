"""The plain reference of a restore under another layout.

What a resharding restore has to put on a device is a matter of indexing
and of nothing else: gather the state handed to the take to one numpy array
a leaf, and the bytes that device ``d`` must hold of a leaf are
``whole[index]`` with ``index = target_sharding.devices_indices_map(shape)[d]``,
whatever layout the state was saved under.  This file imports nothing of the
program; the shardings are JAX's own.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def gather(leaves: List[Any]) -> List[np.ndarray]:
    """Each leaf whole, on the host."""
    return [np.asarray(x) for x in leaves]


def expected_shards(whole: np.ndarray, sharding: Any) -> Dict[Any, np.ndarray]:
    """device → the slice of ``whole`` that the device holds under ``sharding``."""
    return {
        device: whole[index]
        for device, index in sharding.devices_indices_map(whole.shape).items()
    }


def differing_shards(whole: np.ndarray, restored: Any) -> List[str]:
    """The shards of ``restored`` (a ``jax.Array``) that do not hold, bit for
    bit and in shape and dtype, what the reference gives their device; also a
    device the reference names and the array has no shard on."""
    want = expected_shards(whole, restored.sharding)
    wrong, seen = [], set()
    for shard in restored.addressable_shards:
        seen.add(shard.device)
        got, ref = np.asarray(shard.data), want.get(shard.device)
        if (
            ref is None or got.shape != ref.shape or got.dtype != ref.dtype
            or got.tobytes() != ref.tobytes()
        ):
            wrong.append(f"{shard.device}: {shard.index}")
    wrong += [f"{device}: no shard" for device in want if device not in seen]
    return wrong
