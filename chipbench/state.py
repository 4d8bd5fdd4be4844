"""What judges a cell's state: its bytes, the plain digests that decide
``correct``, and the comparison.  It imports nothing of the program.

The state itself (its tree, shardings, train step and batches, made from
``--seed``) comes from the file a configuration names under ``"state"``:
``states/<name>.py`` under one of ``paths`` (``bench.Cell.state_factory``).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def build_mesh(devices: Sequence[Any], dp: int, tp: int):
    from jax.sharding import Mesh

    if dp * tp > len(devices):
        raise ValueError(f"a {dp}x{tp} mesh needs {dp * tp} devices")
    return Mesh(np.array(devices[: dp * tp]).reshape(dp, tp), ("dp", "tp"))


def prng_key(seed: int):
    """A key from any whole number: the driver's seeds pass 2**31."""
    import jax

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    return jax.random.fold_in(key, seed // (2**31 - 1))


def array_leaves(tree) -> List[Any]:
    import jax

    return [x for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array)]


def state_bytes(tree) -> int:
    return sum(x.nbytes for x in array_leaves(tree))


def fullest_device_bytes(tree) -> int:
    """Bytes of state on the device that holds most of it."""
    per_device: Dict[Any, int] = {}
    for x in array_leaves(tree):
        for shard in x.addressable_shards:
            per_device[shard.device] = (
                per_device.get(shard.device, 0) + shard.data.nbytes
            )
    return max(per_device.values())


def _digest_leaf(x):
    """Two wrapping 32-bit sums over a leaf's words: the plain sum, and the
    sum weighted by each word's position, so a word moved or changed shows.
    Integer sums wrap exactly in any order, so the digest of a value is the
    same under every layout."""
    import jax
    import jax.numpy as jnp

    width = x.dtype.itemsize
    if width not in (2, 4):
        raise ValueError(f"no digest for {x.dtype} leaves")
    word = jnp.uint32 if width == 4 else jnp.uint16
    w = jax.lax.bitcast_convert_type(x, word).astype(jnp.uint32)
    pos = jnp.zeros(x.shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(x.ndim)):
        pos = pos + jax.lax.broadcasted_iota(jnp.uint32, x.shape, axis) * jnp.uint32(
            stride % 2**32
        )
        stride *= x.shape[axis]
    weight = pos * jnp.uint32(2654435761) + jnp.uint32(12345)
    return jnp.stack([jnp.sum(w), jnp.sum(w * weight)])


class Digester:
    """One jitted program over all array leaves of a state: [leaves, 2]."""

    def __init__(self) -> None:
        import jax
        import jax.numpy as jnp

        self._fn = jax.jit(
            lambda leaves: jnp.stack([_digest_leaf(x) for x in leaves])
        )

    def __call__(self, tree) -> np.ndarray:
        return np.asarray(self._fn(array_leaves(tree)))


def layout_of(tree) -> List[Tuple[Tuple[int, ...], str, Any]]:
    return [(tuple(x.shape), str(x.dtype), x.sharding) for x in array_leaves(tree)]


def _same_layout(want: Tuple, got: Tuple) -> bool:
    """Same shape and dtype, and the same bytes on the same devices (a
    one-device mesh and that device alone are one placement)."""
    if want[:2] != got[:2]:
        return False
    if hasattr(want[2], "is_equivalent_to"):
        return want[2].is_equivalent_to(got[2], len(want[0]))
    return want[2] == got[2]


def compare(
    reference: np.ndarray,
    got: Optional[np.ndarray],
    want_layout: List[Tuple],
    got_layout: Optional[List[Tuple]],
) -> Dict[str, int]:
    """How many leaves of one answer differ from the reference: in their
    bytes, and in shape, dtype or placement."""
    if got is None or got_layout is None or got.shape != reference.shape:
        n = len(want_layout)
        return {"leaves_mismatched": n, "leaves_misplaced": n}
    return {
        "leaves_mismatched": int(np.any(reference != got, axis=1).sum()),
        "leaves_misplaced": sum(
            1 for a, b in zip(want_layout, got_layout) if not _same_layout(a, b)
        ),
    }
