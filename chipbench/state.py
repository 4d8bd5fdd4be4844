"""The data of a cell: the train state made from ``--seed``, its token
batches, and the plain digests that decide ``correct``.

The model and its train step are the program's (``models/transformer.py``,
as the issue asks); everything that judges the checkpointer — the state's
bytes, the digests, the comparison — is here and imports nothing of it.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# keys of a configuration file that the program's model can run as stated,
# and the TransformerConfig field each one sets
_MODEL_KEYS = {
    "vocab_size": "vocab",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "intermediate_size": "d_ff",
    "max_position_embeddings": "max_seq",
}


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def model_config(conf: Dict[str, Any]):
    """The program's TransformerConfig at the widths the file states."""
    from torchsnapshot_tpu.models.transformer import TransformerConfig

    if conf["head_dim"] * conf["num_attention_heads"] != conf["hidden_size"]:
        raise ValueError("the program's attention has head_dim = hidden / heads")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("the program's attention has as many kv heads as heads")
    if conf["tie_word_embeddings"]:
        raise ValueError("the program's model has an untied head")
    return TransformerConfig(
        **{field: conf[key] for key, field in _MODEL_KEYS.items()}
    )


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


class StateFactory:
    """Makes train states of one configuration under one mesh, each in one
    jitted call from a seed, born with its shardings (nothing is built on
    device 0 first)."""

    def __init__(self, conf: Dict[str, Any], mesh) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from flax.training import train_state
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchsnapshot_tpu.models.transformer import TransformerLM
        from torchsnapshot_tpu.parallel.mesh import param_sharding_rules

        self.cfg = model_config(conf)
        self.mesh = mesh
        model = TransformerLM(self.cfg)
        tx = optax.adamw(3e-4, weight_decay=0.01)
        tokens = jnp.zeros((1, 8), dtype=jnp.int32)

        def init(key):
            return train_state.TrainState.create(
                apply_fn=model.apply, params=model.init(key, tokens), tx=tx
            )

        abstract = jax.eval_shape(init, prng_key(0))

        def sharding_of(path, leaf):
            name = "/".join(
                str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                for p in path
            )
            spec = param_sharding_rules(name, tuple(leaf.shape))
            axes = [
                ax if ax is not None and dim % mesh.shape[ax] == 0 else None
                for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim)
            ]
            return NamedSharding(mesh, P(*axes))

        self.shardings = jax.tree_util.tree_map_with_path(sharding_of, abstract)
        self._init = jax.jit(init, out_shardings=self.shardings)
        self.batch_sharding = NamedSharding(mesh, P("dp", None))

    def make(self, seed: int):
        return self._init(prng_key(seed))

    def token_pool(self, seed: int, batch: Tuple[int, int], n: int) -> List[Any]:
        """``n`` batches whose rows all differ, on the device."""
        import jax

        pool = np.random.default_rng(seed).integers(
            0, self.cfg.vocab, size=(n, *batch), dtype=np.int32
        )
        return [jax.device_put(b, self.batch_sharding) for b in pool]


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
