"""The state of a dense transformer LM in training: float32 parameters with
``optax.adamw``'s float32 moments beside them, 12 bytes a parameter, sharded
over a ``("dp", "tp")`` mesh by the program's own rules.

The model, its train step and its sharding rules are the program's
(``models/transformer.py``, ``parallel/mesh.py``); a configuration file
gives the widths under the keys of a Hugging Face ``config.json``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from chipbench.state import prng_key

# the keys that cut a configuration of this kind to a size a CPU test runs
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=128, vocab_size=256, num_hidden_layers=2,
    max_position_embeddings=64,
)

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

        from torchsnapshot_tpu.models.transformer import TransformerLM, train_step
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
        self.step = jax.jit(train_step, donate_argnums=0)

    def make(self, seed: int):
        return self._init(prng_key(seed))

    def batch_pool(self, seed: int, batch: Sequence[int], n: int) -> List[Any]:
        """``n`` token batches whose rows all differ, on the device."""
        import jax

        pool = np.random.default_rng(seed).integers(
            0, self.cfg.vocab, size=(n, *batch), dtype=np.int32
        )
        return [jax.device_put(b, self.batch_sharding) for b in pool]


factory = StateFactory
