"""The train state that ONE rank of an expert-parallel, mixed-precision job
holds: every parameter four times (``params`` bfloat16, ``master``, ``mu``
and ``nu`` float32: 14 bytes a parameter), one leaf a matrix an expert,
beside an int32 ``step``.

The parameter leaves follow the keys of a latent-attention (MLA)
mixture-of-experts ``config.json`` at the share one rank holds (the
configuration file's ``deployment``): ``n_routed_experts`` experts of the
``n_routed_experts * expert_parallel_size`` its router scores, ``vocab_size``
rows of the embedding and of the untied head.  ``step`` is the deployment's
optimizer, not its model: a mixed-precision AdamW update of every leaf, its
gradient that of a surrogate loss (``assumed.step`` in the configuration
file; ``chipbench/reference/mixed_precision_state.py`` says both in plain
numpy, and the tests hold this file to it).  It imports nothing of the
program: no model, no sharding rule.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench.state import prng_key

# the keys that cut a configuration of this kind to a size a CPU test runs:
# one dense and two expert layers, three experts of twelve, both widths
TINY = dict(
    hidden_size=32, q_lora_rank=24, kv_lora_rank=16, num_attention_heads=2,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
    moe_intermediate_size=12, n_routed_experts=3, expert_parallel_size=4,
    vocab_size=64, num_hidden_layers=3,
)

INIT_STD = 0.02


def parameter_shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter leaves rank 0 holds, as a nested dict of shapes."""
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    q_rank, kv_rank = conf["q_lora_rank"], conf["kv_lora_rank"]
    nope, rope, v = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    held = conf["n_routed_experts"]
    scored = held * conf["expert_parallel_size"]

    def mlp(width: int) -> Dict[str, Tuple[int, ...]]:
        return {"gate_proj": (d, width), "up_proj": (d, width), "down_proj": (width, d)}

    layers = {}
    for i in range(conf["num_hidden_layers"]):
        layer: Dict[str, Any] = {
            "input_layernorm": (d,),
            "self_attn": {
                "q_a_proj": (d, q_rank),
                "q_a_layernorm": (q_rank,),
                "q_b_proj": (q_rank, heads * (nope + rope)),
                "kv_a_proj_with_mqa": (d, kv_rank + rope),
                "kv_a_layernorm": (kv_rank,),
                "kv_b_proj": (kv_rank, heads * (nope + v)),
                "o_proj": (heads * v, d),
            },
            "post_attention_layernorm": (d,),
        }
        if i < conf["first_k_dense_replace"]:
            layer["mlp"] = mlp(conf["intermediate_size"])
        else:
            width = conf["moe_intermediate_size"]
            layer["mlp"] = {
                "gate": {"weight": (d, scored), "e_score_correction_bias": (scored,)},
                "shared_experts": mlp(width * conf["n_shared_experts"]),
                "experts": {f"{e:03d}": mlp(width) for e in range(held)},
            }
        layers[f"{i:02d}"] = layer
    rows = (conf["vocab_size"], d)
    return {"embed_tokens": rows, "layers": layers, "norm": (d,), "lm_head": rows}


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


class StateFactory:
    """Makes the rank's train states under one mesh, each in one jitted call
    from a seed, born with its shardings: every leaf whole on every device
    of the mesh (the deployment's own axes, experts and pipeline stages, are
    other ranks; this rank's mesh is 1x1)."""

    def __init__(self, conf: Dict[str, Any], mesh) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh, self.vocab = mesh, conf["vocab_size"]
        shapes = parameter_shapes(conf)
        hyper = conf["optimizer"]
        lr, b1, b2 = hyper["learning_rate"], hyper["b1"], hyper["b2"]
        eps, decay = hyper["eps"], hyper["weight_decay"]
        f32, bf16 = jnp.float32, jnp.bfloat16
        tree_map = jax.tree_util.tree_map

        def init(key):
            flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)

            def draw(i: int, path, shape: Tuple[int, ...]):
                noise = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shape, f32)
                # a norm's weights lie about one, every other leaf about zero
                return 1.0 + noise if "norm" in path[-1].key else noise

            master = treedef.unflatten(
                [draw(i, path, shape) for i, (path, shape) in enumerate(flat)]
            )
            zeros = lambda: tree_map(jnp.zeros_like, master)
            return {
                "step": jnp.zeros((), jnp.int32),
                "params": tree_map(lambda m: m.astype(bf16), master),
                "master": master, "mu": zeros(), "nu": zeros(),
            }

        def probe(h, n: int):
            x = jnp.resize(h, (n,))
            return jax.lax.stop_gradient(x / jnp.sqrt(jnp.mean(x * x)))

        def loss_of(seen, tokens):
            tokens = tokens.reshape(-1)
            counts = jnp.zeros((self.vocab,), f32).at[tokens].add(1.0)
            share, bag = counts / tokens.size, counts / np.sqrt(tokens.size)
            h = jnp.dot(bag, seen["embed_tokens"], precision="highest")
            z = jnp.dot(seen["lm_head"], h, precision="highest")
            loss = jax.nn.logsumexp(z) - jnp.dot(share, z, precision="highest")
            rest = dict(seen, embed_tokens=None, lm_head=None)
            for w in jax.tree_util.tree_leaves(rest):
                x = probe(h, w.shape[0])
                y = jnp.dot(x, w, precision="highest") if w.ndim == 2 else w * x
                loss = loss + 0.5 * jnp.mean(y * y)
            return loss

        def step(tree, tokens):
            seen = tree_map(lambda p: p.astype(f32), tree["params"])
            loss, grads = jax.value_and_grad(loss_of)(seen, tokens)
            t = tree["step"] + 1
            c1 = 1.0 - jnp.power(f32(b1), t.astype(f32))
            c2 = 1.0 - jnp.power(f32(b2), t.astype(f32))
            mu = tree_map(lambda m, g: b1 * m + (1.0 - b1) * g, tree["mu"], grads)
            nu = tree_map(lambda n, g: b2 * n + (1.0 - b2) * g * g, tree["nu"], grads)
            master = tree_map(
                lambda w, m, n: w - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + decay * w),
                tree["master"], mu, nu,
            )
            params = tree_map(lambda w: w.astype(bf16), master)
            return {"step": t, "params": params, "master": master, "mu": mu, "nu": nu}, loss

        self._whole = NamedSharding(mesh, P())
        self.shardings = tree_map(lambda _: self._whole, jax.eval_shape(init, prng_key(0)))
        self._init = jax.jit(init, out_shardings=self.shardings)
        self.step = jax.jit(step, donate_argnums=0)

    def make(self, seed: int):
        return self._init(prng_key(seed))

    def batch_pool(self, seed: int, batch: Sequence[int], n: int) -> List[Any]:
        """``n`` token batches drawn from the vocabulary slice held here,
        whose rows all differ, on the device."""
        import jax

        pool = np.random.default_rng(seed).integers(
            0, self.vocab, size=(n, *batch), dtype=np.int32
        )
        return [jax.device_put(b, self._whole) for b in pool]


factory = StateFactory
