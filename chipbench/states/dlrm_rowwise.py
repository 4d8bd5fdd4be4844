"""The train state that ONE rank of a recommender job holds whose embedding
tables are sharded row-wise: its rows of every table (``tables/tNN``
``[rows, embedding_dim]`` float32) with row-wise Adagrad's one accumulator a
row beside them (``table_acc/tNN`` ``[rows]``), the dense part whole (a
bottom and a top MLP, ``dense/{bot,top}/<i>/{w,b}``) with element-wise
Adagrad's accumulators (``dense_acc/...``), and an int32 ``step``.

The leaves follow the flags of the public DLRM recipe
(``num_embeddings_per_feature`` at the rows held HERE, ``embedding_dim``,
``dense_arch_layer_sizes``, ``over_arch_layer_sizes``).  ``step`` is that
model and its optimizer written out: bottom MLP, one embedding row a feature
a sample, the pairwise dot products above the diagonal, top MLP, binary
cross-entropy; dense leaves by Adagrad, a table's touched rows by row-wise
Adagrad, scattered into the donated table (no table is copied, and no
gradient of a table's shape is made).  ``chipbench/reference/
dlrm_rowwise_state.py`` says the same in plain numpy, and the tests hold
this file to it.  It imports nothing of the program.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench.state import prng_key

# the keys that cut a configuration of this kind to a size a CPU test runs:
# six tables, one of them of a single row, MLPs 13-16-8 and 29-16-8-1
TINY = dict(
    embedding_dim=8, num_embeddings_per_feature=[40, 3, 1, 17, 40, 5],
    dense_arch_layer_sizes=[16, 8], over_arch_layer_sizes=[16, 8, 1],
)


def mlp_shapes(widths: Sequence[int]) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    """``widths[0]`` inputs through ``len(widths) - 1`` linear layers."""
    return {
        str(i): {"w": (fan_in, fan_out), "b": (fan_out,)}
        for i, (fan_in, fan_out) in enumerate(zip(widths[:-1], widths[1:]))
    }


def leaf_shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The float32 leaves the rank holds, as a nested dict of shapes."""
    dim, rows = conf["embedding_dim"], conf["num_embeddings_per_feature"]
    bot = [conf["dense_in_features"], *conf["dense_arch_layer_sizes"]]
    if bot[-1] != dim:
        raise ValueError("the bottom MLP ends at the embedding dimension")
    vectors = len(rows) + 1  # the bottom MLP's output and one row a table
    top = [dim + vectors * (vectors - 1) // 2, *conf["over_arch_layer_sizes"]]
    if top[-1] != 1:
        raise ValueError("the top MLP ends in one logit")
    dense = {"bot": mlp_shapes(bot), "top": mlp_shapes(top)}
    return {
        "tables": {f"t{f:02d}": (n, dim) for f, n in enumerate(rows)},
        "table_acc": {f"t{f:02d}": (n,) for f, n in enumerate(rows)},
        "dense": dense, "dense_acc": dense,
    }


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


class StateFactory:
    """Makes the rank's train states under one mesh, each in one jitted call
    from a seed, born with its shardings: every leaf whole on the rank's one
    device (the deployment's other 15 ranks hold the other rows)."""

    def __init__(self, conf: Dict[str, Any], mesh) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.rows = list(conf["num_embeddings_per_feature"])
        self.dense_in = conf["dense_in_features"]
        shapes = leaf_shapes(conf)
        names = sorted(shapes["tables"])
        lr, eps = conf["optimizer"]["learning_rate"], conf["optimizer"]["eps"]
        f32 = jnp.float32
        tree_map = jax.tree_util.tree_map
        upper = np.triu_indices(len(names) + 1, k=1)

        def init(key):
            def table(i: int, shape):
                bound = 1.0 / np.sqrt(shape[0])
                return jax.random.uniform(
                    jax.random.fold_in(key, i), shape, f32, -bound, bound
                )

            def weight(i: int, shape):
                # a weight [in, out] by its fans, a bias by its width
                std = np.sqrt(2.0 / sum(shape)) if len(shape) == 2 else np.sqrt(1.0 / shape[0])
                return std * jax.random.normal(jax.random.fold_in(key, 1000 + i), shape, f32)

            flat, treedef = jax.tree_util.tree_flatten_with_path(shapes["dense"], is_leaf=_is_shape)
            dense = treedef.unflatten([weight(i, shape) for i, (_, shape) in enumerate(flat)])
            tables = {n: table(i, shapes["tables"][n]) for i, n in enumerate(names)}
            return {
                "step": jnp.zeros((), jnp.int32),
                "tables": tables,
                "table_acc": {n: jnp.zeros(shapes["table_acc"][n], f32) for n in names},
                "dense": dense,
                "dense_acc": tree_map(jnp.zeros_like, dense),
            }

        def mlp(layers, x, relu_last: bool):
            for i in range(len(layers)):
                x = jnp.dot(x, layers[str(i)]["w"], precision="highest") + layers[str(i)]["b"]
                if relu_last or i < len(layers) - 1:
                    x = jax.nn.relu(x)
            return x

        def loss_of(dense, embedded, batch):
            x = mlp(dense["bot"], batch["dense"], relu_last=True)
            vectors = jnp.stack([x, *embedded], axis=1)
            pairs = jnp.einsum("bik,bjk->bij", vectors, vectors, precision="highest")
            seen = jnp.concatenate([x, pairs[:, upper[0], upper[1]]], axis=1)
            z = mlp(dense["top"], seen, relu_last=False)[:, 0]
            y = batch["labels"]
            return jnp.mean(jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

        def touch(table, acc, ids, grads):
            """Row-wise Adagrad over the rows ``ids`` name.  Samples that
            share a row all compute that row's new value (its gradients
            summed over them), so the scatter writes one value a row."""
            shared = (ids[:, None] == ids[None, :]).astype(f32)
            g = jnp.dot(shared, grads, precision="highest")
            acc_rows = acc[ids] + jnp.mean(g * g, axis=1)
            rows = table[ids] - lr * g / (jnp.sqrt(acc_rows) + eps)[:, None]
            return table.at[ids].set(rows), acc.at[ids].set(acc_rows)

        def step(tree, batch):
            ids = batch["ids"]
            embedded = [tree["tables"][n][ids[:, f]] for f, n in enumerate(names)]
            loss, (g_dense, g_embedded) = jax.value_and_grad(loss_of, argnums=(0, 1))(
                tree["dense"], embedded, batch
            )
            dense_acc = tree_map(lambda a, g: a + g * g, tree["dense_acc"], g_dense)
            dense = tree_map(
                lambda w, a, g: w - lr * g / (jnp.sqrt(a) + eps),
                tree["dense"], dense_acc, g_dense,
            )
            tables, table_acc = {}, {}
            for f, n in enumerate(names):
                tables[n], table_acc[n] = touch(
                    tree["tables"][n], tree["table_acc"][n], ids[:, f], g_embedded[f]
                )
            return {
                "step": tree["step"] + 1, "tables": tables, "table_acc": table_acc,
                "dense": dense, "dense_acc": dense_acc,
            }, loss

        self._whole = NamedSharding(mesh, P())
        self.shardings = tree_map(lambda _: self._whole, jax.eval_shape(init, prng_key(0)))
        self._init = jax.jit(init, out_shardings=self.shardings)
        self.step = jax.jit(step, donate_argnums=0)

    def make(self, seed: int):
        return self._init(prng_key(seed))

    def batch_pool(self, seed: int, batch: Sequence[int], n: int) -> List[Any]:
        """``n`` batches of ``prod(batch)`` samples, on the device: dense
        features, labels, and one id a table below the rows held here (what
        the deployment's id exchange would hand this rank)."""
        import jax

        rng, samples = np.random.default_rng(seed), int(np.prod(batch))
        pool = []
        for _ in range(n):
            ids = np.stack(
                [rng.integers(0, rows, size=samples, dtype=np.int32) for rows in self.rows],
                axis=1,
            )
            pool.append({
                "dense": rng.standard_normal((samples, self.dense_in), dtype=np.float32),
                "ids": ids,
                "labels": (rng.random(samples) < 0.25).astype(np.float32),
            })
        return [jax.device_put(b, self._whole) for b in pool]


factory = StateFactory
