"""The train state that ONE rank of a recommender job with row-wise sharded
embedding tables holds, said plainly: its leaves, the 16 ranks' shares of a
table, its train step and how a committed snapshot's bytes are read back, a
leaf written in chunks among them.  Plain Python and numpy; it imports
nothing of the program and nothing of the state file it is held against
(``chipbench/states/dlrm_rowwise.py``).

The tree.  ``tables/tNN`` ``[rows, D]`` and ``table_acc/tNN`` ``[rows]`` for
each sparse feature, at the rows this rank holds; ``dense/bot/<i>/{w,b}``
and ``dense/top/<i>/{w,b}``, weights ``[in, out]``, with ``dense_acc/...``
of the same shapes; an int32 ``step``.  Every other leaf is float32.

Row-wise sharding.  A table of ``n`` rows over ``R`` ranks gives rank ``r``
the rows ``[r * p, min((r + 1) * p, n))``, ``p = ceil(n / R)``: every rank
but the last as many, a table of fewer than ``R`` rows one row to each of
its first ranks and none to the others.

The step is the public DLRM and the two Adagrads its example trains with:

    x      = bottom MLP of the dense features, a ReLU after every layer
    e_f    = tables[f][ids[:, f]]              one row a feature a sample
    T      = [x, e_0, ..., e_{F-1}]            [B, F + 1, D]
    Z      = T @ T^T, its entries above the diagonal, row by row
    logit  = top MLP of [x, those entries], a ReLU after all but the last
    loss   = mean over the batch of max(z, 0) - z * y + log(1 + exp(-|z|))
    dense  : acc += g**2;            w   -= lr * g / (sqrt(acc) + eps)
    table  : g_r = the sum of row r's gradients over the batch
             acc[r] += mean(g_r**2); row -= lr * g_r / (sqrt(acc[r]) + eps)

and a row that no sample of the batch names is not touched.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

F32 = np.float32

Spec = List[Tuple[str, Tuple[int, ...], str]]


# ------------------------------------------------------------------ leaves


def held_rows(published: Sequence[int], ranks: int) -> List[int]:
    """Rows of each table on rank 0: ``ceil(n / ranks)``."""
    return [-(-n // ranks) for n in published]


def rank_rows(n: int, ranks: int, rank: int) -> Tuple[int, int]:
    """The rows ``[lo, hi)`` of a table of ``n`` rows that ``rank`` holds."""
    if not 0 <= rank < ranks:
        raise ValueError(f"no rank {rank} of {ranks}")
    per = -(-n // ranks)
    return min(rank * per, n), min((rank + 1) * per, n)


def mlp_widths(conf: Dict[str, Any]) -> Tuple[List[int], List[int]]:
    """Inputs and layer widths of the bottom and of the top MLP."""
    dim, tables = conf["embedding_dim"], len(conf["num_embeddings_per_feature"])
    pairs = (tables + 1) * tables // 2
    return (
        [conf["dense_in_features"], *conf["dense_arch_layer_sizes"]],
        [dim + pairs, *conf["over_arch_layer_sizes"]],
    )


def dense_spec(conf: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    out: List[Tuple[str, Tuple[int, ...]]] = []
    for name, widths in zip(("bot", "top"), mlp_widths(conf)):
        for i, (fan_in, fan_out) in enumerate(zip(widths[:-1], widths[1:])):
            out += [(f"{name}/{i}/w", (fan_in, fan_out)), (f"{name}/{i}/b", (fan_out,))]
    return out


def tree_spec(conf: Dict[str, Any]) -> Spec:
    """Every array leaf of the state rank 0 holds: ``(path, shape, dtype)``."""
    dim = conf["embedding_dim"]
    out: Spec = [("step", (), "int32")]
    for f, rows in enumerate(conf["num_embeddings_per_feature"]):
        out += [(f"tables/t{f:02d}", (rows, dim), "float32"),
                (f"table_acc/t{f:02d}", (rows,), "float32")]
    for group in ("dense", "dense_acc"):
        out += [(f"{group}/{name}", shape, "float32") for name, shape in dense_spec(conf)]
    return out


def leaf_nbytes(shape: Tuple[int, ...], dtype: str) -> int:
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def spec_bytes(spec: Spec) -> int:
    return sum(leaf_nbytes(shape, dtype) for _, shape, dtype in spec)


def chunk_rows(shape: Tuple[int, ...], dtype: str, limit: int) -> List[Tuple[int, int]]:
    """The row ranges a leaf over ``limit`` bytes is written in: as many
    whole rows as ``limit`` holds, the last range what is left."""
    row = leaf_nbytes(shape[1:], dtype)
    per = max(1, limit // row)
    return [(lo, min(lo + per, shape[0])) for lo in range(0, shape[0], per)]


# -------------------------------------------------------------------- step


def _forward(layers: List[Tuple[np.ndarray, np.ndarray]], x: np.ndarray, relu_last: bool):
    """The layers' inputs and pre-activations, and the output."""
    seen = []
    for i, (w, b) in enumerate(layers):
        a = x @ w + b
        seen.append((x, a))
        x = np.maximum(a, F32(0)) if relu_last or i < len(layers) - 1 else a
    return seen, x


def _backward(layers, seen, d_out: np.ndarray, relu_last: bool):
    """Gradients of every ``(w, b)`` and of the input, from the output's."""
    grads, d = [None] * len(layers), d_out
    for i in reversed(range(len(layers))):
        x, a = seen[i]
        if relu_last or i < len(layers) - 1:
            d = d * (a > 0)
        grads[i] = (x.T @ d, d.sum(axis=0))
        d = d @ layers[i][0].T
    return grads, d


def _layers(dense: Dict[str, np.ndarray], name: str):
    n = sum(1 for k in dense if k.startswith(name + "/") and k.endswith("/w"))
    return [(dense[f"{name}/{i}/w"], dense[f"{name}/{i}/b"]) for i in range(n)]


def dlrm_step(
    conf: Dict[str, Any], state: Dict[str, Any], batch: Dict[str, np.ndarray]
) -> Tuple[Dict[str, Any], np.float32]:
    """One train step.  ``state``: ``step`` an int; ``tables`` and
    ``table_acc`` name → float32 array; ``dense`` and ``dense_acc``
    ``"bot/0/w"``-style name → float32 array.  ``batch``: ``dense`` [B, 13],
    ``ids`` [B, F] and ``labels`` [B]."""
    lr, eps = F32(conf["optimizer"]["learning_rate"]), F32(conf["optimizer"]["eps"])
    names = sorted(state["tables"])
    ids, y = np.asarray(batch["ids"]), np.asarray(batch["labels"], dtype=F32)
    n, dim = len(y), conf["embedding_dim"]
    bot, top = _layers(state["dense"], "bot"), _layers(state["dense"], "top")

    seen_bot, x = _forward(bot, np.asarray(batch["dense"], dtype=F32), relu_last=True)
    vectors = np.stack([x] + [state["tables"][t][ids[:, f]] for f, t in enumerate(names)], axis=1)
    pairs = vectors @ vectors.transpose(0, 2, 1)
    upper = np.triu_indices(len(names) + 1, k=1)
    seen_top, out = _forward(top, np.concatenate([x, pairs[:, upper[0], upper[1]]], axis=1), False)
    z = out[:, 0]
    loss = np.mean(np.maximum(z, F32(0)) - z * y + np.log1p(np.exp(-np.abs(z))), dtype=F32)

    d_z = ((F32(1) / (F32(1) + np.exp(-z))) - y) / F32(n)
    g_top, d_seen = _backward(top, seen_top, d_z[:, None].astype(F32), relu_last=False)
    d_pairs = np.zeros_like(pairs)
    d_pairs[:, upper[0], upper[1]] = d_seen[:, dim:]
    d_vectors = (d_pairs + d_pairs.transpose(0, 2, 1)) @ vectors
    g_bot, _ = _backward(bot, seen_bot, d_seen[:, :dim] + d_vectors[:, 0], relu_last=True)

    new: Dict[str, Any] = {
        "step": state["step"] + 1, "tables": {}, "table_acc": {}, "dense": {}, "dense_acc": {},
    }
    for name, grads in (("bot", g_bot), ("top", g_top)):
        for i, pair in enumerate(grads):
            for kind, g in zip("wb", pair):
                key = f"{name}/{i}/{kind}"
                acc = state["dense_acc"][key] + g * g
                new["dense_acc"][key] = acc.astype(F32)
                new["dense"][key] = (state["dense"][key] - lr * g / (np.sqrt(acc) + eps)).astype(F32)
    for f, t in enumerate(names):
        table, acc = state["tables"][t].copy(), state["table_acc"][t].copy()
        touched, where = np.unique(ids[:, f], return_inverse=True)
        g_rows = np.zeros((len(touched), dim), dtype=F32)
        np.add.at(g_rows, where.reshape(-1), d_vectors[:, 1 + f])
        acc[touched] += np.mean(g_rows * g_rows, axis=1, dtype=F32)
        table[touched] -= lr * g_rows / (np.sqrt(acc[touched]) + eps)[:, None]
        new["tables"][t], new["table_acc"][t] = table, acc
    return new, loss


# ---------------------------------------------------- a committed snapshot


def read_manifest(snapshot_dir: str) -> Dict[str, Any]:
    """The commit marker's JSON (its last line is a checksum comment)."""
    with open(os.path.join(snapshot_dir, ".snapshot_metadata")) as f:
        body = "".join(line for line in f if not line.startswith("#"))
    return json.loads(body)


def _read(snapshot_dir: str, record: Dict[str, Any], size: int, what: str) -> bytes:
    """``size`` bytes of one object at the record's ``(location, byte_range)``."""
    lo, hi = record.get("byte_range") or (0, size)
    if hi - lo != size:
        raise ValueError(f"{what}: {hi - lo} B in the manifest, {size} by its shape")
    with open(os.path.join(snapshot_dir, record["location"]), "rb") as f:
        f.seek(lo)
        raw = f.read(size)
    if len(raw) != size:
        raise ValueError(f"{what}: {record['location']} ends inside its range")
    return raw


def leaf_bytes(snapshot_dir: str, under: str = "0/ts/") -> Dict[str, Dict[str, Any]]:
    """Every array leaf of a committed snapshot by plain file reads: path
    below ``under`` → its ``bytes``, ``dtype``, ``shape`` and ``chunks``.  A
    leaf written whole (an ``Array`` entry) is one read at its ``(location,
    byte_range)`` and has no chunks.  A leaf written in chunks (a
    ``ChunkedArray`` entry) is its chunk records followed in row order, each
    a whole range of rows ``[offsets[0], offsets[0] + sizes[0])`` that
    starts where the last ended, read at its own ``(location, byte_range)``,
    and the ranges' bytes one after another; ``chunks`` lists them as
    ``(first row, end row, location, bytes)``."""
    leaves: Dict[str, Dict[str, Any]] = {}
    for path, entry in read_manifest(snapshot_dir)["manifest"].items():
        if not path.startswith(under) or entry["type"] in ("dict", "list"):
            continue
        shape, dtype = tuple(entry["shape"]), entry["dtype"]
        if entry["type"] == "Array" and entry["serializer"] == "buffer_protocol":
            raw, chunks = _read(snapshot_dir, entry, leaf_nbytes(shape, dtype), path), []
        elif entry["type"] == "ChunkedArray":
            parts, chunks, at = [], [], 0
            for record in sorted(entry["chunks"], key=lambda c: c["offsets"][0]):
                offsets, sizes = record["offsets"], tuple(record["sizes"])
                if offsets[0] != at or any(offsets[1:]) or sizes[1:] != shape[1:]:
                    raise ValueError(f"{path}: the chunk at {offsets} is no whole range of rows after row {at}")
                size = leaf_nbytes(sizes, dtype)
                parts.append(_read(snapshot_dir, record, size, f"{path} rows {at}+{sizes[0]}"))
                chunks.append((at, at + sizes[0], record["location"], size))
                at += sizes[0]
            if at != shape[0]:
                raise ValueError(f"{path}: the chunks end at row {at} of {shape[0]}")
            raw = b"".join(parts)
        else:
            raise ValueError(f"{path}: no plain read of a {entry['type']} entry")
        leaves[path[len(under):]] = {"bytes": raw, "dtype": dtype, "shape": shape, "chunks": chunks}
    return leaves
