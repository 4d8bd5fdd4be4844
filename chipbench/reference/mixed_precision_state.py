"""The train state that ONE rank of an expert-parallel, mixed-precision job
holds, said plainly: its leaves, its optimizer step and how a committed
snapshot's bytes are read back.  Plain Python and numpy; it imports nothing
of the program and nothing of the state file it is held against
(``chipbench/states/moe_rank_mp.py``).

The tree.  Every parameter is held four times: ``params`` in bfloat16 (what
the forward pass reads), ``master`` in float32, and Adam's ``mu`` and ``nu``
in float32: 14 bytes a parameter, a seventh of them 2-byte (Megatron-LM's
mixed-precision optimizer; Micikevicius et al., arXiv:1710.03740).  Beside
them one int32 ``step``.  The parameter leaves are those of the published
``config.json`` of a latent-attention (MLA) mixture-of-experts decoder, one
leaf a matrix an expert, at the share one rank holds: ``n_routed_experts``
of the ``n_routed_experts * expert_parallel_size`` the router scores,
``vocab_size`` of ``vocab_size * vocab_parallel_size`` rows of the embedding
and of the untied head, ``num_hidden_layers`` layers of which the first
``first_k_dense_replace`` are dense.  Matrices are ``[in, out]``.

The step is the deployment's optimizer, not its model.  The gradient comes
from a surrogate loss that needs no forward pass of the model (a
checkpointer sees bytes), evaluated at the bfloat16 parameters and taken in
float32, as a job that accumulates its gradients in float32 has it:

    h      = sum over the batch's N tokens of embed_tokens[token], / sqrt(N)
    CE     = logsumexp(lm_head @ h) - mean over tokens of (lm_head @ h)[token]
    x(n)   = h repeated cyclically to length n, scaled to unit RMS, and held
             constant (no gradient flows through a probe)
    matrix W [a, b]:  1/2 mean((x(a) @ W)**2)   gradient outer(x, x @ W) / b
    vector v [n]:     1/2 mean((v * x(n))**2)   gradient v * x**2 / n
    loss   = CE + the sum of the leaves' terms (embed_tokens and lm_head are
             in CE alone)

so every leaf's gradient depends on the batch and none is zero, and the
update is AdamW with bias correction: moments and master in float32,
``params = bfloat16(master)`` after it.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

GROUPS = ("params", "master", "mu", "nu")
F32 = np.float32

Spec = List[Tuple[str, Tuple[int, ...], Optional[Tuple[int, int]]]]


# ------------------------------------------------------------------ leaves


def attention_spec(conf: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """The nine leaves of one layer outside its feed-forward: MLA's two
    low-rank paths with their norms, the output projection, two norms."""
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    q_rank, kv_rank = conf["q_lora_rank"], conf["kv_lora_rank"]
    nope, rope, v = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    return [
        ("input_layernorm", (d,)),
        ("self_attn/q_a_proj", (d, q_rank)),
        ("self_attn/q_a_layernorm", (q_rank,)),
        ("self_attn/q_b_proj", (q_rank, heads * (nope + rope))),
        ("self_attn/kv_a_proj_with_mqa", (d, kv_rank + rope)),
        ("self_attn/kv_a_layernorm", (kv_rank,)),
        ("self_attn/kv_b_proj", (kv_rank, heads * (nope + v))),
        ("self_attn/o_proj", (heads * v, d)),
        ("post_attention_layernorm", (d,)),
    ]


def _mlp(prefix: str, d: int, width: int) -> List[Tuple[str, Tuple[int, ...]]]:
    return [
        (f"{prefix}/gate_proj", (d, width)),
        (f"{prefix}/up_proj", (d, width)),
        (f"{prefix}/down_proj", (width, d)),
    ]


def vocab_rows(conf: Dict[str, Any], vocab_rank: int) -> Tuple[int, int]:
    """The rows of the whole vocabulary that slice ``vocab_rank`` holds."""
    if not 0 <= vocab_rank < conf["vocab_parallel_size"]:
        raise ValueError(f"no vocabulary slice {vocab_rank}")
    return vocab_rank * conf["vocab_size"], (vocab_rank + 1) * conf["vocab_size"]


def param_spec(conf: Dict[str, Any], ep_rank: int = 0, vocab_rank: int = 0) -> Spec:
    """The parameter leaves one rank holds: ``(name, shape, rows)``.  An
    expert is named by its index among ALL the experts of its layer, as the
    published checkpoint names it; ``rows`` are the rows of the whole
    vocabulary a slice holds, None for every other leaf."""
    if not 0 <= ep_rank < conf["expert_parallel_size"]:
        raise ValueError(f"no expert-parallel rank {ep_rank}")
    d, held = conf["hidden_size"], conf["n_routed_experts"]
    rows = vocab_rows(conf, vocab_rank)
    out: Spec = [("embed_tokens", (conf["vocab_size"], d), rows)]
    for layer in range(conf["num_hidden_layers"]):
        at = f"layers/{layer:02d}"
        leaves = list(attention_spec(conf))
        if layer < conf["first_k_dense_replace"]:
            leaves += _mlp("mlp", d, conf["intermediate_size"])
        else:
            # the router scores every expert of the layer, held here or not
            scored = held * conf["expert_parallel_size"]
            leaves += [("mlp/gate/weight", (d, scored)),
                       ("mlp/gate/e_score_correction_bias", (scored,))]
            width = conf["moe_intermediate_size"]
            leaves += _mlp("mlp/shared_experts", d, width * conf["n_shared_experts"])
            for e in range(ep_rank * held, (ep_rank + 1) * held):
                leaves += _mlp(f"mlp/experts/{e:03d}", d, width)
        out += [(f"{at}/{name}", shape, None) for name, shape in leaves]
    out += [("norm", (d,), None), ("lm_head", (conf["vocab_size"], d), rows)]
    return out


def whole(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The same layers uncut: every expert and the whole vocabulary on one
    rank (the depth stays: the layers left out lie on further stages)."""
    return dict(
        conf,
        n_routed_experts=conf["n_routed_experts"] * conf["expert_parallel_size"],
        vocab_size=conf["vocab_size"] * conf["vocab_parallel_size"],
        expert_parallel_size=1, vocab_parallel_size=1,
    )


def tree_spec(conf: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every array leaf of the state rank 0 holds: ``(path, shape, dtype)``."""
    out: List[Tuple[str, Tuple[int, ...], str]] = [("step", (), "int32")]
    for group in GROUPS:
        dtype = "bfloat16" if group == "params" else "float32"
        out += [(f"{group}/{name}", shape, dtype) for name, shape, _ in param_spec(conf)]
    return out


def parameter_count(spec: Spec) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in spec)


def width_of(dtype: str) -> int:
    """Bytes an element (numpy has no bfloat16 of its own)."""
    return 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize


def spec_bytes(spec: List[Tuple[str, Tuple[int, ...], str]]) -> Dict[int, int]:
    """Bytes of a ``tree_spec`` by element width."""
    out: Dict[int, int] = {}
    for _, shape, dtype in spec:
        width = width_of(dtype)
        out[width] = out.get(width, 0) + width * int(np.prod(shape))
    return out


# ---------------------------------------------------------------- bfloat16


def to_bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 → the 16 bits of the nearest bfloat16, ties to even (finite
    values: the state holds no NaN)."""
    bits = np.ascontiguousarray(x, dtype=F32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (rounded >> np.uint32(16)).astype(np.uint16)


def from_bf16_bits(bits: np.ndarray) -> np.ndarray:
    return (bits.astype(np.uint32) << np.uint32(16)).view(F32)


# -------------------------------------------------------------------- step


def probe(h: np.ndarray, n: int) -> np.ndarray:
    x = np.resize(h, n).astype(F32)
    return x / np.sqrt(np.mean(x * x, dtype=F32))


def surrogate_grads(
    params: Dict[str, np.ndarray], tokens: np.ndarray
) -> Tuple[np.float32, Dict[str, np.ndarray]]:
    """The surrogate loss at float32 ``params`` (name → array) and its
    gradient for every leaf, in float32."""
    tokens = np.asarray(tokens).reshape(-1)
    embed, head = params["embed_tokens"], params["lm_head"]
    counts = np.bincount(tokens, minlength=embed.shape[0]).astype(F32)
    share, bag = counts / F32(tokens.size), counts / np.sqrt(F32(tokens.size))
    h = bag @ embed
    z = head @ h
    top = z.max()
    lse = top + np.log(np.sum(np.exp(z - top), dtype=F32))
    loss = lse - share @ z
    dz = np.exp(z - lse) - share
    grads = {
        "lm_head": np.outer(dz, h),
        "embed_tokens": np.outer(bag, head.T @ dz),
    }
    for name, w in params.items():
        if name in grads:
            continue
        x = probe(h, w.shape[0])
        y = x @ w if w.ndim == 2 else w * x
        loss = loss + F32(0.5) * np.mean(y * y, dtype=F32)
        grads[name] = (np.outer(x, y) if w.ndim == 2 else y * x) / F32(y.size)
    return F32(loss), {k: g.astype(F32) for k, g in grads.items()}


def adamw_mp_step(
    conf: Dict[str, Any], state: Dict[str, Any], tokens: np.ndarray
) -> Tuple[Dict[str, Any], np.float32]:
    """One mixed-precision AdamW step.  ``state``: ``step`` an int, ``params``
    name → bfloat16 bits (uint16), ``master``, ``mu``, ``nu`` name → float32."""
    o = conf["optimizer"]
    lr, b1, b2 = F32(o["learning_rate"]), F32(o["b1"]), F32(o["b2"])
    eps, decay = F32(o["eps"]), F32(o["weight_decay"])
    seen = {k: from_bf16_bits(v) for k, v in state["params"].items()}
    loss, grads = surrogate_grads(seen, tokens)
    t = state["step"] + 1
    c1, c2 = F32(1) - b1 ** F32(t), F32(1) - b2 ** F32(t)
    new: Dict[str, Any] = {"step": t, "params": {}, "master": {}, "mu": {}, "nu": {}}
    for name, g in grads.items():
        # 1 - b is taken in double and then rounded, as a constant is
        mu = b1 * state["mu"][name] + F32(1 - o["b1"]) * g
        nu = b2 * state["nu"][name] + F32(1 - o["b2"]) * g * g
        update = (mu / c1) / (np.sqrt(nu / c2) + eps)
        master = state["master"][name] - lr * (update + decay * state["master"][name])
        new["mu"][name], new["nu"][name] = mu.astype(F32), nu.astype(F32)
        new["master"][name] = master.astype(F32)
        new["params"][name] = to_bf16_bits(new["master"][name])
    return new, loss


# ---------------------------------------------------- a committed snapshot


def read_manifest(snapshot_dir: str) -> Dict[str, Any]:
    """The commit marker's JSON (its last line is a checksum comment)."""
    with open(os.path.join(snapshot_dir, ".snapshot_metadata")) as f:
        body = "".join(line for line in f if not line.startswith("#"))
    return json.loads(body)


def leaf_bytes(snapshot_dir: str, under: str = "0/ts/") -> Tuple[Dict[str, Dict[str, Any]], Dict[str, set]]:
    """Every array leaf of a committed snapshot by plain file reads at the
    manifest's ``(location, byte_range)``: path below ``under`` → its
    ``bytes``, ``dtype``, ``shape`` and ``location``; and, for every object
    that holds more than one leaf (a slab), the set of its members' element
    widths."""
    leaves: Dict[str, Dict[str, Any]] = {}
    members: Dict[str, List[int]] = {}
    for path, entry in read_manifest(snapshot_dir)["manifest"].items():
        if not path.startswith(under) or entry["type"] in ("dict", "list"):
            continue
        if entry["type"] != "Array" or entry["serializer"] != "buffer_protocol":
            raise ValueError(f"{path}: no plain read of a {entry['type']} entry")
        width = width_of(entry["dtype"])
        size = width * int(np.prod(entry["shape"]))
        lo, hi = entry.get("byte_range") or (0, size)
        if hi - lo != size:
            raise ValueError(f"{path}: {hi - lo} B in the manifest, {size} by its shape")
        with open(os.path.join(snapshot_dir, entry["location"]), "rb") as f:
            f.seek(lo)
            raw = f.read(size)
        if len(raw) != size:
            raise ValueError(f"{path}: {entry['location']} ends inside its range")
        leaves[path[len(under):]] = {
            "bytes": raw, "dtype": entry["dtype"], "shape": tuple(entry["shape"]),
            "location": entry["location"],
        }
        members.setdefault(entry["location"], []).append(width)
    slabs = {loc: set(widths) for loc, widths in members.items() if len(widths) > 1}
    return leaves, slabs
