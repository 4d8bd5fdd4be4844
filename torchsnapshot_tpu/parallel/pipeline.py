"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

Stage s holds layer s's weights (an array sharded ``P("pp")`` on its
leading dim); activations flow stage→stage over the ICI ring with
``lax.ppermute`` while ``lax.scan`` walks the schedule — the classic
(n_microbatches + n_stages - 1)-step pipeline, expressed as compiler-
friendly static control flow (no data-dependent Python branching under
jit, SPMD over the mesh).

The reference has no pipeline-parallel code (SURVEY §2.1: PP is subsumed
by sharding metadata for *checkpointing*); this module exists because a
TPU training framework needs the op itself, and its per-stage weights
are exactly the pp-sharded arrays the checkpointer persists, reshards,
and restores elastically (e.g. onto a different pipeline depth's mesh or
a fully-replicated eval topology).

Each stage here is one MLP block ``h = relu(h @ W + b)``; the schedule
generalizes to any per-stage apply.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _shard_map(fn, mesh, in_specs, out_specs):
    # the masked psum broadcast of the last stage's outputs is varying
    # by construction; skip the replication checker
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def init_pipeline_params(key, n_stages: int, d_model: int, dtype=jnp.float32):
    """Per-stage MLP weights, leading dim = stage (shard it ``P("pp")``)."""
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (n_stages, d_model, d_model), dtype) * (
        1.0 / jnp.sqrt(d_model).astype(dtype)
    )
    b = jnp.zeros((n_stages, d_model), dtype)
    return {"w": w, "b": b}


def sequential_forward(params, x):
    """Oracle: apply the stages in order without any parallelism."""
    h = x
    for s in range(params["w"].shape[0]):
        h = jax.nn.relu(h @ params["w"][s] + params["b"][s])
    return h


def pipeline_forward(
    params, x, mesh, axis_name: str = "pp", n_microbatches: int = 4
):
    """Microbatched pipeline forward over ``mesh[axis_name]``.

    params: {"w": [S, d, d], "b": [S, d]} sharded P(axis_name) on dim 0;
    x: [B, d] (B divisible by n_microbatches), replicated.
    Returns [B, d] (replicated), bitwise the composition of the stages.
    """
    from ..obs import span

    # span covers shard_map construction + (first call) XLA tracing —
    # the host-side cost a trace of a training loop needs attributed
    with span(
        "pp/forward", axis=axis_name, n_microbatches=n_microbatches
    ):
        return _pipeline_forward_impl(
            params, x, mesh, axis_name, n_microbatches
        )


def _pipeline_forward_impl(
    params, x, mesh, axis_name: str, n_microbatches: int
):
    n_stages = mesh.shape[axis_name]
    if params["w"].shape[0] != n_stages:
        # a user-facing precondition (e.g. weights restored onto a mesh
        # of different pipeline depth), not an internal invariant: must
        # fail under `python -O` too — a stripped assert would silently
        # run a wrong schedule
        raise ValueError(
            f"stage dim {params['w'].shape[0]} != pp axis size "
            f"{n_stages}; reshard the stage weights to the mesh depth"
        )
    batch, d = x.shape
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by {n_microbatches} microbatches"
        )
    mb = batch // n_microbatches

    def stage_fn(w, b, x_local):
        # w: [1, d, d]; b: [1, d]; x_local: [B, d] (replicated in)
        idx = lax.axis_index(axis_name)
        w0, b0 = w[0], b[0]
        micro = x_local.reshape(n_microbatches, mb, d)
        n_steps = n_microbatches + n_stages - 1

        def step(carry, t):
            acts, outs = carry  # acts: [mb, d] in-flight activation
            # stage 0 injects microbatch t (when in range); others use
            # the activation ppermute'd from the previous stage
            inject = micro[jnp.clip(t, 0, n_microbatches - 1)]
            h_in = jnp.where(idx == 0, inject, acts)
            active = jnp.logical_and(t - idx >= 0, t - idx < n_microbatches)
            h_out = jax.nn.relu(h_in @ w0 + b0)
            h_out = jnp.where(active, h_out, jnp.zeros_like(h_out))
            # the LAST stage's output for microbatch (t - S + 1) is final
            done_mb = t - (n_stages - 1)
            is_final = jnp.logical_and(
                idx == n_stages - 1,
                jnp.logical_and(done_mb >= 0, done_mb < n_microbatches),
            )
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(
                    is_final, h_out, outs[jnp.clip(done_mb, 0, n_microbatches - 1)]
                ),
                jnp.clip(done_mb, 0, n_microbatches - 1),
                axis=0,
            )
            # rotate activations one stage forward for the next step
            acts_next = lax.ppermute(
                h_out,
                axis_name,
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (acts_next, outs), None

        acts0 = jnp.zeros((mb, d), x_local.dtype)
        outs0 = jnp.zeros((n_microbatches, mb, d), x_local.dtype)
        (_, outs), _ = lax.scan(
            step, (acts0, outs0), jnp.arange(n_steps)
        )
        # only the last stage holds real outputs; psum of the masked
        # value broadcasts them (ppermute can't fan out one source)
        outs = lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name,
        )
        return outs.reshape(batch, d)

    fn = _shard_map(
        stage_fn,
        mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=P(),
    )
    return fn(params["w"], params["b"], x)


@functools.lru_cache(maxsize=16)
def _jitted_train_step(mesh, axis_name: str, n_microbatches: int, lr: float):
    """One compiled step per (mesh, schedule) config: pipeline_forward
    closes over a fresh shard_map each call, so an uncached step would
    retrace value_and_grad + scan every iteration."""

    def step(params, x, y):
        def loss_fn(p):
            out = pipeline_forward(
                p, x, mesh,
                axis_name=axis_name, n_microbatches=n_microbatches,
            )
            return jnp.mean((out - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads
        )
        return new_params, loss

    return jax.jit(step)


def pipeline_train_step(
    params, x, y, mesh, axis_name: str = "pp",
    n_microbatches: int = 4, lr: float = 0.1,
) -> Tuple[dict, jax.Array]:
    """One SGD step through the pipelined forward (grads flow through
    scan + ppermute).  Compiled once per (mesh, schedule) config."""
    from ..obs import span

    with span("pp/train_step", axis=axis_name, n_microbatches=n_microbatches):
        return _jitted_train_step(mesh, axis_name, n_microbatches, float(lr))(
            params, x, y
        )


def shard_pipeline_params(params, mesh, axis_name: str = "pp"):
    """Place per-stage params with stage dim sharded over the pp axis."""
    spec3 = NamedSharding(mesh, P(axis_name, None, None))
    spec2 = NamedSharding(mesh, P(axis_name, None))
    return {
        "w": jax.device_put(params["w"], spec3),
        "b": jax.device_put(params["b"], spec2),
    }
