"""GPipe-style pipeline parallelism over the ``pod`` axis (beyond-paper).

The multi-pod mesh (pod=2, data=16, model=16) can map the pod axis to
pipeline stages instead of pure data parallelism: each pod holds half the
layer stack; microbatches stream through stages via collective_permute
(point-to-point over the slow inter-pod links — bytes per hop are
activations (mb, S, d) instead of a full gradient all-reduce, which is the
winning trade when d_model is small relative to params/layer).

Implemented with shard_map over the pipeline axis; the classic GPipe
schedule with (n_micro + n_stages - 1) ticks; bubble fraction
(n_stages-1)/(n_micro+n_stages-1).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(mesh, pp_axis: str, body: Callable, stage_params,
                     x_micro, *, layers_per_stage: int):
    """Run microbatches through pipeline stages.

    body(params_slice, h) -> h : applies ONE stage's layer block
    stage_params: pytree whose leaves have leading dim n_stages (sharded on
                  pp_axis outside).
    x_micro: (n_micro, mb, S, d) microbatched activations (replicated over
             pp_axis; only stage 0's input matters).
    Returns (n_micro, mb, S, d) outputs (valid on the last stage, broadcast
    to all).
    """
    n_stages = mesh.shape[pp_axis]
    n_micro = x_micro.shape[0]

    def staged(params_local, xs):
        # params_local: this stage's params (leading dim 1) ; xs: all micro
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(pp_axis)
        ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(state, t):
            carry, outputs = state
            # stage 0 ingests microbatch t (if in range), others take carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            h_in = jnp.where(stage == 0, inject, carry)
            valid = (t - stage >= 0) & (t - stage < n_micro)
            h_out = body(params_local, h_in)
            h_out = jnp.where(valid, h_out, h_in)
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = (stage == n_stages - 1) & valid & (t - stage >= 0)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            newv = jnp.where(record, h_out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, newv, out_idx, 0)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry = jax.lax.ppermute(h_out, pp_axis, perm)
            return (carry, outputs), None

        (carry, outputs), _ = jax.lax.scan(
            tick, (carry_in, outputs), jnp.arange(ticks))
        # broadcast the last stage's outputs to every stage (ppermute
        # requires unique src/dst pairs, so gather + select instead)
        all_outs = jax.lax.all_gather(outputs, pp_axis)
        return all_outs[n_stages - 1]

    pspec = jax.tree.map(lambda _: P(pp_axis), stage_params)
    # fully-manual region (no axis_names subset): partially-auto shard_map
    # lowers axis_index through PartitionId, which the SPMD partitioner in
    # the installed XLA rejects; in a fully-manual region it is supported
    return jax.shard_map(
        staged, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
