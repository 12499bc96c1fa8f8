"""Pipeline parallelism: microbatched GPipe over the `pipeline` mesh axis.

TPU-native design (SURVEY §2d requires PP first-class; the reference
delegates it to vLLM — llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:173): stage parameters carry a leading `stage` dimension
sharded over the `pipeline` mesh axis; one shard_map program runs the
rotating-microbatch schedule with `ppermute` moving activations stage→stage
over ICI. The schedule is written as a forward `lax.scan` only — reverse-mode
AD differentiates through the scan and ppermutes, so the backward pipeline
(activations reverse-flowing) is derived by the compiler rather than
hand-scheduled, and `jax.checkpoint` on the stage function gives 1F1B-grade
memory behavior (stash only stage inputs, recompute internals).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack S per-stage param pytrees into one tree with a leading stage
    axis (shard it on `pipeline` via the 'stage' logical axis)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def gpipe(stage_fn: Callable, num_stages: int, num_microbatches: int,
          mesh: Mesh, axis_name: str = "pipeline",
          remat: bool = True) -> Callable:
    """Build `fn(stacked_params, x) -> y` running the GPipe schedule.

    stage_fn(params_s, x_mb) -> y_mb applies ONE stage to ONE microbatch
    (shapes of x_mb and y_mb must match — the usual transformer-block
    contract). x has leading batch dim divisible by num_microbatches.
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def pipelined(stacked_params, x):
        mb = jnp.reshape(x, (num_microbatches, -1) + x.shape[1:])

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(axis_name), P()),  # params: stage-sharded; x: repl
            out_specs=P(),
            check_vma=False)
        def run(params_shard, mb_all):
            # Each device holds its stage's params with leading dim 1.
            params_local = jax.tree_util.tree_map(
                lambda p: jnp.squeeze(p, 0), params_shard)
            stage = jax.lax.axis_index(axis_name)
            S, M = num_stages, num_microbatches
            total = M + S - 1
            perm = [(i, (i + 1) % S) for i in range(S)]

            def step(carry, t):
                send, acc = carry
                recv = jax.lax.ppermute(send, axis_name, perm)
                mb_index = jnp.clip(t, 0, M - 1)
                first_stage_in = jax.lax.dynamic_index_in_dim(
                    mb_all, mb_index, axis=0, keepdims=False)
                x_in = jnp.where(stage == 0, first_stage_in, recv)
                y = stage_fn(params_local, x_in)
                out_slot = t - (S - 1)
                is_output = jnp.logical_and(stage == S - 1, out_slot >= 0)
                acc = jax.lax.cond(
                    is_output,
                    lambda a: jax.lax.dynamic_update_index_in_dim(
                        a, y, jnp.clip(out_slot, 0, M - 1), axis=0),
                    lambda a: a, acc)
                return (y, acc), None

            send0 = jnp.zeros_like(mb_all[0])
            acc0 = jnp.zeros_like(mb_all)
            (_, acc), _ = jax.lax.scan(step, (send0, acc0),
                                       jnp.arange(total))
            # Only the last stage holds real outputs; broadcast them.
            acc = jnp.where(stage == S - 1, acc, jnp.zeros_like(acc))
            return jax.lax.psum(acc, axis_name)

        out = run(stacked_params, mb)
        return jnp.reshape(out, x.shape[:1] + out.shape[2:])

    return pipelined


def split_layers_into_stages(layer_params: list, num_stages: int) -> list:
    """Group L per-layer param trees into S stacked per-stage trees
    (each stage applies L/S layers sequentially)."""
    L = len(layer_params)
    if L % num_stages != 0:
        raise ValueError(f"{L} layers not divisible into {num_stages} stages")
    per = L // num_stages
    stages = []
    for s in range(num_stages):
        group = layer_params[s * per:(s + 1) * per]
        stages.append(jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *group))
    return stages


def make_stage_fn(layer_fn: Callable) -> Callable:
    """Lift layer_fn(params_l, x) -> x into a stage applying its stacked
    layers with a scan (keeps the stage a single compiled loop)."""
    def stage_fn(stage_params, x):
        def body(h, params_l):
            return layer_fn(params_l, h), None
        out, _ = jax.lax.scan(body, x, stage_params)
        return out
    return stage_fn
