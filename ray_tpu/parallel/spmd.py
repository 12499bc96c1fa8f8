"""SPMD training: sharded state creation + pjit train step.

This is the hot path of the whole framework: one jitted function per train
step, parameters/optimizer state laid out by logical-axis rules, gradients
synchronized by GSPMD-inserted collectives over ICI (no NCCL-style explicit
allreduce — the mesh IS the communication backend; SURVEY §2d/§5).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (DEFAULT_LOGICAL_AXIS_RULES, kernel_mesh,
                   logical_to_mesh_axes, named_sharding, params_shardings,
                   unbox)


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        updates, new_opt_state = self.tx.update(grads, self.opt_state,
                                                self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params,
                            opt_state=new_opt_state)


def logical_names_tree(model: nn.Module, rng, sample_input) -> Any:
    """Pytree of logical-axis-name tuples (or None) per param leaf."""
    boxed = jax.eval_shape(lambda r: model.init(r, sample_input), rng)
    boxed = boxed["params"]

    def one(leaf):
        if isinstance(leaf, nn.Partitioned):
            return leaf.names
        return None
    return jax.tree_util.tree_map(
        one, boxed, is_leaf=lambda x: isinstance(x, nn.Partitioned))


def shardings_tree(names_tree, mesh: Mesh, rules: Dict[str, Any]):
    def one(names):
        if names is None:
            return NamedSharding(mesh, P())
        return named_sharding(mesh, names, rules)
    return jax.tree_util.tree_map(one, names_tree,
                                  is_leaf=lambda x: x is None
                                  or isinstance(x, tuple))


def train_state_init(model: nn.Module, sample_input, mesh: Mesh,
                     tx: optax.GradientTransformation,
                     rules: Optional[Dict[str, Any]] = None) -> Callable:
    """The function `create_train_state` jits: rng -> TrainState with the
    parameters constrained to their logical axes' shardings on `mesh`.
    Apart so that a program can be compiled from shapes for devices that
    are described and not attached (`jit(...).lower(key).compile()`'s
    `output_shardings` are the state's)."""
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    names = logical_names_tree(model, jax.random.PRNGKey(0), sample_input)
    shardings = shardings_tree(names, mesh, rules)

    def init_fn(r):
        params = unbox(model.init(r, sample_input)["params"])
        params = jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, params, shardings)
        opt_state = tx.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state, apply_fn=model.apply, tx=tx)

    return init_fn


def create_train_state(rng, model: nn.Module, sample_input,
                       mesh: Mesh, tx: optax.GradientTransformation,
                       rules: Optional[Dict[str, Any]] = None) -> TrainState:
    """Initialize parameters *already sharded* across the mesh: the init fn
    is jitted with sharding constraints inside so no host ever materializes
    the full parameter tree."""
    with mesh:
        return jax.jit(train_state_init(model, sample_input, mesh, tx,
                                        rules))(rng)


def make_train_step(loss_fn: Callable, mesh: Mesh,
                    rules: Optional[Dict[str, Any]] = None,
                    batch_axes: Tuple = ("batch", "seq"),
                    donate: bool = True,
                    state: Optional[TrainState] = None):
    """Build the jitted SPMD train step.

    loss_fn(params, batch) -> scalar loss (model.apply inside). The batch is
    constrained to the data axes and the model states its activations'
    layout under the step's kernel mesh (`mesh.constrain`); the collectives
    are GSPMD's problem.

    Pass the concrete initial `state` to pin the step's OUTPUT state to
    the initial state's shardings. Without it, GSPMD may choose output
    layouts that differ from the input's, and the SECOND call — whose
    input is the first call's output — pays a full re-compile (at 7B
    scale that is minutes of XLA time for an identical program).
    """
    # accel plane: arm XLA compile tracking before this step's (large)
    # compile so rtpu_xla_compile_seconds_total sees it
    from .._internal import accel
    accel.ensure_installed()
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    batch_sharding = named_sharding(mesh, batch_axes, rules)

    def step_fn(state: TrainState, batch):
        batch = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, batch_sharding) if x.ndim == len(batch_axes) else x,
            batch)
        # the model shard_maps its Pallas kernels over this mesh and pins
        # its activations to the layout these rules give them on it
        with kernel_mesh(mesh, rules):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        new_state = state.apply_gradients(grads)
        metrics = {"loss": loss,
                   "grad_norm": optax.global_norm(grads)}
        return new_state, metrics

    kwargs: Dict[str, Any] = {}
    if state is not None:
        state_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, state)
        # pytree-prefix: fixed shardings for the state, compiler's
        # choice (None) for the metrics dict
        kwargs["out_shardings"] = (state_shardings, None)
    return jax.jit(step_fn, donate_argnums=(0,) if donate else (),
                   **kwargs)


# ---------------------------------------------------------------------------
# ZeRO-1 sharded weight updates (cross-replica, arxiv 2004.13336)
# ---------------------------------------------------------------------------
#
# The replicated-update schedule every data-parallel rank runs is
# allreduce(grads) -> full Adam -> identical params: W copies of the
# optimizer state and 2x the reduction bytes actually needed. The
# sharded schedule partitions the FLAT optimizer state over the
# data-parallel axes: reduce-scatter the grads (each rank receives the
# reduced 1/W shard it owns), run Adam shard-local on its m/v slice,
# and allgather only the parameter DELTA — optimizer memory drops by W
# and the wire carries reduce-scatter + allgather instead of a full
# allreduce plus W redundant updates. jax.lax.psum_scatter/all_gather
# inside shard_map lower to exactly those HLO collectives (pinned by
# test_train_gspmd's HLO assertion).


@dataclasses.dataclass(frozen=True)
class Zero1Hyper:
    """AdamW hyperparameters for the sharded update (matches
    optax.chain(clip_by_global_norm, adamw) leaf for leaf so the parity
    tests can diff against the reference optimizer bit-for-bit-ish)."""
    learning_rate: Any = 3e-4      # float, or callable(step)->lr
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    clip_norm: float = 0.0         # 0 = no clipping

    def lr(self, step):
        if callable(self.learning_rate):
            return self.learning_rate(step)
        return self.learning_rate


class Zero1State(flax.struct.PyTreeNode):
    """Train state whose optimizer moments live as ONE flat fp32 buffer
    each, sharded over the data-parallel mesh axes (1/W per device)."""
    step: jax.Array
    params: Any
    m: jax.Array                   # (pad_n,) fp32, P(axes)
    v: jax.Array                   # (pad_n,) fp32, P(axes)
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    hyper: Zero1Hyper = flax.struct.field(pytree_node=False)


def _flat_meta(params) -> Tuple[Any, list, int]:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(math.prod(l.shape)) if l.shape else 1 for l in leaves]
    return treedef, sizes, sum(sizes)


def _flatten_f32(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate(
        [l.astype(jnp.float32).reshape(-1) for l in leaves]) \
        if leaves else jnp.zeros((0,), jnp.float32)


def _unflatten_like(flat: jax.Array, params):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out, offset = [], 0
    for leaf in leaves:
        size = int(math.prod(leaf.shape)) if leaf.shape else 1
        part = jax.lax.dynamic_slice_in_dim(flat, offset, size)
        out.append(part.reshape(leaf.shape).astype(leaf.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _zero1_axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(math.prod(mesh.shape[a] for a in axes))


def _check_params_replicated(shardings, axes: Sequence[str]):
    """The flat-buffer schedule slices the param vector over `axes`;
    the params must therefore be replicated over them (they may be
    sharded over OTHER axes only via size-1 — the flat concat cannot
    cross a physical shard boundary)."""
    axset = set(axes)

    def _names(spec):
        for entry in spec:
            if entry is None:
                continue
            for name in (entry if isinstance(entry, tuple) else (entry,)):
                yield name

    for sh in jax.tree_util.tree_leaves(shardings):
        spec = getattr(sh, "spec", None)
        if spec is None:
            continue
        used = set(_names(spec)) & axset
        if used:
            raise ValueError(
                f"zero-1 sharded updates over axes {tuple(axes)} require "
                f"params replicated over them, but a param is sharded "
                f"over {sorted(used)}; drop those rules (dp_rules) or "
                f"pick different update axes")


def create_zero1_state(rng, model: nn.Module, sample_input, mesh: Mesh,
                       hyper: Optional[Zero1Hyper] = None,
                       rules: Optional[Dict[str, Any]] = None,
                       axes: Sequence[str] = ("data",)) -> Zero1State:
    """Initialize params (sharded per rules, replicated over `axes`)
    plus flat m/v buffers partitioned over the data-parallel `axes`."""
    hyper = hyper or Zero1Hyper()
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    names = logical_names_tree(model, rng, sample_input)
    shardings = shardings_tree(names, mesh, rules)
    _check_params_replicated(shardings, axes)
    W = _zero1_axes_size(mesh, axes)

    abstract = jax.eval_shape(
        lambda r: unbox(model.init(r, sample_input)["params"]), rng)
    _, _, n = _flat_meta(abstract)
    pad_n = -(-n // W) * W
    spec = P(tuple(axes) if len(axes) > 1 else axes[0])
    opt_sharding = NamedSharding(mesh, spec)

    def init_fn(r):
        params = unbox(model.init(r, sample_input)["params"])
        params = jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, params, shardings)
        m = jax.lax.with_sharding_constraint(
            jnp.zeros((pad_n,), jnp.float32), opt_sharding)
        v = jax.lax.with_sharding_constraint(
            jnp.zeros((pad_n,), jnp.float32), opt_sharding)
        return Zero1State(step=jnp.zeros((), jnp.int32), params=params,
                          m=m, v=v, apply_fn=model.apply, hyper=hyper)

    with mesh:
        return jax.jit(init_fn)(rng)


def _adam_shard_update(g_l, p_l, m_l, v_l, t, hyper: Zero1Hyper):
    """Shard-local AdamW on the rank's 1/W slice. `t` is the 1-based
    step for bias correction. Returns (delta_l, m_l, v_l) — delta is
    what allgather rebuilds (params never leave their replicas)."""
    m_l = hyper.b1 * m_l + (1.0 - hyper.b1) * g_l
    v_l = hyper.b2 * v_l + (1.0 - hyper.b2) * g_l * g_l
    tf = t.astype(jnp.float32)
    mhat = m_l / (1.0 - hyper.b1 ** tf)
    vhat = v_l / (1.0 - hyper.b2 ** tf)
    update = mhat / (jnp.sqrt(vhat) + hyper.eps)
    if hyper.weight_decay:
        update = update + hyper.weight_decay * p_l
    delta_l = -hyper.lr(t) * update
    return delta_l, m_l, v_l


def _clip_scale(gnorm, clip_norm: float):
    if not clip_norm:
        return 1.0
    # optax.clip_by_global_norm semantics: identity below the threshold,
    # exact rescale to the threshold above it.
    return jnp.where(gnorm < clip_norm, 1.0, clip_norm / gnorm)


def make_zero1_train_step(loss_fn: Callable, mesh: Mesh,
                          state: Zero1State,
                          axes: Sequence[str] = ("data",),
                          donate: bool = True):
    """Fused ZeRO-1 step: per-shard backward on the local microbatch,
    reduce-scatter(mean) of the flat grads, shard-local AdamW,
    allgather of the param delta — one jitted program.

    loss_fn(params, batch) -> scalar loss on the LOCAL microbatch; the
    batch pytree's leading dim is split over `axes` (global batch must
    be divisible by their product). Returns step(state, batch) ->
    (state, {"loss", "grad_norm"})."""
    from .._internal import accel
    accel.ensure_installed()
    axes = tuple(axes)
    W = _zero1_axes_size(mesh, axes)
    hyper = state.hyper
    treedef, sizes, n = _flat_meta(state.params)
    pad_n = int(state.m.size)
    assert pad_n == -(-n // W) * W, (pad_n, n, W)
    shard = pad_n // W
    ax = axes if len(axes) > 1 else axes[0]
    batch_spec = P(ax)

    def step_fn(state: Zero1State, batch):
        params, m, v, step = state.params, state.m, state.v, state.step
        t = step + 1
        param_specs = jax.tree_util.tree_map(lambda _: P(), params)
        batch_specs = jax.tree_util.tree_map(lambda _: batch_spec, batch)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(param_specs, P(ax), P(ax), batch_specs),
            out_specs=(P(), P(ax), P(ax), P(), P()),
            check_vma=False)
        def run(params, m_l, v_l, batch_l):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch_l)
            flat = _flatten_f32(grads)
            flat = jnp.pad(flat, (0, pad_n - n))
            # reduce-scatter: each rank ends with the MEAN grad of the
            # 1/W slice it owns (psum_scatter sums the W local grads)
            g_l = jax.lax.psum_scatter(
                flat, ax, scatter_dimension=0, tiled=True) / W
            # global grad norm from the reduced shards (disjoint slices)
            gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(g_l * g_l), ax))
            g_l = g_l * _clip_scale(gnorm, hyper.clip_norm)
            idx = jax.lax.axis_index(ax)
            flat_p = jnp.pad(_flatten_f32(params), (0, pad_n - n))
            p_l = jax.lax.dynamic_slice_in_dim(flat_p, idx * shard, shard)
            delta_l, m_l, v_l = _adam_shard_update(
                g_l, p_l, m_l, v_l, t, hyper)
            delta = jax.lax.all_gather(delta_l, ax, tiled=True)
            new_params = jax.tree_util.tree_map(
                lambda p, d: p + d.astype(p.dtype),
                params, _unflatten_like(delta[:pad_n], params))
            return (new_params, m_l, v_l, jax.lax.pmean(loss, ax),
                    gnorm)

        new_params, new_m, new_v, loss, gnorm = run(params, m, v, batch)
        new_state = state.replace(step=t, params=new_params,
                                  m=new_m, v=new_v)
        return new_state, {"loss": loss, "grad_norm": gnorm}

    state_shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
    return jax.jit(step_fn, donate_argnums=(0,) if donate else (),
                   out_shardings=(state_shardings, None))


def make_zero1_apply_step(mesh: Mesh, state: Zero1State,
                          axes: Sequence[str] = ("data",),
                          donate: bool = True):
    """Apply-only half of the sharded update for groups whose gradient
    combine happens OUT of program (the cross-slice host/DCN hop via
    `train.allreduce_gradients`): grads arrive already mean-combined
    and replicated; each rank slices its 1/W shard ("scatter" without
    wire bytes), runs shard-local AdamW, and allgathers the delta.
    Returns apply(state, grads) -> state."""
    axes = tuple(axes)
    W = _zero1_axes_size(mesh, axes)
    hyper = state.hyper
    _, _, n = _flat_meta(state.params)
    pad_n = int(state.m.size)
    shard = pad_n // W
    ax = axes if len(axes) > 1 else axes[0]

    def apply_fn(state: Zero1State, grads):
        params, m, v = state.params, state.m, state.v
        t = state.step + 1
        param_specs = jax.tree_util.tree_map(lambda _: P(), params)
        grad_specs = jax.tree_util.tree_map(lambda _: P(), grads)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(param_specs, grad_specs, P(ax), P(ax)),
            out_specs=(P(), P(ax), P(ax), P()),
            check_vma=False)
        def run(params, grads, m_l, v_l):
            flat = jnp.pad(_flatten_f32(grads), (0, pad_n - n))
            gnorm = jnp.sqrt(jnp.sum(flat * flat))
            idx = jax.lax.axis_index(ax)
            g_l = jax.lax.dynamic_slice_in_dim(flat, idx * shard, shard)
            g_l = g_l * _clip_scale(gnorm, hyper.clip_norm)
            flat_p = jnp.pad(_flatten_f32(params), (0, pad_n - n))
            p_l = jax.lax.dynamic_slice_in_dim(flat_p, idx * shard, shard)
            delta_l, m_l, v_l = _adam_shard_update(
                g_l, p_l, m_l, v_l, t, hyper)
            delta = jax.lax.all_gather(delta_l, ax, tiled=True)
            new_params = jax.tree_util.tree_map(
                lambda p, d: p + d.astype(p.dtype),
                params, _unflatten_like(delta[:pad_n], params))
            return new_params, m_l, v_l, gnorm

        new_params, new_m, new_v, gnorm = run(params, grads, m, v)
        new_state = state.replace(step=t, params=new_params,
                                  m=new_m, v=new_v)
        return new_state, {"grad_norm": gnorm}

    state_shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
    return jax.jit(apply_fn, donate_argnums=(0,) if donate else (),
                   out_shardings=(state_shardings, None))


def make_grad_step(loss_fn: Callable, mesh: Mesh,
                   rules: Optional[Dict[str, Any]] = None,
                   batch_axes: Tuple = ("batch", "seq")):
    """Jitted (loss, grads) for the two-level schedule: in-program
    GSPMD handles intra-slice sharding, the caller moves the returned
    grads over the cross-slice (host/DCN) hop before applying."""
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    batch_sharding = named_sharding(mesh, batch_axes, rules)

    def grad_fn(params, batch):
        batch = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, batch_sharding) if x.ndim == len(batch_axes) else x,
            batch)
        return jax.value_and_grad(loss_fn)(params, batch)

    return jax.jit(grad_fn)


def opt_state_bytes_per_device(state) -> int:
    """Actual per-device optimizer-state residency (device 0's
    addressable shards): the number the ZeRO-1 memory claim is gated
    on — sharded m/v report ~1/W of the replicated footprint."""
    import numpy as np
    leaves = []
    if isinstance(state, Zero1State):
        leaves = [state.m, state.v]
    else:
        leaves = jax.tree_util.tree_leaves(getattr(state, "opt_state",
                                                   state))
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "addressable_shards"):
            shard = leaf.addressable_shards[0]
            total += int(np.prod(shard.data.shape)) * leaf.dtype.itemsize
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10_000,
                      b1: float = 0.9, b2: float = 0.95,
                      clip_norm: float = 1.0) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )
