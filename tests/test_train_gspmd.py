"""GSPMD training plane: ZeRO-1 sharded weight updates on the virtual
8-device mesh (parity vs the replicated optimizer and vs a single-
process baseline), the two-level cross-slice schedule with its DCN byte
ledger, and the MPMD pipeline (stages as actors, activations as device
objects — zero host round-trip, measured bubble fraction)."""

import functools

import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel import (MeshConfig, create_train_state,
                              create_zero1_state, dp_rules,
                              make_grad_step, make_train_step,
                              make_zero1_apply_step, make_zero1_train_step,
                              opt_state_bytes_per_device)
from ray_tpu.parallel.spmd import Zero1Hyper

UPDATE_AXES = ("data", "fsdp")


def _mlp():
    import flax.linen as nn
    import jax.numpy as jnp

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(32)(x)
            x = jnp.tanh(x)
            return nn.Dense(1)(x)

    return MLP()


def _batch(step: int, rank: int = 0, world: int = 1):
    rng = np.random.RandomState(100 + step)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    if world > 1:
        per = 16 // world
        sl = slice(rank * per, (rank + 1) * per)
        return {"x": x[sl], "y": y[sl]}
    return {"x": x, "y": y}


def _mlp_loss(model):
    import jax.numpy as jnp

    def loss_fn(params, batch):
        pred = model.apply({"params": params}, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    return loss_fn


def _two_slice_mesh():
    return MeshConfig(data=2, fsdp=4, dcn_axes=("data",)).build(
        num_slices=2)


# ---------------------------------------------------------------------------
# in-process parity gates (no cluster)
# ---------------------------------------------------------------------------

def test_zero1_parity_and_sharded_optimizer_memory():
    """The fused ZeRO-1 step (reduce-scatter -> shard-local AdamW ->
    allgather delta) tracks the replicated optax AdamW loss trajectory,
    with ~1/8 the per-device optimizer residency."""
    import jax
    import optax

    mesh = _two_slice_mesh()
    rules = dp_rules(UPDATE_AXES)
    model = _mlp()
    loss_fn = _mlp_loss(model)
    rng = jax.random.PRNGKey(0)
    hyper = Zero1Hyper(learning_rate=1e-2, clip_norm=1.0)

    z1 = create_zero1_state(rng, model, _batch(0)["x"], mesh, hyper,
                            rules=rules, axes=UPDATE_AXES)
    step_z1 = make_zero1_train_step(loss_fn, mesh, z1, axes=UPDATE_AXES)
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(1e-2))
    ref = create_train_state(rng, model, _batch(0)["x"], mesh, tx, rules)
    step_ref = make_train_step(loss_fn, mesh, rules,
                               batch_axes=("batch", None), state=ref)

    with mesh:
        for i in range(4):
            batch = {k: jax.numpy.asarray(v)
                     for k, v in _batch(i).items()}
            z1, mz = step_z1(z1, batch)
            ref, mr = step_ref(ref, batch)
            assert abs(float(mz["loss"]) - float(mr["loss"])) < 1e-4, i

    z1_bytes = opt_state_bytes_per_device(z1)
    ref_bytes = opt_state_bytes_per_device(ref)
    # m+v sharded over the 8 update devices vs 2 full replicated copies
    assert z1_bytes * 6 < ref_bytes, (z1_bytes, ref_bytes)


def test_zero1_hlo_has_reduce_scatter_and_allgather():
    """The sharded-update schedule really lowers to the cross-replica
    collectives the paper names (arxiv 2004.13336): reduce-scatter for
    the gradient shards, all-gather for the parameter delta."""
    import jax

    mesh = _two_slice_mesh()
    model = _mlp()
    loss_fn = _mlp_loss(model)
    z1 = create_zero1_state(
        jax.random.PRNGKey(0), model, _batch(0)["x"], mesh,
        Zero1Hyper(), rules=dp_rules(UPDATE_AXES), axes=UPDATE_AXES)
    step = make_zero1_train_step(loss_fn, mesh, z1, axes=UPDATE_AXES)
    batch = {k: jax.numpy.asarray(v) for k, v in _batch(0).items()}
    text = step.lower(z1, batch).as_text()
    assert "reduce_scatter" in text or "reduce-scatter" in text
    assert "all-gather" in text or "all_gather" in text


# -- the model's activation layout under the train step's mesh (PR 31) ------

def _grad_keeping_sgd(lr):
    """SGD whose optimiser state is the gradient it was last given: the
    step's own gradients come out of `make_train_step`'s program."""
    import jax
    import optax

    def init(params):
        return jax.tree_util.tree_map(jax.numpy.zeros_like, params)

    def update(grads, state, params=None):
        return jax.tree_util.tree_map(lambda g: -lr * g, grads), grads
    return optax.GradientTransformation(init, update)


_LLAMA_TOKENS = (4, 32)
# `constrain` calls of the no-cache forward: embedding, final norm and
# logits; a layer's two norms, q, k, v, attention output, o_proj output,
# MLP hidden, down_proj output, two residual adds
_LLAMA_SITES = (3, 11)


def _tiny_llama(layers):
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    return LlamaModel(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=layers, num_heads=4, num_kv_heads=2, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=True,
        attention_impl="reference"))


def _lower_llama_step(layers, meshed):
    """(mesh, initial state, lowered step) of a tiny float32 Llama on one
    device or on fsdp=2 x tensor=2."""
    import jax
    from ray_tpu.models.llama import cross_entropy_loss

    model = _tiny_llama(layers)
    mesh_config = MeshConfig(data=1, fsdp=2, tensor=2) if meshed \
        else MeshConfig(data=1)
    mesh = mesh_config.build(jax.devices()[:4 if meshed else 1])
    rules = mesh_config.rules_dict()
    state = create_train_state(
        jax.random.PRNGKey(0), model,
        jax.numpy.zeros(_LLAMA_TOKENS, jax.numpy.int32), mesh,
        _grad_keeping_sgd(0.1), rules)

    def loss_fn(params, data):
        logits = model.apply({"params": params}, data["tokens"])
        return cross_entropy_loss(logits[:, :-1], data["tokens"][:, 1:])

    step = make_train_step(loss_fn, mesh, rules, state=state, donate=False)
    with mesh:
        return mesh, state, step.lower(state, _llama_batch())


@functools.lru_cache(maxsize=None)
def _llama_step(layers, meshed):
    mesh, state, lowered = _lower_llama_step(layers, meshed)
    return mesh, state, lowered, lowered.compile()


def _llama_batch():
    import jax
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), _LLAMA_TOKENS, 0, 256)}


@pytest.mark.parametrize("layers", [2, 3])
def test_llama_step_on_mesh_matches_one_device(layers):
    """Forward, backward and update under fsdp=2 x tensor=2 against the
    one-device program: the first loss, every gradient leaf (1e-4 of the
    leaf's largest entry) and the losses of three optimiser steps."""
    import jax

    runs = {}
    for meshed in (False, True):
        mesh, state, _, compiled = _llama_step(layers, meshed)
        losses = []
        with mesh:
            for i in range(3):
                state, metrics = compiled(state, _llama_batch())
                losses.append(float(metrics["loss"]))
                if i == 0:
                    grads = jax.tree_util.tree_map(np.asarray,
                                                   state.opt_state)
        runs[meshed] = losses, grads
    (want_losses, want), (got_losses, got) = runs[False], runs[True]
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-4)
    assert got_losses[-1] < got_losses[0]
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert len(leaves) == 3 + 9 * layers
    for (path, w), g in zip(leaves, jax.tree_util.tree_leaves(got)):
        assert np.abs(w).max() > 0, path
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), path


def test_llama_step_states_its_layout_to_the_partitioner():
    """The annotations reach the program: a sharding constraint per
    annotated site in the lowered step on a mesh, only the batch's on one
    device, and the compiled step's all-to-alls do not grow with depth
    (left to propagation the residual stream is resharded eight times a
    layer: 21 and 29 of them at these widths)."""
    from ray_tpu.parallel.mesh import collective_counts

    def constraints(layers, meshed):
        return _llama_step(layers, meshed)[2].as_text().count(
            "sharding_constraint")

    fixed, per_layer = _LLAMA_SITES
    for layers in (2, 3):
        assert constraints(layers, False) == 1
        assert constraints(layers, True) >= 1 + fixed + per_layer * layers
    shallow, deep = (collective_counts(_llama_step(n, True)[3].as_text())
                     for n in (2, 3))
    assert shallow["all-reduce"] < deep["all-reduce"]  # the reader reads
    assert deep.get("all-to-all", 0) == shallow.get("all-to-all", 0) <= 2


def test_llama_forward_constrains_each_site_once():
    """The forward alone, traced under a kernel mesh: exactly one
    constraint a site; none without a mesh, on a mesh of one device, or
    on the cache branches; k and v are left alone where the kv heads do
    not divide the tensor axis."""
    import dataclasses

    import jax
    from ray_tpu.models.llama import LlamaModel, init_kv_caches
    from ray_tpu.parallel.mesh import kernel_mesh

    model = _tiny_llama(2)
    tokens = jax.numpy.zeros(_LLAMA_TOKENS, jax.numpy.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]

    def traced(module, mesh, **kw):
        with kernel_mesh(mesh):
            return str(jax.make_jaxpr(lambda p: module.apply(
                {"params": p}, tokens, **kw))(params)).count(
                    "sharding_constraint")

    four = MeshConfig(data=1, fsdp=2, tensor=2).build(jax.devices()[:4])
    one = MeshConfig(data=1).build(jax.devices()[:1])
    fixed, per_layer = _LLAMA_SITES
    assert traced(model, four) == fixed + per_layer * 2
    assert traced(model, None) == traced(model, one) == 0
    caches = init_kv_caches(model.config, _LLAMA_TOKENS[0], 64)
    assert traced(model, four, kv_caches=caches, cache_index=0) == 0
    one_kv_head = LlamaModel(dataclasses.replace(model.config,
                                                 num_kv_heads=1))
    params = jax.eval_shape(one_kv_head.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    assert traced(one_kv_head, four) == fixed + (per_layer - 2) * 2


def test_collective_counts_reads_pairs_and_clones_once():
    from ray_tpu.parallel.mesh import collective_counts
    text = """
  %ag = bf16[8,4]{1,0} all-gather(%p), channel_id=7, dimensions={0}
  %ag.clone = bf16[8,4]{1,0} all-gather(%q), channel_id=7, dimensions={0}
  %ars = (f32[], f32[]) all-reduce-start(%a, %b), channel_id=9, to_apply=%add
  %ard = (f32[], f32[]) all-reduce-done(%ars)
  %ar2 = f32[4]{0} all-reduce(%c), channel_id=10, to_apply=%add
  %a2a = bf16[4,2,8]{2,1,0} all-to-all(%d), channel_id=11, dimensions={1}
  %cps = (f32[2], f32[2]) collective-permute-start(%e), channel_id=12
  %cpd = f32[2] collective-permute-done(%cps)
  %f = f32[4] fusion(%g), metadata={op_name="jit(f)/all-to-all(x)"}
"""
    assert collective_counts(text) == {
        "all-gather": 1, "all-reduce": 2, "all-to-all": 1,
        "collective-permute": 1}


def _serve_programs(tensor):
    """Lowered text of a tiny paged engine's decode_step and one
    chunk_prefill bucket as the tick calls it (`last`: the head on one
    row under a `cond`, inside which a vocabulary-sharded head's
    collective sits), from shapes."""
    import jax
    from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine
    from ray_tpu.models.llama import LlamaConfig
    model = LlamaConfig(vocab_size=128, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=256, remat=False,
                        use_flash=False, attention_impl="reference")
    mesh = MeshConfig(data=1, tensor=tensor).build(
        jax.devices()[:tensor]) if tensor > 1 else None
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=128, page_size=8, num_pages=64,
        prefill_buckets=(16,)), mesh=mesh)

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
    ints = jax.ShapeDtypeStruct((1, 16), jax.numpy.int32)
    with engine._mesh_scope():
        prefill = engine._chunk_prefill.lower(
            jax.tree_util.tree_map(like, engine.params), ints, ints,
            jax.tree_util.tree_map(like, engine._dense_zero_caches()),
            jax.ShapeDtypeStruct((), jax.numpy.int32),
            jax.ShapeDtypeStruct((), jax.numpy.int32))
    return engine.lower_decode().as_text(), prefill.as_text()


@pytest.mark.parametrize("program", ["train-1-device", "serve-tensor-1",
                                     "serve-tensor-2"])
def test_programs_the_layout_must_not_touch(program, monkeypatch):
    """Where no layout is to be stated the helper changes nothing: the
    train step on a mesh of one device, and the serving programs (cache
    branches) with and without a tensor axis, lower to the same text with
    `constrain` in place and with it bypassed."""
    from ray_tpu.models import llama

    def lower():
        if program == "train-1-device":
            return (_lower_llama_step(2, False)[2].as_text(),)
        return _serve_programs(int(program[-1]))

    with_helper = lower()
    monkeypatch.setattr(llama, "constrain", lambda x, names: x)
    assert lower() == with_helper


def test_zero1_apply_step_matches_fused():
    """The split schedule (in-program grads -> out-of-program combine ->
    apply) follows the fused step exactly when fed the same combined
    gradients — the contract the two-level cross-slice path rests on."""
    import jax

    mesh = _two_slice_mesh()
    rules = dp_rules(UPDATE_AXES)
    model = _mlp()
    loss_fn = _mlp_loss(model)
    hyper = Zero1Hyper(learning_rate=1e-2)
    rng = jax.random.PRNGKey(1)

    fused = create_zero1_state(rng, model, _batch(0)["x"], mesh, hyper,
                               rules=rules, axes=UPDATE_AXES)
    split = create_zero1_state(rng, model, _batch(0)["x"], mesh, hyper,
                               rules=rules, axes=UPDATE_AXES)
    fused_step = make_zero1_train_step(loss_fn, mesh, fused,
                                       axes=UPDATE_AXES)
    grad_step = make_grad_step(loss_fn, mesh, rules,
                               batch_axes=("batch", None))
    apply_step = make_zero1_apply_step(mesh, split, axes=UPDATE_AXES)

    with mesh:
        for i in range(3):
            batch = {k: jax.numpy.asarray(v)
                     for k, v in _batch(i).items()}
            fused, mf = fused_step(fused, batch)
            loss, grads = grad_step(split.params, batch)
            split, _ = apply_step(split, grads)
            assert abs(float(mf["loss"]) - float(loss)) < 1e-5
    flat_f = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(fused.params)])
    flat_s = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(split.params)])
    np.testing.assert_allclose(flat_f, flat_s, atol=1e-5)


def test_dp_rules_drops_conflicting_shardings():
    rules = dp_rules(("data", "fsdp"))
    assert rules["batch"] == ("data", "fsdp")
    assert rules["embed"] is None          # was "fsdp" — an update axis
    assert rules["heads"] == "tensor"      # untouched
    single = dp_rules(("data",))
    assert single["batch"] == "data"
    assert single["embed"] is None or single["embed"] == "fsdp"


def test_zero1_rejects_params_sharded_over_update_axes():
    import jax

    mesh = _two_slice_mesh()
    model = _mlp()
    # DEFAULT rules shard embed over fsdp — invalid for ZeRO-1 over
    # ("data", "fsdp") IF a param uses them; the MLP has no logical
    # names so build an explicit conflict via shardings check instead.
    from ray_tpu.parallel.spmd import _check_params_replicated
    from jax.sharding import NamedSharding, PartitionSpec as P
    bad = NamedSharding(mesh, P("fsdp"))
    with pytest.raises(ValueError, match="replicated"):
        _check_params_replicated({"w": bad}, ("data", "fsdp"))


def test_scaling_config_mesh_declaration():
    from ray_tpu.train import ScalingConfig

    sc = ScalingConfig(num_workers=1,
                       mesh_axes={"data": 2, "fsdp": 4},
                       dcn_axes=("data",), num_slices=2)
    mc = sc.mesh_config()
    assert mc.data == 2 and mc.fsdp == 4 and mc.dcn_axes == ("data",)
    assert ScalingConfig(num_workers=1).mesh_config() is None
    with pytest.raises(ValueError, match="unknown mesh axes"):
        ScalingConfig(mesh_axes={"bogus": 2}).mesh_config()
    with pytest.raises(ValueError, match="dcn_axes requires"):
        ScalingConfig(dcn_axes=("data",))


# ---------------------------------------------------------------------------
# trainer e2e over the actor plane
# ---------------------------------------------------------------------------

@pytest.fixture
def train_cluster():
    ray_tpu.init(num_cpus=8, object_store_memory=200 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _spec(schedule="auto", steps=3):
    from ray_tpu.train import GSPMDTrainSpec
    return GSPMDTrainSpec(
        model_fn=_mlp, loss_fn=lambda model, params, batch:
        _mlp_loss(model)(params, batch),
        batch_fn=_batch, steps=steps,
        hyper=Zero1Hyper(learning_rate=1e-2, clip_norm=1.0),
        tokens_per_step=16, flops_per_step=1e6, schedule=schedule)


def _fit(spec, num_workers, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    trainer = JaxTrainer(
        _loop_entry, train_loop_config={"spec": spec},
        scaling_config=ScalingConfig(
            num_workers=num_workers,
            mesh_axes={"data": 2, "fsdp": 4},
            dcn_axes=("data",), num_slices=2, virtual_devices=8),
        run_config=RunConfig(storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None, result.error
    return result.metrics


def _loop_entry(config):
    from ray_tpu.train import gspmd_train_loop
    return gspmd_train_loop(config)


@pytest.mark.timeout_s(180)
def test_gspmd_trainer_loss_parity_and_telemetry(train_cluster, tmp_path):
    """The acceptance gate: whole-mesh GSPMD (ZeRO-1, two emulated
    slices over DCN) vs the single-process baseline — loss parity
    < 1e-2 with step/MFU/goodput telemetry in the train report."""
    from ray_tpu.train import run_single_process_baseline

    spec = _spec("auto", steps=3)
    base = run_single_process_baseline(spec)
    metrics = _fit(spec, num_workers=1, tmp_path=tmp_path)
    assert metrics["schedule"] == "gspmd" and metrics["zero1"] is True
    deltas = [abs(a - b) for a, b in zip(metrics["losses"],
                                         base["losses"])]
    assert max(deltas) < 1e-2 * max(1.0, abs(base["losses"][-1])), deltas
    # PR-7 telemetry wired from day one
    assert metrics["mean_step_s"] > 0
    goodput = metrics["goodput"]
    assert goodput["compile_s"] > 0 and goodput["device_s"] > 0
    assert "mfu" in metrics and metrics["mfu"] > 0
    assert metrics["step_time_s"] > 0  # controller-foldable keys


@pytest.mark.slow
@pytest.mark.timeout_s(240)
def test_two_level_cross_slice_ledger_and_parity(train_cluster, tmp_path):
    """Two workers = two slices: in-program slice backward, host/DCN
    gradient hop through the selected collective backend, ZeRO-1 apply.
    Parity vs the single-process baseline; the rank-0 report carries
    the per-link byte ledger with every inter-worker byte on DCN."""
    from ray_tpu.train import run_single_process_baseline

    spec = _spec("auto", steps=3)
    base = run_single_process_baseline(spec)
    metrics = _fit(spec, num_workers=2, tmp_path=tmp_path)
    assert metrics["schedule"] == "two_level"
    deltas = [abs(a - b) for a, b in zip(metrics["losses"],
                                         base["losses"])]
    assert max(deltas) < 1e-2 * max(1.0, abs(base["losses"][-1])), deltas
    ledger = metrics["collective_bytes"]
    assert ledger["dcn"] > 0          # the gradient hop really crossed
    assert ledger["ici"] == 0         # one rank per slice: all DCN
    assert metrics["goodput"]["device_s"] > 0


@pytest.mark.slow
@pytest.mark.timeout_s(240)
def test_two_level_replicated_ab_arm_honors_zero1_switch(train_cluster,
                                                         tmp_path):
    """spec.zero1=False must actually run the replicated-update A/B arm
    on the two_level schedule (not silently keep ZeRO-1), at loss parity
    with the single-process baseline."""
    import dataclasses

    from ray_tpu.train import run_single_process_baseline

    spec = dataclasses.replace(_spec("auto", steps=3), zero1=False)
    base = run_single_process_baseline(spec)
    metrics = _fit(spec, num_workers=2, tmp_path=tmp_path)
    assert metrics["schedule"] == "two_level"
    assert metrics["zero1"] is False
    deltas = [abs(a - b) for a, b in zip(metrics["losses"],
                                         base["losses"])]
    assert max(deltas) < 1e-2 * max(1.0, abs(base["losses"][-1])), deltas


# ---------------------------------------------------------------------------
# MPMD pipeline: stages as actors, activations as device objects
# ---------------------------------------------------------------------------

WIDTH = 16


def _stage_init(stage_index, num_stages):
    import jax.numpy as jnp

    rng = np.random.RandomState(42 + stage_index)
    if stage_index == 0:
        params = {"w": jnp.asarray(rng.randn(8, WIDTH) / np.sqrt(8),
                                   jnp.float32)}

        def apply_fn(p, x):
            return jnp.tanh(x @ p["w"])
    else:
        params = {"w": jnp.asarray(rng.randn(WIDTH, 1) / np.sqrt(WIDTH),
                                   jnp.float32)}

        def apply_fn(p, x):
            return x @ p["w"]
    return apply_fn, params


def _pipe_loss(y, targets):
    import jax.numpy as jnp
    return jnp.mean((y - jnp.asarray(targets)) ** 2)


def _pipe_reference(steps, microbatches):
    """Fused single-process twin: same stage params, same microbatch
    grad averaging, same AdamW."""
    import jax
    import jax.numpy as jnp
    import optax

    stages = [_stage_init(s, 2) for s in range(2)]
    params = [p for _, p in stages]

    def full_loss(params, x, y):
        h = jnp.asarray(x)
        for (fn, _), p in zip(stages, params):
            h = fn(p, h)
        return _pipe_loss(h, y)

    tx = optax.adamw(1e-2)
    opt_state = tx.init(params)
    losses = []
    for i in range(steps):
        batch = _pipe_batch(i)
        xs = np.split(batch[0], microbatches)
        ys = np.split(batch[1], microbatches)
        grads, step_losses = None, []
        for mb in range(microbatches):
            loss, g = jax.value_and_grad(full_loss)(params, xs[mb],
                                                    ys[mb])
            step_losses.append(float(loss))
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(np.mean(step_losses)))
    return losses


def _pipe_batch(step):
    rng = np.random.RandomState(step)
    return (rng.randn(32, 8).astype(np.float32),
            rng.randn(32, 1).astype(np.float32))


@pytest.mark.timeout_s(180)
def test_pipeline_zero_host_roundtrip_and_bubble(train_cluster):
    """MPMD GPipe over 2 stage actors x 4 microbatches: activations
    cross stages as device objects ONLY (zero host round-trips — every
    inter-stage hop resolved to a descriptor + runtime pull), the loss
    matches the fused single-process reference, and the measured bubble
    fraction is reported and bounded."""
    from ray_tpu.train import MPMDPipeline

    steps, M, S = 3, 4, 2
    ref_losses = _pipe_reference(steps, M)
    pipe = MPMDPipeline(_stage_init, num_stages=S, loss_fn=_pipe_loss,
                        microbatches=M,
                        hyper_kwargs={"learning_rate": 1e-2})
    try:
        losses = []
        for i in range(steps):
            x, y = _pipe_batch(i)
            losses.append(pipe.step(x, y)["loss"])
        report = pipe.bubble_report()
    finally:
        pipe.teardown()

    deltas = [abs(a - b) for a, b in zip(losses, ref_losses)]
    assert max(deltas) < 1e-4, (losses, ref_losses)
    # zero host round-trip: every inter-stage activation AND backward
    # grad moved as a device object (fwd: S-1 hops x M x steps;
    # bwd: same) — none spilled to the host object store
    assert report["host_roundtrips"] == 0
    assert report["device_pulls"] == 2 * (S - 1) * M * steps
    # bubble: measured, reported, and bounded. On one contended socket
    # stages can serialize entirely, so the honest bound is the serial
    # floor (1 - 1/S) plus scheduling slack — NOT the parallel-hardware
    # theoretical (S-1)/(S-1+M), which is also reported.
    bubble = report["bubble_fraction"]
    assert bubble is not None
    assert 0.0 <= bubble <= report["bubble_serial_floor"] + 0.25, report
    assert abs(report["bubble_theoretical"] - (S - 1) / (S - 1 + M)) \
        < 1e-9


@pytest.mark.timeout_s(120)
def test_pipeline_activations_are_descriptors(train_cluster):
    """The control-plane value behind an inter-stage ref is a
    DeviceObjectDescriptor (bytes-sized), never the activation array:
    the payload moved runtime-to-runtime."""
    from ray_tpu.experimental.device_objects import (
        DeviceObjectDescriptor, device_put_ref)

    @ray_tpu.remote(num_cpus=0.25)
    class Producer:
        def make(self):
            import jax.numpy as jnp
            self.ref = device_put_ref(jnp.ones((256, 16), jnp.float32))
            return [self.ref]

    producer = Producer.remote()
    wrapped = ray_tpu.get(producer.make.remote(), timeout=60)
    control = ray_tpu.get(wrapped[0], timeout=60)
    assert isinstance(control, DeviceObjectDescriptor)
    assert control.nbytes == 256 * 16 * 4
