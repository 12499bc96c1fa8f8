"""The chip's compiler, without the chip: the kernels of the two main paths
AOT-compiled for a described `v5e:2x2` at Llama-3-8B widths, plus the
bring-up rules a CPU run can hold the repo to (a chip lease becomes the TPU
platform, `chip_smoke.py` refuses to pass off-chip).

Interpret mode proves a kernel's arithmetic; only this compile proves the
TPU will take it (tiling, VMEM, partitioning). The kernels choose their
branch from `jax.default_backend()`, which here says "cpu", so each test
steers that question itself — compiled the obvious way, the same programs
hold no kernel at all and prove nothing.
"""

import json
import math
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu out of /tmp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Llama-3-8B attention widths
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128


@pytest.fixture(scope="module")
def v5e():
    """Four described (not attached) v5e chips."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture
def as_tpu(monkeypatch):
    """Answer the kernels' backend question as the chip would, and keep
    the persistent compile cache out of it (an AOT entry written here
    cannot be read back without a chip; the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _kernels(fn, *specs):
    from ray_tpu.ops.attention import pallas_kernels
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return pallas_kernels(text)


def _qkv(batch, seq, sharding):
    def spec(heads):
        return jax.ShapeDtypeStruct((batch, heads, seq, HEAD_DIM),
                                    jnp.bfloat16, sharding=sharding)
    return spec(HEADS), spec(KV_HEADS), spec(KV_HEADS)


def test_flash_forward_compiles_for_v5e(v5e, as_tpu):
    from ray_tpu.ops.attention import flash_attention
    kernels = _kernels(flash_attention,
                       *_qkv(2, 2048, SingleDeviceSharding(v5e[0])))
    assert kernels == {"flash_fwd": 1}


def test_flash_forward_backward_compiles_for_v5e(v5e, as_tpu):
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    kernels = _kernels(jax.grad(loss, argnums=(0, 1, 2)),
                       *_qkv(2, 2048, SingleDeviceSharding(v5e[0])))
    assert kernels == {"flash_fwd": 1, "flash_bwd_kv": 1, "flash_bwd_q": 1}


def test_flash_on_a_mesh_is_shard_mapped(v5e, as_tpu):
    """GSPMD refuses to partition a Mosaic call ("wrap the call in a
    shard_map"): under the train step's kernel mesh the model maps the
    kernel over batch (fsdp) and heads (tensor) itself."""
    from ray_tpu.models.llama import _flash_on_mesh
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.parallel.mesh import kernel_mesh
    mesh = MeshConfig(data=1, fsdp=2, tensor=2).build(v5e)
    sharding = NamedSharding(mesh, P(("data", "fsdp"), "tensor"))

    def attend(q, k, v):
        with kernel_mesh(mesh):
            return _flash_on_mesh(q, k, v)

    assert _kernels(attend, *_qkv(2, 2048, sharding)) == {"flash_fwd": 1}


def _paged_decode_layer(mesh=None):
    """One Attention layer's paged decode step (q_len 1) through the
    model's own branch: page scatter + ops.paged_attention's kernel."""
    import dataclasses

    from ray_tpu.models.llama import Attention, LlamaConfig
    from ray_tpu.parallel.mesh import kernel_mesh
    cfg = dataclasses.replace(LlamaConfig.llama3_8b(), dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    layer = Attention(cfg)

    def decode(params, x, kp, vp, tables, lengths):
        cache = {"k": kp, "v": vp, "block_tables": tables,
                 "lengths": lengths}
        with kernel_mesh(mesh):
            out, new = layer.apply({"params": params}, x, lengths[:, None],
                                   cache, None)
        return out, new["k"], new["v"]

    return cfg, layer, decode


def _paged_specs(cfg, layer, batch, pages, page_size, place):
    from ray_tpu.parallel.mesh import unbox
    x = jnp.zeros((batch, 1, cfg.hidden_size), cfg.dtype)
    params = jax.eval_shape(
        lambda: unbox(layer.init(jax.random.PRNGKey(0), x,
                                 jnp.zeros((batch, 1), jnp.int32))["params"]))

    def spec(shape, dtype, names=()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place(names))
    pool = (cfg.num_kv_heads, pages, page_size, cfg.head_dim_)
    return (jax.tree_util.tree_map(
                lambda a: spec(a.shape, a.dtype), params),
            spec(x.shape, x.dtype),
            spec(pool, cfg.dtype, ("tensor",)),
            spec(pool, cfg.dtype, ("tensor",)),
            spec((batch, 2048 // page_size), jnp.int32),
            spec((batch,), jnp.int32))


def test_paged_decode_compiles_for_v5e(v5e, as_tpu):
    cfg, layer, decode = _paged_decode_layer()
    one = SingleDeviceSharding(v5e[0])
    specs = _paged_specs(cfg, layer, 8, 512, 16, lambda names: one)
    assert _kernels(decode, *specs) == {"paged_attention": 1}


def test_paged_decode_tensor_parallel_compiles_for_v5e(v5e, as_tpu):
    """tensor=4: the paged kernel runs per shard under shard_map (local
    heads 8/2), which a pallas_call only accepts with check_vma off."""
    from ray_tpu.parallel import MeshConfig
    mesh = MeshConfig(data=1, tensor=4).build(v5e)
    cfg, layer, decode = _paged_decode_layer(mesh)
    specs = _paged_specs(cfg, layer, 8, 512, 16,
                         lambda names: NamedSharding(mesh, P(*names)))
    assert _kernels(decode, *specs) == {"paged_attention": 1}


@pytest.mark.parametrize("tensor", [1, 4])
def test_paged_decode_relays_no_page_pool_out(v5e, as_tpu, tensor):
    """The token's K/V write leaves the donated pools in the paged
    kernel's layout: the compiled layer copies no whole pool (indexed by
    (page, offset) alone, XLA:TPU copied K and V out to another layout
    and back, every layer, every tick), on one chip and per shard. At
    the serve cell's 2048 pages: a shard of a few MB the compiler may
    stage through fast memory, which is a copy but no relayout."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.parallel import MeshConfig
    mesh = MeshConfig(data=1, tensor=tensor).build(v5e) \
        if tensor > 1 else None
    cfg, layer, decode = _paged_decode_layer(mesh)
    one = SingleDeviceSharding(v5e[0])
    specs = _paged_specs(
        cfg, layer, 8, 2048, 16,
        lambda names: NamedSharding(mesh, P(*names)) if mesh else one)
    compiled = jax.jit(decode, donate_argnums=(2, 3)).lower(
        *specs).compile()
    pool = (cfg.num_kv_heads // tensor, 2048, 16, cfg.head_dim_)
    text = compiled.as_text()
    assert "bf16[" + ",".join(map(str, pool)) + "]" in text
    assert pool_copies(text, pool) == 0
    # both pools still alias in place (bf16: two bytes an element)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        2 * 2 * math.prod(pool)


@pytest.mark.timeout_s(300)
def test_train_step_on_the_2x2_mesh_keeps_its_stated_layout(v5e, as_tpu):
    """The `train-yi-2x2` cell's step (its config file's widths, its
    builder, 8 x 2048 on fsdp=2 x tensor=2) at two layers: the model's
    `constrain` calls hold the partitioner to batch over fsdp and heads /
    mlp over tensor. Left to propagation it resharded the residual stream
    with eight all-to-alls a layer and all-reduced each MLP projection's
    partial sums of the whole batch over the fsdp pairs, in 3.93 GB of
    temporaries (my AOT compile of the parent, PR 31)."""
    import re

    from benchmarks.harness.builders import llama_train
    from ray_tpu.ops.attention import pallas_kernels
    from ray_tpu.parallel import MeshConfig, make_train_step
    from ray_tpu.parallel.mesh import collective_counts, named_sharding
    from ray_tpu.parallel.spmd import train_state_init
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "yi-1.5-9b-train-2x2.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2)
    built = llama_train(config)
    mesh_config = MeshConfig(**config["mesh_axes"])
    mesh = mesh_config.build(v5e)
    rules = mesh_config.rules_dict()
    batch, seq = 8, 2048
    tokens = jnp.zeros((batch, seq), jnp.int32)
    key = jax.random.PRNGKey(0)
    init = train_state_init(built["module"], tokens, mesh, built["tx"],
                            rules)
    with mesh:
        # create_train_state's program, compiled and never run
        placed = jax.jit(init).lower(key).compile().output_shardings
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(init, key), placed)
        data = {"tokens": jax.ShapeDtypeStruct(
            tokens.shape, tokens.dtype,
            sharding=named_sharding(mesh, ("batch", "seq"), rules))}
        compiled = make_train_step(built["loss_fn"], mesh, rules,
                                   state=state).lower(state, data).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"flash_fwd": 4, "flash_bwd_kv": 2,
                                    "flash_bwd_q": 2}
    assert collective_counts(text).get("all-to-all", 0) <= 2
    hidden = f"[{batch},{seq},{config['intermediate_size'] // 2}]"
    assert not re.findall(
        re.escape(hidden) + r"\S* all-reduce(?:-start)?\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 3.93e9


def test_obvious_compile_holds_no_kernel(v5e):
    """Why the tests above steer the backend question: unsteered, the
    public entry point compiles for the described chip without complaint
    and without one Mosaic call in it."""
    from ray_tpu.ops.attention import flash_attention
    text = jax.jit(flash_attention).lower(
        *_qkv(1, 512, SingleDeviceSharding(v5e[0]))).compile().as_text()
    assert "tpu_custom_call" not in text


# ---------------------------------------------------------------------------
# bring-up rules that hold on any machine
# ---------------------------------------------------------------------------

def test_chip_lease_names_the_tpu_platform():
    """A lease that holds chips is its own worker environment with
    JAX_PLATFORMS=tpu (open the chip or die); every other lease keeps the
    plain key, whose workers the raylet forces onto the CPU."""
    from ray_tpu._internal.raylet import Raylet
    from ray_tpu._internal.resources import ResourceSet
    from ray_tpu._internal.task_spec import runtime_env_key
    key = Raylet._env_key(None, {}, ResourceSet({"CPU": 1}))
    assert key == runtime_env_key({})
    chip = Raylet._env_key(None, {}, ResourceSet({"TPU": 1}))
    assert dict(chip[0]) == {"JAX_PLATFORMS": "tpu"} and chip[1:] == key[1:]
    # a runtime env that names a platform itself is left alone
    named = {"env_vars": {"JAX_PLATFORMS": "cpu", "A": "1"}}
    assert Raylet._env_key(None, named, ResourceSet({"TPU": 4})) \
        == runtime_env_key(named)


def test_use_tpu_takes_the_detected_chips_or_raises(monkeypatch):
    from ray_tpu.train import ScalingConfig
    monkeypatch.setenv("RTPU_NUM_TPU_CHIPS", "1")
    assert ScalingConfig(use_tpu=True).worker_resources()["TPU"] == 1
    monkeypatch.setenv("RTPU_NUM_TPU_CHIPS", "0")
    with pytest.raises(ValueError, match="no TPU chip"):
        ScalingConfig(use_tpu=True).worker_resources()
    explicit = ScalingConfig(use_tpu=True, resources_per_worker={"TPU": 4})
    assert explicit.worker_resources()["TPU"] == 4


def test_compile_cache_dir_is_fixed_and_yields_to_the_environment():
    from ray_tpu.accelerators.tpu import compile_cache_dir
    env = {}
    assert compile_cache_dir(env) == os.path.join(REPO, ".jax_cache")
    assert env == {"JAX_COMPILATION_CACHE_DIR":
                   os.path.join(REPO, ".jax_cache")}
    env = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}
    assert compile_cache_dir(env) == "/elsewhere"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}


def test_launchers_read_the_virtual_mesh_from_the_environment():
    """bench.py and __graft_entry__.py respawn onto virtual CPU devices by
    reading the environment; asking jax would open the host's chip."""
    from ray_tpu.accelerators.tpu import on_virtual_cpu_mesh
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--a=b --xla_force_host_platform_device_count=8"}
    assert on_virtual_cpu_mesh(8, env) and on_virtual_cpu_mesh(4, env)
    assert not on_virtual_cpu_mesh(16, env)
    assert not on_virtual_cpu_mesh(8, dict(env, JAX_PLATFORMS="tpu,cpu"))
    assert not on_virtual_cpu_mesh(1, {})


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_chip_smoke_refuses_to_pass_off_chip(args):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=110)
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert '"ok": true' not in out.stdout
    with pytest.raises(ValueError):
        json.loads(last)  # a failure prints no result object


# ---------------------------------------------------------------------------
# the hybrid cell's programs (a Mamba-2 mixer beside 5:1 GQA attention)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def falcon_programs(v5e):
    """The `serve-falconh1-chat-closed128` cell's engine programs (its
    config file's widths, rows and pool, its builder) at one layer, with
    the shapes of their arguments on one described chip: the engine's own
    `_kind_programs`, on an engine that never allocated anything."""
    from benchmarks.harness.builders_falcon_h1 import falcon_h1_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        config = dict(json.load(f), num_hidden_layers=1)
    engine_cfg = falcon_h1_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = placed(jax.eval_shape(lambda: unbox(engine.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    rows = engine_cfg.max_batch
    pool = (cfg.num_kv_heads, engine_cfg.num_pages, engine_cfg.page_size,
            cfg.head_dim)
    return {
        "engine": engine, "cfg": cfg, "pool": pool, "spec": spec,
        "params": params, "rows": rows,
        "pages": [spec(cfg.dtype, *pool)],
        "state": placed(jax.eval_shape(lambda: cfg.init_state(rows))),
        "staged": placed(jax.eval_shape(engine._dense_zero_caches))}


def test_hybrid_decode_step_compiles_for_v5e_and_copies_no_pool(
        falcon_programs, as_tpu):
    """5:1 grouping (20 query heads on 4 kv heads) through the paged
    kernel, and the donated pools, the pages' and the scan state's
    [rows, 32, 128, 256] float32, updated in place."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = falcon_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    compiled = p["engine"]._decode.lower(
        p["params"], p["pages"], p["pages"], p["state"],
        spec(jnp.bool_, rows),
        spec(jnp.int32, rows, p["engine"].config.pages_per_seq),
        spec(jnp.int32, rows), spec(jnp.int32, rows),
        spec(jnp.uint32, 2), spec(jnp.float32, rows),
        spec(jnp.int32, rows), spec(jnp.float32, rows)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_attention": 1}
    # the sampled tokens leave as the [rows] vector the next call takes
    # (PR 33: the step ahead is fed on the device)
    assert compiled.output_shardings[0] is not None
    out_tokens = jax.tree_util.tree_leaves(compiled.out_info)[0]
    assert (out_tokens.shape, out_tokens.dtype) == ((rows,), jnp.int32)
    ssm = p["state"][0][1].shape
    assert ssm == (rows, cfg.mamba_n_heads, cfg.mamba_d_head,
                   cfg.mamba_d_state)
    assert "f32[" + ",".join(map(str, ssm)) + "]" in text
    assert pool_copies(text, ssm) == 0
    assert pool_copies(text, p["pool"]) == 0
    # both page pools (bf16) and the scan state (float32) alias in place
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        2 * 2 * math.prod(p["pool"]) + 4 * math.prod(ssm)


def test_first_token_compiles_for_v5e(falcon_programs, as_tpu):
    """The program that keeps a prompt's first token on the device: it
    takes the one row of logits the prompt's last chunk returned and
    writes that row's argmax into the token vector, and holds no sort
    (greedy traffic never pays the sampler's compile)."""
    from ray_tpu.llm.paged import first_token
    p = falcon_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    greedy = first_token.lower(
        spec(jnp.int32, rows), spec(jnp.float32, 1, cfg.vocab_size),
        spec(jnp.int32), spec(jnp.uint32, 2), spec(jnp.float32, 1),
        spec(jnp.int32, 1), spec(jnp.float32, 1), sampled=False).compile()
    assert greedy.memory_analysis().output_size_in_bytes <= 1024
    assert " sort(" not in greedy.as_text()


def test_hybrid_prefill_chunk_compiles_for_v5e(falcon_programs, as_tpu):
    """The largest bucket (two chunks of the scan), as the tick runs it
    (`last`: the head on one row, under a `cond`): the staging pytree
    (dense K/V and one row's state) is donated and aliased, nothing of a
    pool's shape is in it, and its temporaries stay far under what the
    cell's memory plan leaves. (The staged caches themselves, a few MB
    each, the compiler stages through fast memory: copies, no relayout.)
    What it returns is the staging and ONE row of float32 logits, 9.0 MB
    at this one layer (the logits of all 256 positions would be 267 MB
    more), and no array of [256, vocab] is in the program."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    p = falcon_programs
    spec, vocab = p["spec"], p["cfg"].vocab_size
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 256), spec(jnp.int32, 1, 256),
        p["staged"], spec(jnp.int32), spec(jnp.int32),
        spec(jnp.int32)).compile()
    text = compiled.as_text()
    assert pool_copies(text, p["pool"]) == 0
    assert pool_copies(text, p["state"][0][1].shape) == 0
    assert array_shapes(text, (256, vocab)) == 0
    assert array_shapes(text, (1, 256, vocab)) == 0
    staged_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(p["staged"]))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= staged_bytes
    assert memory.temp_size_in_bytes < 0.5e9
    assert memory.output_size_in_bytes < 50e6
    assert memory.output_size_in_bytes < staged_bytes + 2 * 4 * vocab


# (query heads a kv head, kv heads, the dense cache's positions): the three
# configurations whose prefill chunk attends a dense cache
_DENSE_CHUNKS = {"mistral": (4, 8, 2560), "falcon_h1": (5, 4, 1792),
                 "nemotron_h": (16, 2, 1792)}


@pytest.mark.parametrize("bucket", [32, 256])
@pytest.mark.parametrize("name", sorted(_DENSE_CHUNKS))
def test_attend_cache_compiles_for_v5e_at_the_shapes_it_takes(
        v5e, as_tpu, name, bucket):
    """The kernel under `ops.attention.attend_cache` at the smallest and
    the largest bucket of each cell whose chunk attends a dense cache: whole
    groups of query rows up to 1,024 a step (Falcon-H1's five groups of 256
    go one at a time), blocks of 512 cached positions where the capacity is
    whole blocks of them and of 256 where it is not."""
    from ray_tpu.ops import attention
    groups, kv_heads, capacity = _DENSE_CHUNKS[name]
    blocks = attention._cache_blocks(groups, bucket, capacity, HEAD_DIM)
    rows = groups * bucket
    assert blocks == {
        ("mistral", 32): (128, 512), ("mistral", 256): (1024, 512),
        ("falcon_h1", 32): (160, 256), ("falcon_h1", 256): (256, 256),
        ("nemotron_h", 32): (512, 256), ("nemotron_h", 256): (1024, 256),
    }[name, bucket]
    one = SingleDeviceSharding(v5e[0])

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cache = spec(jnp.bfloat16, 1, kv_heads, capacity, HEAD_DIM)
    kernels = _kernels(
        lambda *a: attention._attend_cache_pallas(*a, *blocks),
        spec(jnp.bfloat16, 1, kv_heads, rows, HEAD_DIM), cache, cache,
        spec(jnp.int32, 1, rows), spec(jnp.int32))
    assert kernels == {"attend_cache": 1}


def test_attend_cache_leaves_a_decode_token_and_odd_heads_to_the_loop():
    """One new position a row (the dense `LLMEngine`'s decode), heads that
    are no whole lanes and a capacity that is no whole blocks of 128 are
    not the kernel's: `attend_cache` runs its XLA loop there."""
    from ray_tpu.ops.attention import _cache_blocks
    assert _cache_blocks(4, 1, 2560, 128) is None
    assert _cache_blocks(4, 256, 2560, 64) is None
    assert _cache_blocks(4, 256, 2560 + 16, 128) is None
    assert _cache_blocks(4, 256, 2560, 128) == (1024, 512)


def test_dense_prefill_chunk_builds_no_logits_of_its_whole_cache(
        v5e, as_tpu):
    """The chat and doc-QA cells' chunk (`mistral-7b-v0.3-serve.json` at one
    layer, bucket 256) attends through `ops.attention.attend_cache`, one
    kernel a layer: the logits of 32 heads x 256 queries over the row's
    whole private cache (2,560 positions: 84 MB of float32 a layer) stand
    nowhere in the program, whole, grouped or without their batch, and no
    float32 array has a cache's shape or a cache's repeated to the query
    heads. The donated caches alias; the temporaries are a third of what
    the einsum branch took (104 MB at 16 layers, the configuration file's
    record)."""
    from benchmarks.harness.builders import llama_engine
    from ray_tpu.llm.paged import PagedLLMEngine, array_shapes
    from ray_tpu.ops.attention import pallas_kernels
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mistral-7b-v0.3-serve.json")) as f:
        config = dict(json.load(f), num_hidden_layers=1)
    engine_cfg = llama_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._page_sharding = engine._dense_sharding = None
    engine._dense_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    params = placed(jax.eval_shape(lambda: unbox(engine.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    staged = placed(jax.eval_shape(engine._dense_zero_caches))
    capacity = engine_cfg.max_len + 256
    heads, kv_heads, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    assert staged[0][0].shape == (1, kv_heads, capacity, hd)
    compiled = engine._chunk_prefill.lower(
        params, spec(1, 256), spec(1, 256), staged, spec(),
        spec()).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"attend_cache": 1}
    rows = heads // kv_heads * 256
    for logits in ((heads, 256, capacity), (1, heads, 256, capacity),
                   (kv_heads, rows, capacity), (1, kv_heads, rows, capacity)):
        assert array_shapes(text, logits) == 0
    for shape in ((1, kv_heads, capacity, hd), (kv_heads, capacity, hd),
                  (1, heads, capacity, hd), (heads, capacity, hd)):
        assert "f32[" + ",".join(map(str, shape)) + "]" not in text
    memory = compiled.memory_analysis()
    staged_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(staged))
    assert memory.alias_size_in_bytes >= staged_bytes
    assert memory.temp_size_in_bytes < 40e6


# ---------------------------------------------------------------------------
# the Nemotron-H cell's programs (layers of three kinds, 128 held experts)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nemotron_programs(v5e):
    """The `serve-nemotron3-reason-closed192` cell's engine programs: its
    config file's widths, rows and pool, its builder, its whole period of
    11 layers, with the shapes of their arguments on one described chip,
    on an engine that never allocated anything."""
    from benchmarks.harness.builders_nemotron_h import nemotron_h_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-super-120b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = nemotron_h_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    rows = engine_cfg.max_batch
    return {
        "engine": engine, "cfg": cfg, "config": config, "spec": spec,
        "rows": rows,
        "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]))),
        "pool": (cfg.num_kv_heads, engine_cfg.num_pages,
                 engine_cfg.page_size, cfg.head_dim),
        "state": placed(jax.eval_shape(lambda: cfg.init_state(rows))),
        "counters": placed(jax.eval_shape(cfg.init_counters)),
        "staged": placed(jax.eval_shape(engine._dense_zero_caches))}


# what the compiler admits of a v5e's 16 GiB (PERF.md section 4)
V5E_BYTES_LIMIT = 16.9e9


def test_nemotron_decode_step_compiles_for_v5e_within_memory(
        nemotron_programs, as_tpu):
    """96 rows: 16:1 grouping through ONE paged kernel (the one
    layer that attends), every held expert on every token (plain einsums:
    models/moe.py held_expert_sum), the page pool, the five
    scan-state pools and the expert counters donated and updated in
    place, and arguments + temporaries + every row's prefill staging
    under what the chip holds: the numbers of the file's
    `memory_analysis`."""
    from benchmarks.harness import costs_nemotron_h
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = nemotron_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    pages = [spec(cfg.dtype, *p["pool"])]
    compiled = p["engine"]._decode.lower(
        p["params"], pages, pages, p["state"], spec(jnp.bool_, rows),
        spec(jnp.int32, rows, p["engine"].config.pages_per_seq),
        spec(jnp.int32, rows), spec(jnp.int32, rows), spec(jnp.uint32, 2),
        spec(jnp.float32, rows), spec(jnp.int32, rows),
        spec(jnp.float32, rows), p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_attention": 1}
    ssm = p["state"][0][1].shape
    assert len(p["state"]) == 5 and len(p["counters"]) == 5
    assert ssm == (rows, 128, 64, 128)
    assert pool_copies(text, ssm) == 0
    assert pool_copies(text, p["pool"]) == 0
    memory = compiled.memory_analysis()
    donated = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(
                      (pages, pages, p["state"], p["counters"])))
    assert memory.alias_size_in_bytes >= donated
    recorded = p["config"]["memory_analysis"]["decode_step_batch96"]
    assert memory.argument_size_in_bytes == recorded["argument_bytes"]
    assert abs(memory.temp_size_in_bytes - recorded["temp_bytes"]) \
        < 0.2 * recorded["temp_bytes"]
    staging = rows * costs_nemotron_h.table(p["config"])[
        "staging_bytes_per_row"]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + staging < V5E_BYTES_LIMIT


def test_nemotron_prefill_chunk_compiles_for_v5e(nemotron_programs, as_tpu):
    """The largest bucket (256 tokens, the same einsums over every held
    expert as a decode step's: no kernel for them): the
    staging pytree holds dense K/V for the ONE layer that attends and
    a state for each of the five that scan, is donated and aliased, and
    nothing of a pool's shape is in the program."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = nemotron_programs
    spec = p["spec"]
    assert len(p["staged"]["kv"]) == 1 and len(p["staged"]["state"]) == 5
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 256), spec(jnp.int32, 1, 256),
        p["staged"], spec(jnp.int32), spec(jnp.int32),
        spec(jnp.int32)).compile()
    text = compiled.as_text()
    # the one layer that attends, through `ops.attention.attend_cache`
    # (PR 61); the expert layers still hold no kernel of their own
    assert pallas_kernels(text) == {"attend_cache": 1}
    assert pool_copies(text, p["pool"]) == 0
    assert pool_copies(text, p["state"][0][1].shape) == 0
    staged_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(p["staged"]))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= staged_bytes
    # the config file's record is of the call without `last`: one scalar
    recorded = p["config"]["memory_analysis"]["chunk_prefill_256"]
    assert 0 < memory.argument_size_in_bytes \
        - recorded["argument_bytes"] <= 512
    assert memory.temp_size_in_bytes < 0.5e9


# ---------------------------------------------------------------------------
# the EvaByte cell's programs (EVA attention: pages that leave a living row)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def evabyte_programs(v5e):
    """The `serve-evabyte-doc-closed80` cell's engine programs: its config
    file's widths, rows and pool, its builder, all 8 layers, with the
    shapes of their arguments on one described chip. The decode step of
    this model is the dense engine's, built in `__init__`: so a real
    engine, with shapes for weights and a pool of 8 pages, whose programs
    are then lowered at the file's pool."""
    import dataclasses
    from benchmarks.harness.builders_evabyte import evabyte_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "evabyte-6.5b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = evabyte_engine(config, seed=0)
    cfg = engine_cfg.model
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = placed(jax.eval_shape(lambda: unbox(cfg.module().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    engine = PagedLLMEngine(dataclasses.replace(engine_cfg, num_pages=8),
                            params=params)
    pool = (cfg.num_kv_heads, engine_cfg.num_pages, engine_cfg.page_size,
            cfg.head_dim)
    return {"engine": engine, "cfg": cfg, "engine_cfg": engine_cfg,
            "config": config, "pool": pool, "spec": spec, "params": params,
            "rows": engine_cfg.max_batch,
            "pages": [spec(cfg.dtype, *pool)] * cfg.num_layers}


def _within(read: int, recorded: int, share: float = 0.02) -> bool:
    return abs(read - recorded) <= share * recorded


def test_evabyte_decode_step_compiles_for_v5e_within_memory(
        evabyte_programs, as_tpu):
    """44 rows, a block table 160 wide (not ceil(10496 / 16) = 656), 32
    kv heads with one query each through the paged kernel, a kernel a
    layer, every pool donated and updated in place, and arguments +
    temporaries as the file's `memory_analysis` records them."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = evabyte_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    recorded = p["config"]["memory_analysis"]["decode_step_batch44"]
    assert (rows, width) == (44, 160) == (44, recorded["block_table_width"])
    compiled = p["engine"]._decode.lower(
        p["params"], p["pages"], p["pages"], spec(jnp.int32, rows, width),
        spec(jnp.int32, rows), spec(jnp.int32, rows), spec(jnp.uint32, 2),
        spec(jnp.float32, rows), spec(jnp.int32, rows),
        spec(jnp.float32, rows)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_attention": cfg.num_layers}
    assert pool_copies(text, p["pool"]) == 0
    memory = compiled.memory_analysis()
    pools = 2 * cfg.num_layers * 2 * math.prod(p["pool"])
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert _within(memory.argument_size_in_bytes,
                   recorded["argument_bytes"])
    assert _within(memory.argument_size_in_bytes
                   + memory.temp_size_in_bytes,
                   recorded["argument_bytes"] + recorded["temp_bytes"])
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < V5E_BYTES_LIMIT - 1.5e9


def test_evabyte_prefill_chunk_compiles_for_v5e_over_the_pools(
        evabyte_programs, as_tpu):
    """The largest bucket, as the tick runs it: the pools donated and
    aliased, the row's table in place of a dense cache (no argument of
    [1, 32, >= 2048, 128]), no pool relaid out, temporaries as
    recorded."""
    from ray_tpu.llm.paged import pool_copies
    p = evabyte_programs
    spec, cfg = p["spec"], p["cfg"]
    lowered = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 256), spec(jnp.int32, 1, 256),
        (p["pages"], p["pages"]), spec(jnp.int32),
        spec(jnp.int32, p["engine_cfg"].pages_per_seq), spec(jnp.int32))
    dense = [a for a in jax.tree_util.tree_leaves(lowered.args_info)
             if len(a.shape) == 4 and a.shape[0] == 1
             and a.shape[2] >= cfg.window_size]
    assert not dense
    compiled = lowered.compile()
    assert pool_copies(compiled.as_text(), p["pool"]) == 0
    memory = compiled.memory_analysis()
    recorded = p["config"]["memory_analysis"]["chunk_prefill_256"]
    assert memory.alias_size_in_bytes >= recorded["alias_bytes"]
    assert _within(memory.argument_size_in_bytes
                   + memory.temp_size_in_bytes,
                   recorded["argument_bytes"] + recorded["temp_bytes"])
    assert memory.temp_size_in_bytes < 0.25e9


def test_evabyte_compress_window_compiles_for_v5e_in_place(
        evabyte_programs, as_tpu):
    """One row's 128 window pages of every layer into 8 pages of
    summaries, in the donated pools, with no copy of a pool."""
    from ray_tpu.llm.paged import pool_copies
    p = evabyte_programs
    spec, cfg = p["spec"], p["cfg"]
    compiled = p["engine"]._compress_window.lower(
        p["params"], p["pages"], p["pages"],
        spec(jnp.int32, cfg.window_size // p["engine_cfg"].page_size)
    ).compile()
    assert pool_copies(compiled.as_text(), p["pool"]) == 0
    memory = compiled.memory_analysis()
    recorded = p["config"]["memory_analysis"]["compress_window"]
    assert memory.alias_size_in_bytes >= recorded["alias_bytes"]
    assert _within(memory.argument_size_in_bytes
                   + memory.temp_size_in_bytes,
                   recorded["argument_bytes"] + recorded["temp_bytes"])
    assert memory.temp_size_in_bytes < 16e6


# ---------------------------------------------------------------------------
# the Sarvam-105B cell's programs (latent attention: one pool a layer)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sarvam_programs(v5e):
    """The `serve-sarvam-docturns-closed96` cell's engine programs: its
    config file's widths, rows and pool, its builder, its six layers, with
    the shapes of their arguments on one described chip, on an engine that
    never allocated anything."""
    from benchmarks.harness.builders_sarvam_mla import sarvam_mla_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sarvam-105b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = sarvam_mla_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (1, engine_cfg.num_pages, engine_cfg.page_size,
            cfg.latent_cache()[0])
    return {"engine": engine, "engine_cfg": engine_cfg, "cfg": cfg,
            "config": config, "spec": spec, "pool": pool,
            "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]))),
            "rows": engine_cfg.max_batch,
            "pools": [spec(cfg.dtype, *pool)] * cfg.num_layers,
            "counters": placed(jax.eval_shape(cfg.init_counters))}


def test_sarvam_decode_step_compiles_for_v5e_within_memory(
        sarvam_programs, as_tpu):
    """48 rows, a block table 524 wide, 64 heads against ONE latent kv
    head through the latent kernel, a kernel a layer and ONE pool a layer
    (640 lanes: 576 in whole tiles), every held expert on every token, the
    pools and the expert counters donated and updated in place, and
    arguments + temporaries as the file's `memory_analysis` records them."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = sarvam_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    recorded = p["config"]["memory_analysis"]["decode_step_batch48"]
    assert (rows, width) == (48, 524) == (48, recorded["block_table_width"])
    assert p["pool"] == (1, p["engine_cfg"].num_pages, 64, 640)
    compiled = p["engine"]._decode.lower(
        p["params"], p["pools"], spec(jnp.bool_, rows),
        spec(jnp.int32, rows, width), spec(jnp.int32, rows),
        spec(jnp.int32, rows), spec(jnp.uint32, 2), spec(jnp.float32, rows),
        spec(jnp.int32, rows), spec(jnp.float32, rows),
        p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"latent_attention": cfg.num_layers}
    assert pool_copies(text, p["pool"]) == 0
    assert len(p["counters"]) == 5
    memory = compiled.memory_analysis()
    pools = cfg.num_layers * 2 * math.prod(p["pool"])
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert _within(memory.argument_size_in_bytes,
                   recorded["argument_bytes"])
    assert _within(memory.argument_size_in_bytes
                   + memory.temp_size_in_bytes,
                   recorded["argument_bytes"] + recorded["temp_bytes"])
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < V5E_BYTES_LIMIT - 1.0e9


def test_sarvam_prefill_chunk_compiles_for_v5e_over_the_pools(
        sarvam_programs, as_tpu):
    """The largest bucket (256 tokens): the chunk's latent rows go into
    the row's pages through its table and are attended there in blocks
    (no kernel of the program's own, no dense cache of a row, nothing of
    [chunk, vocab]); the pools are donated and aliased."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = sarvam_programs
    spec, cfg = p["spec"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 256), spec(jnp.int32, 1, 256),
        p["pools"], spec(jnp.int32), spec(jnp.int32, width),
        spec(jnp.int32), spec(jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {}
    assert pool_copies(text, p["pool"]) == 0
    assert array_shapes(text, (256, cfg.vocab_size)) == 0
    memory = compiled.memory_analysis()
    recorded = p["config"]["memory_analysis"]["chunk_prefill_256"]
    assert memory.alias_size_in_bytes \
        >= cfg.num_layers * 2 * math.prod(p["pool"])
    assert _within(memory.argument_size_in_bytes,
                   recorded["argument_bytes"])
    assert _within(memory.temp_size_in_bytes, recorded["temp_bytes"], 0.2)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < V5E_BYTES_LIMIT - 1.0e9


# ---------------------------------------------------------------------------
# the Keye-VL-2.0 cell's programs (sparse attention: three pools a layer)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def keye_programs(v5e):
    """The `serve-keye-longdoc-closed96` cell's engine programs: its config
    file's widths, rows and pools, its builder, its six layers, with the
    shapes of their arguments on one described chip, on an engine that
    never allocated anything."""
    from benchmarks.harness.builders_keye_dsa import keye_dsa_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = keye_dsa_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    kv = (1, engine_cfg.num_pages, engine_cfg.page_size,
          cfg.num_kv_heads * cfg.head_dim)
    index = kv[:3] + (cfg.index_cache(),)
    return {"engine": engine, "engine_cfg": engine_cfg, "cfg": cfg,
            "config": config, "spec": spec, "kv": kv, "index": index,
            "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]))),
            "rows": engine_cfg.max_batch,
            "pools": ([spec(cfg.dtype, *kv)] * cfg.num_layers,
                      [spec(cfg.dtype, *kv)] * cfg.num_layers,
                      [spec(cfg.dtype, *index)] * cfg.num_layers),
            "counters": placed(jax.eval_shape(cfg.init_counters))}


def _keye_pool_bytes(p) -> int:
    return p["cfg"].num_layers * 2 * (2 * math.prod(p["kv"])
                                      + math.prod(p["index"]))


def test_keye_decode_step_compiles_for_v5e_within_memory(
        keye_programs, as_tpu):
    """48 rows, a block table 1036 wide: every row's index keys scored over
    its pages by the scoring kernel (a kernel a layer), the exact top-2048,
    the selected tokens' K and V gathered from token-major pools and read
    as they are gathered (nothing of `[rows, k, kv heads, head dim]`: the
    chip would relay out every gathered row for it), every held expert on
    every token; the three pools a layer and the expert counters donated
    and updated in place, NO pool copied, and arguments + temporaries as
    the file's `memory_analysis` records them and not higher, the fullest
    device over 60 % full."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = keye_programs
    spec, rows = p["spec"], p["rows"]
    width = p["engine_cfg"].pages_per_seq
    recorded = p["config"]["memory_analysis"]["decode_step_batch48"]
    assert (rows, width) == (48, 1036) == (48, recorded["block_table_width"])
    assert p["kv"] == (1, 13312, 64, 512) and p["index"] == (1, 13312, 64, 128)
    compiled = p["engine"]._decode.lower(
        p["params"], p["pools"], spec(jnp.bool_, rows),
        spec(jnp.int32, rows, width), spec(jnp.int32, rows),
        spec(jnp.int32, rows), spec(jnp.uint32, 2), spec(jnp.float32, rows),
        spec(jnp.int32, rows), spec(jnp.float32, rows),
        p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"dsa_index_scores": p["cfg"].num_layers}
    assert pool_copies(text, p["kv"]) == pool_copies(text, p["index"]) == 0
    cfg = p["cfg"]
    assert array_shapes(text, (rows, cfg.index_topk, cfg.num_kv_heads,
                               cfg.head_dim)) == 0
    assert array_shapes(text, (rows, cfg.index_topk, p["kv"][3])) > 0
    assert len(p["counters"]) == 6
    memory = compiled.memory_analysis()
    pools = _keye_pool_bytes(p)
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert _within(memory.argument_size_in_bytes,
                   recorded["argument_bytes"])
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert _within(held, recorded["argument_bytes"] + recorded["temp_bytes"])
    assert memory.temp_size_in_bytes <= recorded["temp_bytes"]
    assert 0.6 * V5E_BYTES_LIMIT < held < V5E_BYTES_LIMIT - 1.5e9


@pytest.mark.parametrize("rows,k,kv_heads,group,page_size,dtype", [
    (48, 2048, 4, 8, 64, jnp.bfloat16),     # the cell's
    (5, 512, 1, 8, 16, jnp.bfloat16),       # one kv head
    (9, 1024, 8, 1, 64, jnp.bfloat16),      # a query a kv head
    (4, 256, 2, 4, 32, jnp.float32)])
def test_the_gathered_rows_are_attended_as_they_lie_on_v5e(
        v5e, as_tpu, rows, k, kv_heads, group, page_size, dtype):
    """`sparse_attend` compiled for the chip: the selected rows of each
    pool gathered once, `[rows, k, width]`, and no array of `[rows, k, kv
    heads, 128]` (or of any other split of the rows) beside them; the
    reference form holds the split rows, which is what the chip relays
    out."""
    from ray_tpu.llm.paged import array_shapes
    from ray_tpu.ops import sparse_attention as sa
    one = SingleDeviceSharding(v5e[0])

    def spec(kind, *shape):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one)

    width, table = kv_heads * 128, 4 * k // page_size
    args = (spec(jnp.float32, rows, kv_heads * group, 128),
            spec(dtype, 1, rows * table, page_size, width),
            spec(dtype, 1, rows * table, page_size, width),
            spec(jnp.int32, rows, k), spec(jnp.int32, rows),
            spec(jnp.int32, rows, table))

    def text(**how):
        return jax.jit(lambda *a: sa.sparse_attend(
            *a, kv_heads=kv_heads, **how)).lower(*args).compile().as_text()

    split, as_they_lie = (rows, k, kv_heads, 128), text()
    assert array_shapes(as_they_lie, split) == 0
    assert array_shapes(as_they_lie, (rows, k, width)) > 0
    if kv_heads > 1:
        assert array_shapes(text(reference=True), split) > 0


@pytest.mark.parametrize("rows,k,width,pages,page_size", [
    (48, 2048, 1036, 13312, 64),            # the cell's
    (8, 2048, 4096, 13312, 64),             # the published context's table
    (5, 512, 100, 2000, 16)])               # under one group of 128 pages
def test_the_selected_rows_are_found_with_no_gather_of_page_ids_on_v5e(
        v5e, as_tpu, rows, k, width, pages, page_size):
    """`sparse_attend` compiled for the chip holds exactly two gathers,
    K's rows and V's: the block table is read by a one-hot product
    (`pool_rows`), so no gather gives `[rows, k]` int32 (98,304 single
    elements a layer at the cell's shapes, 1.00 ms of the chip: PERF.md
    section 6, PR 57), nothing of `[rows, k, table width]` stands
    anywhere in any type, and the temporaries are no more than the
    reference form's, which keeps the third gather."""
    from ray_tpu.llm.paged import array_shapes
    from ray_tpu.ops import sparse_attention as sa
    one = SingleDeviceSharding(v5e[0])

    def spec(kind, *shape):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one)

    pool = spec(jnp.bfloat16, 1, pages, page_size, 512)
    args = (spec(jnp.float32, rows, 32, 128), pool, pool,
            spec(jnp.int32, rows, k), spec(jnp.int32, rows),
            spec(jnp.int32, rows, width))

    def compiled(**how):
        return jax.jit(lambda *a: sa.sparse_attend(
            *a, kv_heads=4, **how)).lower(*args).compile()

    def gathers(program):
        return sorted(re.findall(r"= (\w+\[[\d,]*\])\S* gather\(",
                                 program.as_text()))

    taken, plain = compiled(), compiled(reference=True)
    selected = f"bf16[{rows},{k},512]"
    assert gathers(taken) == [selected, selected]
    assert gathers(plain) == [selected, selected, f"s32[{rows},{k}]"]
    assert array_shapes(taken.as_text(), (rows, k, width)) == 0
    assert taken.memory_analysis().temp_size_in_bytes \
        <= plain.memory_analysis().temp_size_in_bytes


def _row_piece_copy(pool_shape, piece_rows: int):
    """A kernel that copies `piece_rows` token rows, from an aligned
    offset it is told, out of a pool of `pool_shape` in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(at_ref, pool_hbm, o_ref, sem):
        copy = pltpu.make_async_copy(
            pool_hbm.at[pl.ds(pl.multiple_of(at_ref[0] * piece_rows,
                                             piece_rows), piece_rows)],
            o_ref, sem.at[0])
        copy.start()
        copy.wait()

    piece = (piece_rows,) + tuple(pool_shape[1:])
    return lambda at, pool: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(piece, lambda i, *_: (0,) * len(piece)),
            grid=(1,), scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct(piece, jnp.bfloat16))(at, pool)


def test_the_compiler_refuses_a_one_row_piece_of_a_token_major_pool(
        v5e, as_tpu):
    """Why no kernel gathers the selected rows from the K and V pools as
    they stand (PERF.md section 6, PR 51): seen as `[tokens, 512]` bf16 a
    pool stands in HBM in tiles of 8 token rows, and a copy of ONE row is
    refused; the aligned group of 8 that holds it (8 KB for 1 KB wanted)
    is taken, and so is one token of a pool `[tokens, 8, 128]` (a whole
    tile a token: ROADMAP S14(a)'s fused row). The day the first stops
    being refused, a kernel may gather a row a piece."""
    one = SingleDeviceSharding(v5e[0])

    def compiles(pool_shape, piece_rows):
        jax.jit(_row_piece_copy(pool_shape, piece_rows)).lower(
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct(pool_shape, jnp.bfloat16,
                                 sharding=one)).compile()

    with pytest.raises(Exception, match="aligned to tiling"):
        compiles((13312 * 64, 512), 1)
    compiles((13312 * 64, 512), 8)
    compiles((13312 * 64, 8, 128), 1)


@pytest.mark.parametrize("rows,heads,lanes,page_size,dtype", [
    (48, 16, 128, 64, jnp.bfloat16),        # the cell's
    (5, 20, 256, 16, jnp.bfloat16),         # heads padded, two lane tiles
    (9, 16, 128, 2048, jnp.bfloat16),       # a page a scoring step
    (4, 8, 128, 32, jnp.float32)])
def test_the_scoring_kernel_compiles_for_v5e_at_the_shapes_it_takes(
        v5e, as_tpu, rows, heads, lanes, page_size, dtype):
    """What `sparse_kernel` answers "pallas" for, the chip's compiler
    takes: pages that fill whole tiles and divide a scoring step, lanes in
    whole tiles, any head count."""
    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.attention import pallas_kernels
    assert sa.sparse_kernel(False, page_size, lanes) == "pallas"
    one = SingleDeviceSharding(v5e[0])

    def spec(kind, *shape):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one)

    width = 2 * sa._COPY_BLOCK_STEPS * sa._SCORE_BLOCK_TOKENS // page_size
    compiled = sa._index_scores_pallas.lower(
        spec(dtype, rows, heads, lanes), spec(jnp.float32, rows, heads),
        spec(dtype, 1, 4 * width, page_size, lanes), spec(jnp.int32, rows),
        spec(jnp.int32, rows, width)).compile()
    assert pallas_kernels(compiled.as_text()) == {"dsa_index_scores": 1}


@pytest.mark.parametrize("tokens,k,held,width,mlp,gated", [
    (512, 4, 64, 3584, 1024, True),         # the Xing cell's chunk
    (512, 8, 16, 2048, 768, True),          # Keye's (set-up's documents)
    (512, 8, 16, 4096, 2048, True),         # Sarvam's widths, were it 512
    (512, 22, 128, 1024, 2688, False)])     # Nemotron's, two matrices
def test_the_grouped_products_compile_for_v5e_within_fast_memory(
        v5e, as_tpu, tokens, k, held, width, mlp, gated):
    """What `moe.sorted_form` sends to the sorted form, the chip's compiler
    takes: the two kernels of `ops.grouped_matmul` over the T x k sorted
    pairs, the matrices' blocks double-buffered inside the fast memory the
    kernels ask for (whole matrices at the cell's widths), and none of the
    dense form's [held, T, f] arrays."""
    from ray_tpu.llm.paged import array_shapes
    from ray_tpu.models import moe
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops.attention import pallas_kernels
    assert moe.sorted_form(tokens, width, mlp)
    assert gm.grouped_kernel(width, mlp) == "pallas"
    matrices = 2 if gated else 1
    tile = gm._column_tile(width, mlp, matrices, 2)
    assert 2 * matrices * width * tile * 2 <= gm._MATRIX_BUDGET \
        < gm._VMEM_LIMIT < 128 << 20
    if (width, mlp) == (3584, 1024):
        assert tile == mlp and gm._column_tile(mlp, width, 1, 2) == width
    one = SingleDeviceSharding(v5e[0])

    def spec(kind, *shape):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one)

    w_in = spec(jnp.bfloat16, held, width, mlp)
    arrays = [spec(jnp.bfloat16, tokens, width),
              spec(jnp.int32, tokens, k), spec(jnp.float32, tokens, k),
              spec(jnp.bool_, tokens), w_in,
              spec(jnp.bfloat16, held, mlp, width)] + [w_in] * gated

    def expert_layer(*a):
        return moe.held_expert_sum(*a[:6], 0, *a[6:])

    compiled = jax.jit(expert_layer).lower(*arrays).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"grouped_hidden": 1, "grouped_out": 1}
    assert array_shapes(text, (held, tokens, mlp)) == 0


def test_keye_prefill_chunk_compiles_for_v5e_over_the_pools(
        keye_programs, as_tpu):
    """The largest bucket (512 tokens, `q_chunk_size`): the chunk's K, V
    and index rows go into the row's pages through its table, are scored
    there, and attended in blocks under each query's threshold (no dense
    cache of a row, nothing of [chunk, vocab], no pool copied); the pools
    are donated and aliased."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = keye_programs
    spec, cfg = p["spec"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    assert p["engine_cfg"].prefill_buckets[-1] == 512
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 512), spec(jnp.int32, 1, 512),
        p["pools"], spec(jnp.int32), spec(jnp.int32, width),
        spec(jnp.int32), spec(jnp.int32)).compile()
    text = compiled.as_text()
    # the routed experts of a 512-token chunk are sorted pairs (PR 53)
    assert pallas_kernels(text) == {"grouped_hidden": cfg.num_layers,
                                    "grouped_out": cfg.num_layers}
    assert pool_copies(text, p["kv"]) == pool_copies(text, p["index"]) == 0
    assert array_shapes(text, (512, cfg.vocab_size)) == 0
    memory = compiled.memory_analysis()
    recorded = p["config"]["memory_analysis"]["chunk_prefill_512"]
    assert memory.alias_size_in_bytes >= _keye_pool_bytes(p)
    assert _within(memory.argument_size_in_bytes,
                   recorded["argument_bytes"])
    assert _within(memory.temp_size_in_bytes, recorded["temp_bytes"], 0.2)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < V5E_BYTES_LIMIT - 1.5e9


# ---------------------------------------------------------------------------
# the Xing4.0 cell's programs (four residual streams mixed by
# hyper-connections; latent attention with a query latent, 32 heads; every
# expert held)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def xing_programs(v5e):
    """The `serve-xing-longin-closed64` cell's engine programs: its config
    file's widths, rows and pool, its builder, its six layers, with the
    shapes of their arguments on one described chip, on an engine that
    never allocated anything."""
    from benchmarks.harness.builders_xing_mhc import xing_mhc_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = xing_mhc_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (1, engine_cfg.num_pages, engine_cfg.page_size,
            cfg.latent_cache()[0])
    return {"engine": engine, "engine_cfg": engine_cfg, "cfg": cfg,
            "config": config, "spec": spec, "pool": pool,
            "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]))),
            "rows": engine_cfg.max_batch,
            "pools": [spec(cfg.dtype, *pool)] * cfg.num_layers,
            "counters": placed(jax.eval_shape(cfg.init_counters))}


def _xing_memory(p, compiled, recorded, temporaries="within"):
    """Arguments and temporaries as the file's `memory_analysis` states
    them (the temporaries within a fifth, or no higher than that where the
    program has shed some since), the pools aliased, and the rule of the
    cut: 80 % of the chip in arguments, 1.5 GB free beside the program."""
    memory = compiled.memory_analysis()
    pools = p["cfg"].num_layers * 2 * math.prod(p["pool"])
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert _within(memory.argument_size_in_bytes, recorded["argument_bytes"])
    if temporaries == "within":
        assert _within(memory.temp_size_in_bytes, recorded["temp_bytes"], 0.2)
    else:
        assert memory.temp_size_in_bytes <= recorded["temp_bytes"]
    assert memory.argument_size_in_bytes >= 0.8 * V5E_BYTES_LIMIT
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < V5E_BYTES_LIMIT - 1.5e9


def test_xing_decode_step_compiles_for_v5e_within_memory(
        xing_programs, as_tpu):
    """48 rows, a block table 152 wide, 32 heads against ONE latent kv head
    through the latent kernel (its blocks are functions of `heads`), a
    kernel a layer and ONE pool a layer, all 64 experts on every token, the
    pools and the expert counters donated and updated in place; the model's
    parameters are the issue's 4.79 B."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = xing_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    recorded = p["config"]["memory_analysis"]["decode_step_batch48"]
    assert (rows, width) == (48, 152) == (48, recorded["block_table_width"])
    assert p["pool"] == (1, p["engine_cfg"].num_pages, 64, 640)
    assert p["engine_cfg"].num_pages >= 48 * width
    table = p["config"]["memory_analysis"]["table"]
    leaves = jax.tree_util.tree_leaves(p["params"])
    assert sum(math.prod(a.shape) for a in leaves) \
        == table["weights_params"] == 4792669828
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) \
        == table["weights_bytes"]
    compiled = p["engine"]._decode.lower(
        p["params"], p["pools"], spec(jnp.bool_, rows),
        spec(jnp.int32, rows, width), spec(jnp.int32, rows),
        spec(jnp.int32, rows), spec(jnp.uint32, 2), spec(jnp.float32, rows),
        spec(jnp.int32, rows), spec(jnp.float32, rows),
        p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"latent_attention": cfg.num_layers}
    assert pool_copies(text, p["pool"]) == 0
    assert len(p["counters"]) == 5
    for scope in ("mhc/coeff", "mhc/pre", "mhc/post", "mla/q", "moe/route",
                  "moe/experts"):
        assert scope in text, scope
    _xing_memory(p, compiled, recorded)


def test_xing_prefill_chunk_compiles_for_v5e_over_the_pools(
        xing_programs, as_tpu):
    """The largest bucket (512 tokens): the chunk's latent rows go into the
    row's pages through its table and are attended there in blocks (no
    dense cache of a row, nothing of [chunk, vocab], no pool copied); the
    five expert layers' pairs go sorted through the two grouped kernels
    (PR 53), and none of the dense form's [64, 512, f] arrays is left; the
    pools are donated and aliased."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = xing_programs
    spec, cfg = p["spec"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    assert p["engine_cfg"].prefill_buckets[-1] == 512
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 512), spec(jnp.int32, 1, 512),
        p["pools"], spec(jnp.int32), spec(jnp.int32, width),
        spec(jnp.int32), spec(jnp.int32)).compile()
    text = compiled.as_text()
    experts = cfg.num_layers - cfg.first_k_dense_replace
    assert pallas_kernels(text) == {"grouped_hidden": experts,
                                    "grouped_out": experts}
    assert array_shapes(text, (64, 512, cfg.moe_intermediate_size)) == 0
    assert pool_copies(text, p["pool"]) == 0
    assert array_shapes(text, (512, cfg.vocab_size)) == 0
    # the file records the dense form's temporaries (260 MB; 94 now)
    _xing_memory(p, compiled,
                 p["config"]["memory_analysis"]["chunk_prefill_512"],
                 temporaries="no_higher")


# ---------------------------------------------------------------------------
# the LFM2 cell's programs (2 + 20 of 40 layers: 17 gated short convolutions,
# 5 attentions whose 64-wide heads stand two to a row of a packed pool, 8
# of 64 experts held)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lfm2_programs(v5e):
    """The `serve-lfm2-mixlen-closed128` cell's engine programs: its config
    file's widths, rows and pool, its builder, its 22 layers, with the
    shapes of their arguments on one described chip, on an engine that
    never allocated anything."""
    from benchmarks.harness.builders_lfm2 import lfm2_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        config = json.load(f)
    engine_cfg = lfm2_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = cfg.page_pool(engine_cfg.num_pages, engine_cfg.page_size)
    attending = cfg.layer_types.count("full_attention")
    return {"engine": engine, "engine_cfg": engine_cfg, "cfg": cfg,
            "config": config, "spec": spec, "pool": pool,
            "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]))),
            "rows": engine_cfg.max_batch,
            "pools": [spec(cfg.dtype, *pool)] * attending,
            "state": lambda rows: placed(jax.eval_shape(
                lambda: cfg.init_state(rows))),
            "counters": placed(jax.eval_shape(cfg.init_counters))}


def _lfm2_memory(p, compiled, recorded, largest=False):
    """Arguments and temporaries no higher than the file's
    `memory_analysis` states them, the pools aliased, and the rule of the
    cut (ISSUE 56): 1.5 GB free beside the program, and beside the
    `largest` program not 256 pages more. That is what the pool RESERVES;
    what the cell's traffic fills of it is the cell's `pool_in_use_pct`
    (a fifth: PERF.md section 4)."""
    memory = compiled.memory_analysis()
    pools = 2 * len(p["pools"]) * 2 * math.prod(p["pool"])
    # what the file's memory_analysis should say (pytest -s shows it)
    print({"argument_bytes": memory.argument_size_in_bytes,
           "temp_bytes": memory.temp_size_in_bytes,
           "alias_bytes": memory.alias_size_in_bytes, "pool_bytes": pools})
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert memory.argument_size_in_bytes <= 1.001 * recorded["argument_bytes"]
    assert memory.temp_size_in_bytes <= 1.2 * recorded["temp_bytes"]
    used = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert used < V5E_BYTES_LIMIT - 1.5e9
    if largest:
        more = 256 * p["config"]["memory_analysis"]["table"]["page_bytes"]
        assert used + more >= V5E_BYTES_LIMIT - 1.5e9


def test_lfm2_decode_step_compiles_for_v5e_within_memory(
        lfm2_programs, as_tpu):
    """96 rows, a block table 152 wide, 32 heads 64 wide against 8 kv heads
    that stand two to a 128-lane row of the pool: the paged kernel in each
    of the 5 attending layers (no gather fallback), no pool copied, the
    pools, the seventeen window pools and the 20 counter pairs donated and
    updated in place; the parameters are the file's table (2.13 B at 22
    layers, the issue's 3.76 B at 40)."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    from ray_tpu.ops.paged_attention import paged_kernel
    p = lfm2_programs
    spec, rows, cfg = p["spec"], p["rows"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    recorded = p["config"]["memory_analysis"]["decode_step_batch96"]
    assert (rows, width) == (96, 152) == (96, recorded["block_table_width"])
    assert p["pool"] == (4, p["engine_cfg"].num_pages, 64, 128) \
        == tuple(recorded["pool_shape"])
    assert paged_kernel(64, lanes=128) == "pallas"
    assert paged_kernel(64) == "gather" and paged_kernel(128) == "pallas"
    table = p["config"]["memory_analysis"]["table"]
    leaves = jax.tree_util.tree_leaves(p["params"])
    assert sum(math.prod(a.shape) for a in leaves) \
        == table["weights_params"] == 2129332096
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) \
        == table["weights_bytes"]
    state = p["state"](rows)
    assert len(state) == 17 == recorded["window_pools"]
    assert len(p["counters"]) == 20 == recorded["counter_pairs"]
    compiled = p["engine"]._decode.lower(
        p["params"], p["pools"], p["pools"], state, spec(jnp.bool_, rows),
        spec(jnp.int32, rows, width), spec(jnp.int32, rows),
        spec(jnp.int32, rows), spec(jnp.uint32, 2), spec(jnp.float32, rows),
        spec(jnp.int32, rows), spec(jnp.float32, rows),
        p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_attention": 5}
    assert pool_copies(text, p["pool"]) == 0
    for scope in ("conv/in", "conv/filter", "conv/out", "attn/qk_norm",
                  "attn/attend", "moe/route", "moe/experts"):
        assert scope in text, scope
    _lfm2_memory(p, compiled, recorded)


def test_lfm2_prefill_chunk_compiles_for_v5e_into_the_pages(
        lfm2_programs, as_tpu):
    """The largest bucket (512 tokens): the chunk's K/V go into the row's
    pages through its table and are attended there in blocks: no dense
    K/V of a row ([1, 8, max_len + bucket, 64]) among its arguments or
    temporaries, nothing of [chunk, vocab], no pool copied; the 20 expert
    layers' pairs go sorted through the two grouped kernels; the pools
    are donated and aliased."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = lfm2_programs
    spec, cfg = p["spec"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    assert p["engine_cfg"].prefill_buckets[-1] == 512
    staged = {"kv": list(zip(p["pools"], p["pools"])),
              "state": p["state"](1)}
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 512), spec(jnp.int32, 1, 512),
        staged, spec(jnp.int32), spec(jnp.int32, width), spec(jnp.int32),
        spec(jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"grouped_hidden": 20, "grouped_out": 20}
    assert pool_copies(text, p["pool"]) == 0
    positions = width * p["engine_cfg"].page_size + 512
    assert array_shapes(text, (1, 8, positions, 64)) == 0
    assert array_shapes(text, (8, positions, 64)) == 0
    assert array_shapes(text, (512, cfg.vocab_size)) == 0
    _lfm2_memory(p, compiled,
                 p["config"]["memory_analysis"]["chunk_prefill_512"],
                 largest=True)


# -- generation by diffusion over blocks (SDAR) ---------------------------


@pytest.fixture(scope="module")
def sdar_programs(v5e):
    """The `serve-sdar-fixedgen-closed160` cell's engine programs: its
    config file's widths, rows and pool, its builder, its six layers, with
    the shapes of their arguments on one described chip, on an engine that
    never allocated anything."""
    from benchmarks.harness.builders_sdar import sdar_engine
    from ray_tpu.llm.paged import PagedLLMEngine
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b-chat-serve.json")) as f:
        config = json.load(f)
    engine_cfg = sdar_engine(config, seed=0)
    cfg = engine_cfg.model
    engine = PagedLLMEngine.__new__(PagedLLMEngine, engine_cfg)
    engine.config, engine.model = engine_cfg, cfg.module()
    engine._kind_programs()
    one = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = (cfg.num_kv_heads, engine_cfg.num_pages, engine_cfg.page_size,
            cfg.head_dim)
    return {"engine": engine, "engine_cfg": engine_cfg, "cfg": cfg,
            "config": config, "spec": spec, "pool": pool,
            "params": placed(jax.eval_shape(lambda: unbox(engine.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]))),
            "pools": [spec(cfg.dtype, *pool)] * cfg.num_layers,
            "counters": placed(jax.eval_shape(cfg.init_counters))}


def _sdar_memory(p, compiled, recorded):
    """Arguments and temporaries no higher than the file's
    `memory_analysis` states them, the pools aliased, and the rule of the
    pool's size: the traffic's worst case (128 rows of 2,048 + 512 tokens)
    and a quarter more, with over 1.5 GB free beside the program."""
    memory = compiled.memory_analysis()
    pools = 2 * len(p["pools"]) * 2 * math.prod(p["pool"])
    # what the file's memory_analysis should say (pytest -s shows it)
    print({"argument_bytes": memory.argument_size_in_bytes,
           "temp_bytes": memory.temp_size_in_bytes,
           "alias_bytes": memory.alias_size_in_bytes, "pool_bytes": pools})
    assert pools == p["config"]["memory_analysis"]["table"]["pool_bytes"]
    assert memory.alias_size_in_bytes >= pools
    assert memory.argument_size_in_bytes <= 1.001 * recorded["argument_bytes"]
    assert memory.temp_size_in_bytes <= 1.2 * recorded["temp_bytes"]
    used = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert used < V5E_BYTES_LIMIT - 1.5e9
    engine = p["engine_cfg"]
    worst = engine.max_batch * (2048 + 512) // engine.page_size
    assert engine.num_pages == worst + worst // 4 == 6400


@pytest.mark.timeout_s(600)
def test_sdar_block_step_compiles_for_v5e_within_memory(
        sdar_programs, as_tpu):
    """128 rows x 4 block positions, a block table 48 wide: the paged
    kernel in each of the six layers at 32 queries a kv head (no gather
    fallback), the 512 token rows' 4,096 pairs through the two grouped
    kernels a layer (the sorted form, at decode), no pool copied, the pools
    and the six counter pairs donated and updated in place; the parameters
    are the file's table (4.36 B at six layers); the largest program."""
    from ray_tpu.llm.paged import pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = sdar_programs
    spec, cfg = p["spec"], p["cfg"]
    rows, width = p["engine_cfg"].max_batch, p["engine_cfg"].pages_per_seq
    L = cfg.block_length
    recorded = p["config"]["memory_analysis"]["decode_step_batch128"]
    assert (rows, width) == (128, 48) == (128, recorded["block_table_width"])
    assert p["pool"] == tuple(recorded["pool_shape"])
    table = p["config"]["memory_analysis"]["table"]
    leaves = jax.tree_util.tree_leaves(p["params"])
    assert sum(math.prod(a.shape) for a in leaves) \
        == table["weights_params"] == 4361055744
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) \
        == table["weights_bytes"]
    assert len(p["counters"]) == 6 == recorded["counter_pairs"]
    compiled = p["engine"]._decode.lower(
        p["params"], p["pools"], p["pools"], spec(jnp.bool_, rows),
        spec(jnp.int32, rows, width), spec(jnp.int32, rows),
        spec(jnp.int32, rows, L + 2), spec(jnp.bool_, rows),
        spec(jnp.int32, rows, L), spec(jnp.int32, rows),
        spec(jnp.float32, rows), spec(jnp.uint32, 2),
        spec(jnp.float32, rows), spec(jnp.int32, rows),
        spec(jnp.float32, rows), p["counters"]).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_attention": 6,
                                    "grouped_hidden": 6, "grouped_out": 6}
    assert pool_copies(text, p["pool"]) == 0
    for scope in ("attn/qk_norm", "sdar/attend", "moe/route", "moe/experts",
                  "sdar/confidence", "sdar/unmask"):
        assert scope in text, scope
    _sdar_memory(p, compiled, recorded)


@pytest.mark.timeout_s(600)
def test_sdar_prefill_chunk_compiles_for_v5e_into_the_pages(
        sdar_programs, as_tpu):
    """The largest bucket (512 tokens): the chunk's K/V go into the row's
    pages through its table and are attended there in blocks under the
    block mask: no dense K/V of a row among its arguments or temporaries,
    nothing of [chunk, vocab] (a prompt samples nothing: no head), no pool
    copied; the pairs go sorted through the two grouped kernels a layer;
    the chunks' own expert counters (six pairs of [128] int32) ride donated
    beside the pools."""
    from ray_tpu.llm.paged import array_shapes, pool_copies
    from ray_tpu.ops.attention import pallas_kernels
    p = sdar_programs
    spec, cfg = p["spec"], p["cfg"]
    width = p["engine_cfg"].pages_per_seq
    assert p["engine_cfg"].prefill_buckets[-1] == 512
    compiled = p["engine"]._chunk_prefill.lower(
        p["params"], spec(jnp.int32, 1, 512), spec(jnp.int32, 1, 512),
        (p["pools"], p["pools"], p["counters"]), spec(jnp.int32),
        spec(jnp.int32, width), spec(jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"grouped_hidden": 6, "grouped_out": 6}
    assert pool_copies(text, p["pool"]) == 0
    positions = width * p["engine_cfg"].page_size + 512
    assert array_shapes(text, (1, 4, positions, 128)) == 0
    assert array_shapes(text, (512, cfg.vocab_size)) == 0
    _sdar_memory(p, compiled,
                 p["config"]["memory_analysis"]["chunk_prefill_512"])
