"""Benchmark: Llama training step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": "train_tokens_per_sec_per_chip", "value": N, "unit": "tok/s",
   "vs_baseline": MFU/0.40, ...}

The baseline target is the north star from BASELINE.json: >=40% MFU on the
Llama fine-tune path (the reference has no in-repo number for this — 40% MFU
is the bar it sets). vs_baseline > 1.0 means above-target MFU.

`python bench.py` needs a chip and exits non-zero without one: a number
from the CPU is never printed under the chip metric's name. The
`--multichip`, `--rpc` and `--dryrun7b` arms are host-plane A/Bs on
(virtual) CPU devices by design.
"""

import json
import os
import sys
import time

if __name__ == "__main__":
    # Decided from the arguments, before jax is imported (it reads these
    # variables then): the CPU A/B arms hold this process to the CPU; the
    # chip cell points jax at the persistent compile cache.
    if "--rpc" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        from ray_tpu.accelerators.tpu import compile_cache_dir
        compile_cache_dir()

import jax
import jax.numpy as jnp

# Peak FLOP/s now live in ray_tpu/accelerators/flops.py — ONE table
# shared with the live MFU gauge (_internal/accel.py), re-exported here
# for callers that historically imported them from bench.
from ray_tpu.accelerators.flops import PEAK_FLOPS, peak_flops  # noqa: F401


def main():
    import optax

    from ray_tpu.models import LlamaConfig, LlamaModel, cross_entropy_loss
    from ray_tpu.parallel import (MeshConfig, create_train_state,
                                  make_train_step)

    if jax.default_backend() != "tpu":
        sys.exit(f"bench.py: no TPU (backend {jax.default_backend()!r}, "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); the "
                 "training cell is measured on the chip or not at all")
    if not os.environ.get("RTPU_BENCH_SMALL"):
        # ~2.65B params (VERDICT r3 item 5: push past 2.5B with remat).
        # Memory budget on one v5e (16 GB HBM): bf16 params 5.3 GB +
        # bf16 donated grads 5.3 GB + adafactor factored stats (fp32
        # row/col vectors, ~MBs) + remat'd activations. fp32 params
        # would be 10.6+10.6 GB and spill — bf16 params with
        # adafactor's fp32 factored accumulators is the T5X-lineage
        # memory-frugal configuration.
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2560, intermediate_size=6912,
            num_layers=32, num_heads=20, num_kv_heads=20,
            max_seq_len=2048, param_dtype=jnp.bfloat16)
        batch, seq, steps = 2, 2048, 10
        tx = optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adafactor(learning_rate=1e-3))
    else:
        # RTPU_BENCH_SMALL=1: ~1.26B params (the round-3
        # headline config). 16 heads of head_dim=128 keep the MXU's
        # 128-wide contraction full. Memory budget on one v5e (16 GB HBM):
        # fp32 params 5.0 GB + adafactor's factored second moments (~row+
        # col vectors, MBs) + remat'd activations + donated bf16 grads.
        # AdamW's m/v would add +10 GB and spill; adafactor is the
        # standard TPU memory-frugal choice (T5/PaLM lineage).
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=22, num_heads=16, num_kv_heads=16, max_seq_len=2048)
        batch, seq, steps = 4, 2048, 12
        tx = optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adafactor(learning_rate=1e-3))

    mesh = MeshConfig(data=-1).build()
    model = LlamaModel(config)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    state = create_train_state(
        jax.random.PRNGKey(0), model, tokens, mesh, tx)

    def loss_fn(params, batch_data):
        logits = model.apply({"params": params}, batch_data["tokens"])
        return cross_entropy_loss(logits[:, :-1], batch_data["tokens"][:, 1:])

    train_step = make_train_step(loss_fn, mesh, state=state)
    rng = jax.random.PRNGKey(1)
    data = {"tokens": jax.random.randint(rng, (batch, seq), 0,
                                         config.vocab_size)}

    from ray_tpu._internal import accel

    with mesh:
        # Warmup / compile, fenced by fetching the scalar loss. The accel
        # plane's compile tracker is installed before warmup so the
        # compile lands in rtpu_xla_compile_seconds_total.
        accel.ensure_installed()
        compile_t0 = time.perf_counter()
        state, metrics = train_step(state, data)
        float(jax.device_get(metrics["loss"]))
        warmup_s = time.perf_counter() - compile_t0
        start = time.perf_counter()
        for _ in range(steps):
            state, metrics = train_step(state, data)
        final_loss = float(jax.device_get(metrics["loss"]))
        elapsed = time.perf_counter() - start

    n_devices = jax.device_count()
    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / elapsed
    tokens_per_sec_per_chip = tokens_per_sec / n_devices

    n_params = config.num_params()
    flops_per_token = 6 * n_params + 12 * config.num_layers * seq * \
        config.hidden_size
    achieved = tokens_per_sec_per_chip * flops_per_token
    peak = peak_flops(jax.devices()[0])
    mfu = achieved / peak

    # Feed the live accelerator plane the same numbers the JSON line
    # reports: the rtpu_step_mfu gauge and the bench's offline MFU now
    # share both the FLOP model and the peak-FLOPs denominator.
    accel.report_step(
        "bench_train", elapsed, steps=steps,
        tokens=tokens_per_step * steps,
        device_s=elapsed,  # the loop is device-bound end to end
        flops=float(flops_per_token) * tokens_per_step * steps
        / n_devices,
        device_kind=getattr(jax.devices()[0], "device_kind", "cpu"))

    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tok/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": round(mfu, 4),
        "model_params": n_params,
        "batch": batch, "seq": seq, "steps": steps,
        "backend": jax.default_backend(),
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "loss": round(final_loss, 4),
        "warmup_s": round(warmup_s, 2),
        # jax.monitoring-attributed compile time (accel plane tracker)
        "xla_compile_s": round(accel.compile_seconds_total(), 2),
    }))


def dryrun_7b(n_devices: int = 8, run_step: bool = True):
    """The 7B north-star config sharded over an n-device mesh
    (BASELINE.json config 3: Llama-2-7B fine-tune), dryrun-grade on the
    virtual CPU mesh: AOT-compile the full SPMD train step (fsdp x data
    sharding with remat + adafactor), report XLA's PER-DEVICE memory
    accounting from the compiled executable, and optionally execute one
    real step for wall-clock. Run with:
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python bench.py --dryrun7b
    """
    import optax

    from ray_tpu.models import LlamaConfig, LlamaModel, cross_entropy_loss
    from ray_tpu.parallel import (MeshConfig, create_train_state,
                                  make_train_step)

    import dataclasses
    # bf16 params (see the single-chip big config): 7B fp32 would be
    # 26 GB/device unsharded; fsdp over 8 shards the 13 GB bf16 tree to
    # ~1.7 GB/device + adafactor factored stats.
    config = dataclasses.replace(LlamaConfig.llama2_7b(),
                                 param_dtype=jnp.bfloat16)
    batch, seq = n_devices, 2048
    mesh = MeshConfig(fsdp=n_devices // 2, data=2).build()
    model = LlamaModel(config)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adafactor(learning_rate=1e-4))
    t0 = time.perf_counter()
    state = create_train_state(
        jax.random.PRNGKey(0), model, tokens, mesh, tx)
    init_s = time.perf_counter() - t0

    def loss_fn(params, batch_data):
        logits = model.apply({"params": params}, batch_data["tokens"])
        return cross_entropy_loss(logits[:, :-1],
                                  batch_data["tokens"][:, 1:])

    train_step = make_train_step(loss_fn, mesh, state=state)
    data = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, config.vocab_size)}
    with mesh:
        t0 = time.perf_counter()
        lowered = train_step.lower(state, data)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        per_device = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(
                mem, "peak_memory_in_bytes",
                getattr(mem, "temp_size_in_bytes", None)),
        }
        step_s = None
        loss = None
        if run_step:
            t0 = time.perf_counter()
            state, metrics = compiled(state, data)
            loss = float(jax.device_get(metrics["loss"]))
            step_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "llama7b_dryrun_mesh",
        "model_params": config.num_params(),
        "mesh": {"fsdp": n_devices // 2, "data": 2},
        "n_devices": n_devices,
        "batch": batch, "seq": seq,
        "init_s": round(init_s, 1),
        "compile_s": round(compile_s, 1),
        "step_s": round(step_s, 1) if step_s is not None else None,
        "loss": round(loss, 4) if loss is not None else None,
        "per_device_memory": per_device,
        "backend": jax.default_backend(),
    }))


# ---------------------------------------------------------------------------
# multi-chip training plane: rank-Python-DP vs GSPMD vs MPMD pipeline
# (ROADMAP item 1; run `bench.py --multichip` — records MULTICHIP_r06-
# style rows; `--dryrun7b` appends the GSPMD parity gate + the 7B
# ZeRO-1 AOT memory accounting)
# ---------------------------------------------------------------------------

_RESPAWN_MARK = "_RTPU_BENCH_RESPAWNED"


def _ensure_virtual_devices(n: int) -> bool:
    """Re-exec (same argv) under an n-device virtual CPU mesh unless this
    process was already started under one. Returns True when the CURRENT
    process should run. Decided from the environment alone: a parent that
    asked jax for its devices would open the chip it found."""
    import subprocess

    from ray_tpu.accelerators.tpu import on_virtual_cpu_mesh
    flag = f"--xla_force_host_platform_device_count={n}"
    if on_virtual_cpu_mesh(n):
        return True
    if os.environ.get(_RESPAWN_MARK) == "1":
        raise RuntimeError(
            f"respawned for {n} virtual CPU devices, but the environment "
            f"still lacks JAX_PLATFORMS=cpu and {flag}")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                   f" {flag}"),
        PYTHONPATH=os.pathsep.join(
            p for p in (here, os.environ.get("PYTHONPATH")) if p),
        **{_RESPAWN_MARK: "1"})
    subprocess.run([sys.executable, os.path.abspath(__file__)]
                   + sys.argv[1:], env=env, cwd=here, check=True)
    return False


# The shared A/B model: L residual tanh blocks over width D. Every arm
# (dp/two-level/gspmd/pipeline and the single-process reference) trains
# the SAME math from the same seeds, so loss columns are comparable.
_AB = {"width": 128, "hidden": 256, "blocks": 4, "batch": 64,
       "steps": 6, "lr": 1e-2}


def _ab_block_params(rng, width, hidden):
    import numpy as np
    return {"w1": (rng.randn(width, hidden) / np.sqrt(width)
                   ).astype("float32"),
            "w2": (rng.randn(hidden, width) / np.sqrt(hidden)
                   ).astype("float32")}


def _ab_model_fn():
    import flax.linen as nn
    import jax.numpy as jnp

    cfg = _AB

    class Blocks(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(cfg["blocks"]):
                h = nn.Dense(cfg["hidden"])(x)
                x = x + nn.Dense(cfg["width"])(jnp.tanh(h))
            return nn.Dense(1)(x)

    return Blocks()


def _ab_loss_fn(model, params, batch):
    import jax.numpy as jnp
    pred = model.apply({"params": params}, batch["x"])
    return jnp.mean((pred - batch["y"]) ** 2)


def _ab_batch_fn(step, rank, world):
    import numpy as np
    rng = np.random.RandomState(1000 + step)
    x = rng.randn(_AB["batch"], _AB["width"]).astype(np.float32)
    y = rng.randn(_AB["batch"], 1).astype(np.float32)
    if world > 1:
        per = _AB["batch"] // world
        sl = slice(rank * per, (rank + 1) * per)
        return {"x": x[sl], "y": y[sl]}
    return {"x": x, "y": y}


def _ab_flops_per_step() -> float:
    # 6x params-touched per token-row (fwd 2x + bwd 4x), dense layers
    cfg = _AB
    per_row = 2 * (cfg["width"] * cfg["hidden"] * 2 * cfg["blocks"]
                   + cfg["width"])
    return 6.0 * per_row * cfg["batch"] / 2.0


def _ab_spec(schedule: str, steps: int, quant: str = None):
    from ray_tpu.parallel.spmd import Zero1Hyper
    from ray_tpu.train import GSPMDTrainSpec
    return GSPMDTrainSpec(
        model_fn=_ab_model_fn, loss_fn=_ab_loss_fn, batch_fn=_ab_batch_fn,
        steps=steps, hyper=Zero1Hyper(learning_rate=_AB["lr"]),
        tokens_per_step=_AB["batch"], flops_per_step=_ab_flops_per_step(),
        schedule=schedule, collective_quant=quant)


def _ab_trainer_arm(schedule: str, num_workers: int, steps: int,
                    quant: str = None, label: str = None) -> dict:
    """One JaxTrainer arm; returns the rank-0 final report + timing."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    trainer = JaxTrainer(
        _ab_train_loop_entry,
        train_loop_config={"spec": _ab_spec(schedule, steps, quant)},
        scaling_config=ScalingConfig(
            num_workers=num_workers,
            mesh_axes={"data": 2, "fsdp": 4},
            dcn_axes=("data",), num_slices=2,
            virtual_devices=8),
        run_config=RunConfig(storage_path="/tmp/rtpu-multichip-bench"))
    result = trainer.fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    wall = float(m.get("wall_s") or 0.0)
    compile_s = float((m.get("goodput") or {}).get("compile_s") or 0.0)
    return {
        "arm": label or schedule, "workers": num_workers, "steps": steps,
        "losses": m.get("losses"), "loss": m.get("loss"),
        "wall_s": round(wall, 3),
        "compile_s": round(compile_s, 3),
        "steady_step_s": round(max(0.0, wall - compile_s) / steps, 4),
        "tokens_per_s": round(
            _AB["batch"] * steps / max(1e-9, wall - compile_s), 1),
        "mfu": m.get("mfu"),
        "goodput": m.get("goodput"),
        "collective_bytes": m.get("collective_bytes"),
        "collective_algo": m.get("collective_algo"),
    }


def _ab_train_loop_entry(config):
    from ray_tpu.train import gspmd_train_loop
    return gspmd_train_loop(config)


def _ab_stage_init(stage_index, num_stages):
    """Pipeline split of the SAME blocks model: stage 0 = first half of
    the residual blocks, last stage = second half + head. Seeds match
    _ab_model_fn's flax init? No — flax init order differs; the
    pipeline arm is gated against its OWN fused single-process
    reference (same stage params), not against the flax arms' losses."""
    import numpy as np
    import jax.numpy as jnp

    cfg = _AB
    rng = np.random.RandomState(7 + stage_index)
    per = cfg["blocks"] // num_stages
    blocks = [_ab_block_params(rng, cfg["width"], cfg["hidden"])
              for _ in range(per)]
    params = {"blocks": [
        {k: jnp.asarray(v) for k, v in b.items()} for b in blocks]}
    if stage_index == num_stages - 1:
        params["head"] = jnp.asarray(
            (rng.randn(cfg["width"], 1) / np.sqrt(cfg["width"])
             ).astype("float32"))

    is_last = stage_index == num_stages - 1

    def apply_fn(p, x):
        for b in p["blocks"]:
            x = x + jnp.tanh(x @ b["w1"]) @ b["w2"]
        if is_last:
            return x @ p["head"]
        return x

    return apply_fn, params


def _ab_pipeline_loss(y, targets):
    import jax.numpy as jnp
    return jnp.mean((y - jnp.asarray(targets)) ** 2)


def _pipeline_reference(num_stages: int, steps: int, microbatches: int):
    """Fused single-process twin of the pipeline arm: same per-stage
    params, same microbatch grad averaging, same AdamW — the parity
    reference for the MPMD schedule."""
    import numpy as np
    import optax

    stages = [_ab_stage_init(s, num_stages) for s in range(num_stages)]
    params = [p for _, p in stages]
    applies = [fn for fn, _ in stages]

    def full_loss(params, x, y):
        h = x
        for fn, p in zip(applies, params):
            h = fn(p, h)
        return _ab_pipeline_loss(h, y)

    tx = optax.adamw(_AB["lr"])
    opt_state = tx.init(params)
    step_fn = jax.jit(lambda p, o, x, y: _ref_step(tx, full_loss, p, o,
                                                   x, y, microbatches))
    losses = []
    for i in range(steps):
        batch = _ab_batch_fn(i, 0, 1)
        p_new, opt_state, loss = step_fn(params, opt_state,
                                         batch["x"], batch["y"])
        params = p_new
        losses.append(float(np.asarray(loss)))
    return losses


def _ref_step(tx, full_loss, params, opt_state, x, y, microbatches):
    import jax.numpy as jnp
    import optax

    xs = jnp.reshape(x, (microbatches, -1) + x.shape[1:])
    ys = jnp.reshape(y, (microbatches, -1) + y.shape[1:])

    def grad_one(mb):
        return jax.value_and_grad(lambda p: full_loss(p, xs[mb], ys[mb])
                                  )(params)

    losses, grads = [], None
    for mb in range(microbatches):
        loss_mb, g = grad_one(mb)
        losses.append(loss_mb)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
    updates, opt_state = tx.update(grads, opt_state, params)
    return (optax.apply_updates(params, updates), opt_state,
            jnp.mean(jnp.stack(losses)))


def _ab_pipeline_arm(steps: int, num_stages: int = 2,
                     microbatches: int = 4) -> dict:
    import numpy as np

    from ray_tpu.train import MPMDPipeline

    ref_losses = _pipeline_reference(num_stages, steps, microbatches)
    pipe = MPMDPipeline(_ab_stage_init, num_stages=num_stages,
                        loss_fn=_ab_pipeline_loss,
                        microbatches=microbatches,
                        hyper_kwargs={"learning_rate": _AB["lr"]})
    try:
        losses = []
        # round 0 pays the stage compiles; measure the steady window
        batch0 = _ab_batch_fn(0, 0, 1)
        losses.append(pipe.step(batch0["x"], batch0["y"])["loss"])
        pipe.reset_window()
        t0 = time.perf_counter()
        for i in range(1, steps):
            batch = _ab_batch_fn(i, 0, 1)
            losses.append(pipe.step(batch["x"], batch["y"])["loss"])
        steady = time.perf_counter() - t0
        bubble = pipe.bubble_report()
    finally:
        pipe.teardown()
    deltas = [abs(a - b) for a, b in zip(losses, ref_losses)]
    return {
        "arm": "pipeline", "workers": num_stages, "steps": steps,
        "microbatches": microbatches,
        "losses": [round(x, 6) for x in losses],
        "loss": losses[-1],
        "ref_losses": [round(x, 6) for x in ref_losses],
        "parity_max_delta": max(deltas),
        "steady_step_s": round(steady / max(1, steps - 1), 4),
        "tokens_per_s": round(
            _AB["batch"] * (steps - 1) / max(1e-9, steady), 1),
        "bubble_fraction": bubble["bubble_fraction"],
        "bubble_theoretical": bubble["bubble_theoretical"],
        "bubble_serial_floor": bubble["bubble_serial_floor"],
        "host_roundtrips": bubble["host_roundtrips"],
        "device_pulls": bubble["device_pulls"],
    }


class _FlightDeckRank:
    """One rank of the straggler/SLO-alert demo (plain class; wrapped
    with ray_tpu.remote inside _flight_deck_demo)."""

    def __init__(self, rank, world, group):
        self.rank, self.world, self.group = rank, world, group

    def join(self, chaos_spec=""):
        if chaos_spec:
            # arm THIS process's chaos registry: every incoming
            # collective hop is delayed, making this rank late into
            # every subsequent op — the seeded straggler
            from ray_tpu._internal.chaos import REGISTRY
            REGISTRY.arm(spec=chaos_spec, seed=7)
        from ray_tpu.util.collective import collective as col
        col.init_collective_group(self.world, self.rank,
                                  group_name=self.group)
        return True

    def run_ops(self, ops):
        import numpy as np

        from ray_tpu.util.collective import collective as col
        for _ in range(ops):
            col.allreduce(np.arange(64, dtype=np.int64),
                          group_name=self.group)
        summary = col._group(self.group).straggler_summary()
        return summary

    def flush(self):
        from ray_tpu.train import steptrace
        from ray_tpu.util import metrics
        steptrace.flush()
        return metrics.flush_now()

    def leave(self):
        from ray_tpu.util.collective import collective as col
        col.destroy_collective_group(self.group)
        return True


def _flight_deck_demo(ops: int = 8, delay_s: float = 0.05) -> dict:
    """Deterministic straggler + SLO-alert e2e on the live cluster:
    four collective ranks; rank 1 arms a prob-1.0 chaos delay on its
    incoming collective hops (fixed seed — nothing is time-seeded), so
    it enters every op ~delay_s late. Rank 0 — the star root, the only
    rank that hears from several peers — attributes the skew to rank 1
    and emits STRAGGLER_DETECTED; one alert-engine pass over the
    cluster's flushed metrics then trips the collective-wait p95 SLO.
    Both surfaces land in the GCS (cli stragglers / cli alerts /
    /api/alerts)."""
    import ray_tpu
    from ray_tpu._internal.alerts import AlertEngine, default_rules
    from ray_tpu._internal.core_worker import get_core_worker
    from ray_tpu.train.steptrace import steptrace_disabled
    from ray_tpu.util import state as st
    from ray_tpu.util.metrics import collect_cluster_metrics

    world = 4
    group = "flightdeck-demo"
    rank_cls = ray_tpu.remote(num_cpus=1)(_FlightDeckRank)
    actors = [rank_cls.remote(r, world, group) for r in range(world)]
    spec = f"collective_msg:delay:1.0:{delay_s}"
    ray_tpu.get([a.join.remote(spec if r == 1 else "")
                 for r, a in enumerate(actors)], timeout=120)
    summaries = ray_tpu.get([a.run_ops.remote(ops) for a in actors],
                            timeout=300)
    ray_tpu.get([a.flush.remote() for a in actors], timeout=60)
    stragglers = st.stragglers()
    engine = AlertEngine(rules=default_rules())
    fired = engine.evaluate_once(
        snapshots=collect_cluster_metrics(get_core_worker().gcs))
    alert_rows = st.alerts()
    try:
        ray_tpu.get([a.leave.remote() for a in actors], timeout=60)
    except Exception:
        pass
    for a in actors:
        ray_tpu.kill(a)
    return {
        "chaos_spec": spec,
        "ops": ops,
        "steptrace_disabled": steptrace_disabled(),
        "straggler_events": [
            {k: e.get(k) for k in ("rank", "phase", "observer_rank",
                                   "wait_s", "median_others_s")}
            for e in stragglers["events"]],
        "observer_summary": summaries[0],
        "alerts_fired": [f["rule"] for f in fired],
        "alert_table_rules": sorted({a["rule"] for a in alert_rows}),
    }


def multichip_ab(steps: int = 6, out_path: str = None) -> dict:
    """The multi-chip A/B: rank-Python DP baseline vs two-level GSPMD
    vs whole-mesh GSPMD (ZeRO-1) vs MPMD pipeline, all on the emulated
    two-slice 8-device topology. Single-core caveat: arms that rely on
    overlap (pipeline) or on deleting Python turnarounds (gspmd) show
    their structure here and their full wall-clock win only with real
    parallel cores/chips."""
    import os

    import ray_tpu
    from ray_tpu.train import run_single_process_baseline

    if not _ensure_virtual_devices(8):
        return {}
    baseline = run_single_process_baseline(_ab_spec("auto", steps))
    ray_tpu.init(num_cpus=8, object_store_memory=300 * 1024 * 1024)
    try:
        rows = [
            _ab_trainer_arm("dp", num_workers=2, steps=steps),
            _ab_trainer_arm("two_level", num_workers=2, steps=steps),
            _ab_trainer_arm("two_level", num_workers=2, steps=steps,
                            quant="int8", label="two_level_int8"),
            _ab_trainer_arm("gspmd", num_workers=1, steps=steps),
            _ab_pipeline_arm(steps),
        ]
        # -- train-plane flight deck --------------------------------------
        # (1) the cross-rank step timeline the arms just flushed;
        # (2) the seeded straggler + SLO-alert e2e
        from ray_tpu.util import state as st
        timeline_path = "MULTICHIP_timeline.json"
        trace = st.train_timeline(filename=timeline_path)
        flight_deck = _flight_deck_demo()
    finally:
        ray_tpu.shutdown()
    for row in rows:
        if row["arm"] in ("dp", "two_level", "two_level_int8", "gspmd"):
            row["parity_max_delta"] = max(
                abs(a - b) for a, b in zip(row["losses"],
                                           baseline["losses"]))
    result = {
        "metric": "multichip_train_ab",
        "n_devices": 8,
        "topology": "two-slice emulated (data=2 over DCN x fsdp=4)",
        "model": dict(_AB),
        "baseline_losses": [round(x, 6) for x in baseline["losses"]],
        "rows": rows,
        "timeline": {
            "path": timeline_path,
            "spans": len(trace),
            "tracks": sorted({str(r["pid"]) for r in trace}),
        },
        "flight_deck": flight_deck,
        "caveat": ("one contended CPU socket: stage/worker overlap is "
                   "partially serialized, so pipeline/DP wall-clock "
                   "gaps understate real multi-chip behavior; the "
                   "structural wins (no per-step host turnaround for "
                   "gspmd, sharded optimizer, descriptor-only "
                   "activation channels) are measured directly"),
    }
    print(json.dumps(result))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def gspmd_parity_dryrun(steps: int = 4) -> dict:
    """The --dryrun7b acceptance gate: the GSPMD trainer (ZeRO-1, two
    emulated slices over DCN) vs the single-process baseline — loss
    parity < 1e-2 with MFU/goodput telemetry present in the train
    report. Runs at A/B scale: a 7B single-process CPU baseline would
    need ~26 GB and hours; the 7B-scale memory story is the AOT
    zero1 arm below."""
    import ray_tpu
    from ray_tpu.train import run_single_process_baseline

    spec = _ab_spec("auto", steps)
    baseline = run_single_process_baseline(spec)
    ray_tpu.init(num_cpus=8, object_store_memory=300 * 1024 * 1024)
    try:
        row = _ab_trainer_arm("gspmd", num_workers=1, steps=steps)
    finally:
        ray_tpu.shutdown()
    delta = max(abs(a - b) for a, b in zip(row["losses"],
                                           baseline["losses"]))
    rel = delta / max(1e-9, abs(baseline["losses"][-1]))
    out = {
        "metric": "gspmd_parity_dryrun",
        "losses": [round(x, 6) for x in row["losses"]],
        "baseline_losses": [round(x, 6) for x in baseline["losses"]],
        "parity_max_delta": delta,
        "parity_rel": rel,
        "mfu": row["mfu"],
        "goodput": row["goodput"],
        "steady_step_s": row["steady_step_s"],
        "ok": bool(rel < 1e-2 and row["goodput"] is not None
                   and row["mfu"] is not None),
    }
    assert out["ok"], out
    print(json.dumps(out))
    return out


def dryrun_7b_zero1(n_devices: int = 8, config=None, batch=None,
                    seq: int = 2048):
    """7B ZeRO-1 memory accounting WITHOUT allocating 7B of host RAM:
    AOT-lower the fused sharded-update step over abstract
    ShapeDtypeStruct state and read XLA's per-device accounting. The
    honest headline is argument_bytes: the optimizer moments enter the
    program sharded 1/8 per device (vs replicated AdamW's full copies);
    temp_bytes ALSO reports the flat-buffer schedule's concat cost —
    recorded, not hidden."""
    import dataclasses

    import numpy as np

    from ray_tpu.models import LlamaConfig, LlamaModel, cross_entropy_loss
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.parallel.spmd import (Zero1Hyper, Zero1State,
                                       make_zero1_train_step)

    if config is None:
        config = dataclasses.replace(LlamaConfig.llama2_7b(),
                                     param_dtype=jnp.bfloat16)
    batch = batch or n_devices
    mesh = MeshConfig(data=2, fsdp=n_devices // 2,
                      dcn_axes=("data",)).build(num_slices=2)
    model = LlamaModel(config)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    axes = ("data", "fsdp")
    hyper = Zero1Hyper(learning_rate=1e-4, clip_norm=1.0)

    from jax.sharding import NamedSharding, PartitionSpec as P
    abstract_params = jax.eval_shape(
        lambda r: _unboxed_init(model, r, tokens), jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(abstract_params))
    W = n_devices                   # update axes ("data","fsdp") = mesh
    pad_n = -(-n_params // W) * W
    opt_sharding = NamedSharding(mesh, P(axes))
    repl = NamedSharding(mesh, P())
    state = Zero1State(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        params=jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=repl),
            abstract_params),
        m=jax.ShapeDtypeStruct((pad_n,), jnp.float32,
                               sharding=opt_sharding),
        v=jax.ShapeDtypeStruct((pad_n,), jnp.float32,
                               sharding=opt_sharding),
        apply_fn=model.apply, hyper=hyper)

    def loss_fn(params, batch_data):
        logits = model.apply({"params": params}, batch_data["tokens"])
        return cross_entropy_loss(logits[:, :-1],
                                  batch_data["tokens"][:, 1:])

    data = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=repl)}
    with mesh:
        t0 = time.perf_counter()
        step = make_zero1_train_step(loss_fn, mesh, state, axes=axes,
                                     donate=False)
        compiled = step.lower(state, data).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
    opt_bytes_per_dev = 2 * pad_n * 4 // W
    print(json.dumps({
        "metric": "llama7b_zero1_dryrun",
        "model_params": n_params,
        "mesh": {"data": 2, "fsdp": n_devices // 2, "dcn": ["data"]},
        "optimizer_bytes_per_device_sharded": opt_bytes_per_dev,
        "optimizer_bytes_per_device_replicated": 2 * pad_n * 4,
        "optimizer_sharding_factor": W,
        "compile_s": round(compile_s, 1),
        "per_device_memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                      None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "backend": jax.default_backend(),
    }))


def _unboxed_init(model, rng, tokens):
    from ray_tpu.parallel.mesh import unbox
    return unbox(model.init(rng, tokens)["params"])


def rpc_transport_bench(out_path: str = "BENCH_rpc_r01.json") -> dict:
    """Transport-observatory overhead (`bench.py --rpc`): real-socket
    loopback echo with instrumentation on vs the RTPU_NO_RPC_METRICS
    kill switch, interleaved on/off rounds (min-of-runs each side), as
    a BENCH_rpc JSON artifact. The gate is deliberately loose (50%):
    the loopback echo is the worst case — ~100us baseline against a
    fixed per-call instrumentation cost of a few us — and run-to-run
    noise on a shared box swings the ratio by tens of percent."""
    from ray_tpu.perf import rpc_bench

    out = rpc_bench(n=2000)
    overhead = out["rpc_metrics_overhead_pct"]
    gates = {"rpc_metrics_overhead_pct_lt_50": overhead < 50.0}
    result = {
        "metric": "rpc_transport_overhead_ab",
        "rpc_call_us": round(out["rpc_call_us"], 2),
        "rpc_call_nometrics_us": round(out["rpc_call_nometrics_us"], 2),
        "rpc_metrics_overhead_pct": round(overhead, 2),
        "ring_stats_read_ns": round(out["ring_stats_read_ns"], 1)
        if "ring_stats_read_ns" in out else None,
        "gates": gates,
        "passed": all(gates.values()),
    }
    print(json.dumps(result))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    if "--dryrun7b" in sys.argv:
        if _ensure_virtual_devices(8):
            dryrun_7b(run_step="--no-step" not in sys.argv)
            dryrun_7b_zero1()
            gspmd_parity_dryrun()
    elif "--multichip" in sys.argv:
        multichip_ab(out_path="MULTICHIP_r06.json")
    elif "--rpc" in sys.argv:
        rpc_transport_bench()
    else:
        main()
