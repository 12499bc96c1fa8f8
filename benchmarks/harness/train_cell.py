"""One run of a training cell: JaxTrainer.fit() with a loop of the
benchmark's own in the worker that holds the chips — seeded batches made on
the host and prefetched one step ahead, the loss fetched and reported every
step, whole steps until --seconds have passed. Returns the run's record."""

from __future__ import annotations

import os
import queue
import shutil
import threading
import time
from typing import Any, Dict

from . import cluster, spec
from .cluster import say

WARM_STEPS = 2


def train_loop(config: Dict[str, Any]) -> Dict[str, Any]:
    """train_loop_per_worker: runs in the worker that holds the chip(s)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu._internal import accel
    from ray_tpu.parallel import (MeshConfig, create_train_state,
                                  make_train_step)
    from ray_tpu.parallel.mesh import named_sharding

    from . import trace as trace_mod, traffic as traffic_mod
    from .builders import jax_seed

    devices = jax.devices()
    device = {"pid": os.getpid(), "platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    rehearse, seed = config["rehearse"], config["seed"]
    if device["platform"] != ("cpu" if rehearse else "tpu"):
        raise RuntimeError(f"train worker is on {device}")
    cell_config, traffic = config["config"], config["traffic"]
    built = spec.resolve(cell_config["builder"])(cell_config, rehearse)
    module, tx, loss_fn = built["module"], built["tx"], built["loss_fn"]
    vocab = built["vocab_size"]
    batch, seq = int(traffic["batch"]), int(traffic["sequence"])

    ctx = train.get_context()
    mesh_config = ctx.mesh_config() or MeshConfig(data=1)
    mesh = ctx.get_mesh() if ctx.mesh_config() is not None \
        else mesh_config.build(devices[:1])
    rules = mesh_config.rules_dict()
    key = jax.random.PRNGKey(jax_seed(seed))
    sample = jnp.zeros((batch, seq), jnp.int32)

    def fresh_state():
        return create_train_state(key, module, sample, mesh, tx, rules)

    state = fresh_state()
    batch_sharding = named_sharding(mesh, ("batch", "seq"), rules)

    def make(step: int):
        tokens = traffic_mod.train_batch(traffic, seed, step, vocab)
        return {"tokens": jax.device_put(tokens, batch_sharding)}

    # the input pipeline: one batch ahead, on a thread of its own
    ahead: "queue.Queue" = queue.Queue(maxsize=1)
    stop = threading.Event()

    def produce():
        step = 0
        while not stop.is_set():
            item = make(step)
            while not stop.is_set():
                try:
                    ahead.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            step += 1

    producer = threading.Thread(target=produce, daemon=True,
                                name="bench-batches")
    step_fn = make_train_step(loss_fn, mesh, rules, state=state)
    first_batch = traffic_mod.train_batch(traffic, seed, 0, vocab)
    losses, ends, waits = [], [], []
    reduced = None
    with mesh:
        compiled = step_fn.lower(state, make(0)).compile()
        producer.start()

        def one_step(index: int):
            nonlocal state
            with jax.profiler.TraceAnnotation("train_step"):
                w0 = time.monotonic()
                with jax.profiler.TraceAnnotation("data_wait"):
                    data = ahead.get()
                waits.append(time.monotonic() - w0)
                with jax.profiler.TraceAnnotation("dispatch:train_step"):
                    state, metrics = compiled(state, data)
                with jax.profiler.TraceAnnotation("fetch_loss"):
                    loss = float(jax.device_get(metrics["loss"]))
            losses.append(loss)
            train.report({"step": index, "loss": loss})
            ends.append(time.monotonic())

        for i in range(WARM_STEPS):
            one_step(i)
        compiles_before = accel.compile_summary().get("compiles", 0)
        t0 = time.monotonic()
        seconds = config["seconds"]
        trace_at = t0 + seconds / 2.0 if config["traced"] else None
        index = WARM_STEPS
        while time.monotonic() - t0 < seconds:
            if trace_at is not None and time.monotonic() >= trace_at:
                trace_at = None
                directory = config["trace_dir"]
                shutil.rmtree(directory, ignore_errors=True)
                trace_mod.start(directory)
                began = time.monotonic()
                while time.monotonic() - began < min(4.0, seconds / 3.0) \
                        or index - WARM_STEPS < 3:
                    one_step(index)
                    index += 1
                    if time.monotonic() - began > 12.0:
                        break
                jax.profiler.stop_trace()
                continue
            one_step(index)
            index += 1
        t1 = ends[-1]
        compiles = accel.compile_summary().get("compiles", 0) \
            - compiles_before
        stop.set()
        memory = [d.memory_stats() for d in devices]
        if config["traced"]:
            reduced = trace_mod.reduce_directory(
                config["trace_dir"], config.get("keep_events"))
            shutil.rmtree(config["trace_dir"], ignore_errors=True)
        # parity, outside the window: the first step's loss against the
        # plain reference on the same (initial) weights and batch
        del state, compiled
        state = fresh_state()
        parity = built["reference_check"](state.params, first_batch,
                                          losses[0])
    n_window = len([e for e in ends if e > t0])
    return {"device": device, "t0": t0, "t1": t1, "losses": losses,
            "window_steps": n_window,
            "window_tokens": n_window * batch * seq,
            "window_waits": waits[-n_window:],
            "step_ends": [e for e in ends if e > t0],
            "compiles_in_window": compiles, "memory": memory,
            "trace": reduced, "parity": parity,
            "mesh": {k: int(v) for k, v in mesh.shape.items()}}


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config, traffic = cell.config, dict(cell.traffic)
    if rehearse:
        traffic.update(traffic.get("rehearse", {}))
    mesh_axes = config.get("mesh_axes")
    if rehearse:
        scaling = ScalingConfig(
            num_workers=1, mesh_axes=mesh_axes,
            virtual_devices=cell.chips if cell.chips > 1 else None)
    else:
        scaling = ScalingConfig(
            num_workers=1, use_tpu=True, mesh_axes=mesh_axes,
            resources_per_worker={"TPU": cell.chips})
    cluster.start_cluster(cell.chips, rehearse,
                          config.get("program_settings"))
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "traffic": traffic, "seed": seed,
                "seconds": seconds, "traced": traced, "rehearse": rehearse,
                "trace_dir": os.path.join(cell.root, "chiprun_out",
                                          "bench_trace", cell.name),
                "keep_events": os.path.join(
                    cell.root, "chiprun_out",
                    f"trace_events_{cell.name}.json.gz")},
            scaling_config=scaling).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    worker = result.worker_returns[0]
    cluster.wait_pid_gone(worker["device"]["pid"], "train worker")
    cluster.check_device(worker["device"], cell.chips, rehearse)
    record = dict(worker, kind="train", traffic=traffic, config=config,
                  seconds=seconds, rehearse=rehearse,
                  setup_s=worker["t0"] - started, chips=cell.chips)
    reasons = []
    if not all(math.isfinite(x) for x in worker["losses"]):
        reasons.append(f"non-finite loss in {worker['losses'][:8]}")
    if worker["compiles_in_window"]:
        reasons.append(f"{worker['compiles_in_window']} compiles inside "
                       "the window")
    if not worker["parity"]["ok"]:
        reasons.append(f"parity failed: {worker['parity']}")
    if result.metrics.get("loss") != worker["losses"][-1]:
        reasons.append("report() did not carry the last loss")
    say(f"bench: train parity {worker['parity']}")
    record.update(attempted=len(worker["losses"]), failed=0,
                  correct=not reasons, reasons=reasons)
    return record
