"""Full IMPALA CartPole convergence run: trains until the mean return
clears the 450 bar (reference release criterion) and writes the trace
to tests/artifacts_impala_full_run.json. Run on an uncontended box:

    JAX_PLATFORMS=cpu python tests/run_impala_full.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ray_tpu  # noqa: E402
from ray_tpu.rllib import ImpalaConfig  # noqa: E402

TARGET = 450.0
MAX_ITERS = int(os.environ.get("RTPU_IMPALA_ITERS", "4000"))
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "artifacts_impala_full_run.json")


def main():
    ray_tpu.init(num_cpus=4, object_store_memory=200 * 1024 * 1024)
    config = dict(
        lr=1e-3, lr_final=1.5e-4, lr_decay_iters=1600,
        lr_decay_begin_iters=1000,
        entropy_coeff=0.01, entropy_coeff_final=0.0,
        entropy_decay_iters=1800, vf_coeff=0.25,
        train_batch_slots=64, num_epochs=2, seed=0)
    algo = (ImpalaConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=32,
                         rollout_fragment_length=32)
            .training(**config)
            .build())
    trace = []
    best = 0.0
    t0 = time.time()
    reached = False
    result = {}
    for i in range(MAX_ITERS):
        result = algo.train()
        ret = result["episode_return_mean"]
        if ret == ret:
            best = max(best, ret)
        if i % 25 == 0 or best >= TARGET:
            trace.append({"iter": i,
                          "steps": result["num_env_steps_sampled"],
                          "ret": round(ret, 1) if ret == ret else None,
                          "best": round(best, 1)})
            print(trace[-1], flush=True)
        if best >= TARGET:
            reached = True
            break
    algo.stop()
    artifact = {
        "target": TARGET,
        "best_return": round(best, 1),
        "reached": reached,
        "iters": result.get("training_iteration", 0),
        "env_steps": result.get("num_env_steps_sampled", 0),
        "wall_s": round(time.time() - t0, 1),
        "config": config,
        "trace": trace,
    }
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=1)
    print("wrote", OUT, "reached:", reached, "best:", best)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
