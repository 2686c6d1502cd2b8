"""Quickstart: the full COSMIC loop in one minute.

1. Search the full-stack design space for a GPT3-13B training cluster.
2. Map the discovered workload design onto an executable JAX mesh plan.
3. Train a (reduced) qwen2-family model a few steps with the real runtime.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.bridge import plan_from_design
from repro.core.compute import SYSTEM_1_DEVICE
from repro.core.dse import run_search
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import TrainScenario
from repro.core.workload import Parallelism
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.train.train_step import RunConfig, init_train_state, make_train_step


def main():
    # -- 1. agent-based full-stack DSE (paper Sections 4-6) ---------------
    spec = ARCHS["gpt3-13b"]
    env = CosmicEnv(spec=spec, n_npus=512, device=SYSTEM_1_DEVICE,
                    scenario=TrainScenario(512, 2048))
    res = run_search(paper_psa(512), env, "ga", steps=300, seed=0)
    cfg = res.best_config
    print(f"[dse] best reward {res.best_reward:.3e} "
          f"latency {res.best_latency_ms:.1f} ms at step {res.steps_to_peak}")
    print(f"[dse] discovered workload: DP={cfg['dp']} SP={cfg['sp']} PP={cfg['pp']} "
          f"ZeRO={cfg['weight_sharded']} | collectives {cfg['coll_algo']} "
          f"| topology {cfg['topology']}")

    # -- 2. the design point is executable -------------------------------
    par = Parallelism(512, cfg["dp"], cfg["sp"], cfg["pp"], bool(cfg["weight_sharded"]))
    plan = plan_from_design(par)
    print(f"[bridge] mesh plan: shape={plan.shape} axes={plan.axis_names} "
          f"fsdp={plan.fsdp} sp={plan.sp}")

    # -- 3. train a real (reduced) model with the runtime ------------------
    mspec = reduced(ARCHS["qwen2-1.5b"])
    run_cfg = RunConfig(remat="none")
    state = init_train_state(jax.random.PRNGKey(0), mspec, run_cfg)
    step = jax.jit(make_train_step(mspec, cfg=run_cfg))
    data = SyntheticLM(mspec, DataConfig(global_batch=8, seq_len=64, seed=0))
    for i in range(20):
        state, metrics = step(state, data.batch_at(i))
        if i % 5 == 0:
            print(f"[train] step {i} loss {float(metrics['loss']):.4f}")
    print("[done] quickstart complete")


if __name__ == "__main__":
    main()
