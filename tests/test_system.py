"""End-to-end system behaviour: the paper's full loop (PsA -> PSS -> agents
-> simulator -> discovered design -> executable mesh plan), the training
loop with fault drills, and the serving engine."""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.bridge import design_from_mesh, plan_from_design
from repro.core.compute import SYSTEM_1_DEVICE, SYSTEM_2_DEVICE
from repro.core.dse import run_search
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import TrainScenario
from repro.core.workload import Parallelism
from repro.models import model as M

REPO = Path(__file__).resolve().parent.parent


def test_full_stack_beats_single_stack():
    """The paper's headline claim, at test scale: full-stack DSE finds a
    design at least as good as workload-only DSE (strictly better in the
    evaluation benchmarks with bigger budgets)."""
    pset = paper_psa(1024)
    defaults = dict(sched_policy="fifo", coll_algo=("ring",) * 4, chunks=2,
                    multidim_coll="baseline", topology=("ring", "ring", "ring", "switch"),
                    npus_per_dim=(4, 8, 4, 8), bw_per_dim=(100,) * 4)
    wl_only = pset.restrict({"workload"}, defaults)

    def search(ps, seed):
        env = CosmicEnv(spec=ARCHS["gpt3-13b"], n_npus=1024, device=SYSTEM_2_DEVICE,
                        scenario=TrainScenario(1024, 2048))
        return run_search(ps, env, "ga", steps=250, seed=seed).best_reward

    full = max(search(pset, s) for s in (0, 1))
    single = max(search(wl_only, s) for s in (0, 1))
    assert full >= single


def test_discovered_design_is_executable():
    """bridge: a COSMIC workload design point maps onto a jax mesh plan."""
    par = Parallelism(1024, dp=64, sp=4, pp=1, weight_sharded=True)
    plan = plan_from_design(par)
    assert np.prod(plan.shape) == par.dp * par.sp * par.tp
    assert plan.fsdp
    rt = design_from_mesh({"data": 16, "model": 16}, weight_sharded=True)
    assert rt.n_npus == 256 and rt.dp == 16 and rt.tp == 16


def test_agents_find_multiple_distinct_optima():
    """Fig. 9: different agents converge to distinct configs of similar
    quality (design-space redundancy)."""
    env_fn = lambda: CosmicEnv(spec=ARCHS["gpt3-13b"], n_npus=1024,
                               device=SYSTEM_2_DEVICE,
                               scenario=TrainScenario(1024, 2048))
    pset = paper_psa(1024)
    results = {k: run_search(pset, env_fn(), k, steps=150, seed=3) for k in ("ga", "aco")}
    rewards = [r.best_reward for r in results.values()]
    assert all(r > 0 for r in rewards)
    # similar quality (within 10x of each other)...
    assert max(rewards) / max(min(rewards), 1e-30) < 10.0


def _run_train(args: list[str]) -> subprocess.CompletedProcess:
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *args],
        capture_output=True, text=True, timeout=900, env=env)


@pytest.mark.slow
def test_train_failure_and_resume(tmp_path):
    base = ["--arch", "qwen2-1.5b", "--reduced", "--steps", "20", "--batch", "2",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "50"]
    crash = _run_train(base + ["--fail-at", "12"])
    assert crash.returncode != 0
    assert "injected failure at step 12" in crash.stderr
    resume = _run_train(base)
    assert resume.returncode == 0, resume.stderr[-2000:]
    assert "resumed from step 10" in resume.stdout
    assert "done at step 20" in resume.stdout


def test_serving_engine_generates():
    from repro.serve.engine import Engine
    spec = reduced(ARCHS["qwen2-1.5b"], n_layers=2)
    params = M.init_params(jax.random.PRNGKey(0), spec)
    eng = Engine(spec, params, max_len=64)
    prompts = np.random.default_rng(0).integers(0, spec.vocab_size, (2, 8)).astype(np.int32)
    out, stats = eng.generate(prompts, max_new=8)
    assert out.shape == (2, 8)
    assert stats.tokens_out == 16
    # greedy decode is deterministic
    out2, _ = eng.generate(prompts, max_new=8)
    np.testing.assert_array_equal(out, out2)
