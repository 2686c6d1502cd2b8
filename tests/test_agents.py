"""Search agents: interface compliance + learning behaviour on the real env."""
from __future__ import annotations

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.agents import make_agent
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.dse import run_search
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import TrainScenario
from repro.core.space import DesignSpace


def _env():
    return CosmicEnv(spec=ARCHS["gpt3-13b"], n_npus=1024, device=SYSTEM_2_DEVICE,
                     scenario=TrainScenario(1024, 2048))


@pytest.mark.parametrize("kind", ["rw", "ga", "aco", "bo"])
def test_agent_runs_and_proposes_valid(kind):
    space = DesignSpace(paper_psa(1024))
    agent = make_agent(kind, space, seed=0)
    env = _env()
    for _ in range(30 if kind != "bo" else 22):
        cfg = agent.propose()
        assert space.is_valid(cfg)
        ev = env.step(cfg)
        agent.observe(cfg, ev.reward)
    assert agent.best_config is not None
    assert agent.best_reward >= 0


def test_learning_agents_beat_random_walk():
    steps, seeds = 300, (0, 1, 2)
    def best(kind, seed):
        return run_search(paper_psa(1024), _env(), kind, steps=steps, seed=seed).best_reward
    rw = np.mean([best("rw", s) for s in seeds])
    ga = np.mean([best("ga", s) for s in seeds])
    aco = np.mean([best("aco", s) for s in seeds])
    # history-aware agents should find better optima on average at this budget
    assert max(ga, aco) > rw
    assert min(ga, aco) >= rw * 0.7  # and never collapse far below baseline


def test_reward_curve_monotone_nondecreasing():
    res = run_search(paper_psa(1024), _env(), "ga", steps=80, seed=0)
    c = res.reward_curve
    assert all(c[i + 1] >= c[i] for i in range(len(c) - 1))
    assert res.steps_to_peak <= res.steps


def test_aco_pheromones_update():
    space = DesignSpace(paper_psa(1024))
    agent = make_agent("aco", space, seed=0)
    before = [t.copy() for t in agent.tau]
    cfg = agent.propose()
    agent.observe(cfg, 1.0)
    changed = any(not np.allclose(b, a) for b, a in zip(before, agent.tau))
    assert changed


def test_bo_uses_surrogate_after_init():
    space = DesignSpace(paper_psa(1024))
    agent = make_agent("bo", space, seed=0, n_init=5, candidates=32)
    rng = np.random.default_rng(0)
    for i in range(8):
        cfg = agent.propose()
        agent.observe(cfg, float(rng.random()))
    assert len(agent.X) == 8
    cfg = agent.propose()  # surrogate path
    assert space.is_valid(cfg)
