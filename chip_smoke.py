"""Run the system's accelerator paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: engine, serve and train phases
    python chip_smoke.py --chips 4    # four chips: the trainer on a 2x2 mesh,
                                      # against the same steps on one device

1. engine: the DSE hot path.  A 32-point population of collective/network
   stacks over a qwen2-1.5b request stream on system2 (256 Poisson requests,
   ~26k ops) through ``CosmicEnv.step_batch`` on the fused ``jax`` backend,
   then a short GA campaign (``run_study``, gpt3-175b training on system2) on
   the same backend.  Both are repeated on the ``reference`` event loop and
   every simulated statistic is compared.
2. serve: qwen2-1.5b at its published widths, all 28 layers, through
   ``serve.engine.Engine``: 8 prompts of 512 tokens, 32 new tokens.  The
   logits of prefill and of every cached decode step are compared with one
   float32 full forward pass at the highest matmul precision.
3. train: ``launch.train.train_loop`` at qwen2-1.5b widths cut to 8 layers,
   bf16 params with an fp32 master copy.  The step-0 loss is compared with a
   float32 forward pass at the highest matmul precision.

Weights and data come from ``--seed``.  Each phase prints its compile
seconds (its first call less a steady one) and steady seconds, its deviations and the device's ``peak_bytes_in_use``; any
failed check raises, so the process exits non-zero.  The last line of
standard output is one JSON object naming the device.  The script refuses
to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.fig10_agents import agents_study, backend_population  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core.backends import get_backend  # noqa: E402
from repro.core.study import run_study  # noqa: E402
from repro.core.systems import system_env  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticLM  # noqa: E402
from repro.launch.train import parse_args, train_loop  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.runtime import spans  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402
from repro.train.loss import cross_entropy  # noqa: E402

# Engine: the fused backend prices and sweeps in float64, which the TPU does
# not have; XLA emulates it with pairs of float32 (about 48 bits of
# mantissa, not 53), so the chip is not bit-identical to the float64 numpy
# reference.  Each emulated add or multiply is off by ~2^-47 relative, and
# the sweep chains about 26k of them along a critical path: 26e3 * 2^-47
# ~ 2e-10, under this bound.  A pricing or scheduling fault is off by far
# more than 1e-9.
ENGINE_RTOL = 1e-9
# Serve: the engine's own compiled steps, replayed at the highest matmul
# precision, against the float32 forward pass at the same precision: the
# same f32 math in another order.  At the TPU's default precision (bf16
# operands, 2^-9) the logits of this random-init 28-layer model moved by
# 0.18 relative L2 on the chip, an amplification of ~100x; f32 rounding
# (2^-24) amplified alike gives ~5e-6.  A stale or misplaced KV-cache row
# is off by the logits' own size.  The default-precision deviation is
# printed, not checked: it measures the serving precision, not a fault.
SERVE_RTOL = 1e-3
# Train: bf16 weights and activations (2^-9 relative each); the mean
# cross-entropy over 4096 tokens averages their rounding out, far below 0.5%
# of a loss near ln(vocab) ~ 11.9.  A wrong gradient, batch or label shift
# moves the step-0 loss itself, or the steps after it, by more.
TRAIN_LOSS_RTOL = 5e-3
# Four chips against one: the same bf16 step partitioned over a 2x2 mesh sums
# in another order; a partitioning fault (a lost shard, a double-counted
# replica) moves the loss by a whole fraction of itself.
MESH_LOSS_RTOL = 5e-3


def say(phase: str, **kw) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def memory(stat: str = "peak_bytes_in_use") -> list[int]:
    """A memory statistic of every device, as the runtime reports it."""
    return [d.memory_stats()[stat] for d in jax.devices()]


def deviation(got, want) -> tuple[float, float]:
    """Largest absolute and largest relative deviation of ``got`` from
    ``want``; equal entries (infinities included) deviate by 0."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    same = got == want
    diff = np.where(same, 0.0, np.abs(got - want))
    rel = np.where(same, 0.0, diff / np.maximum(np.abs(want), 1e-300))
    return float(diff.max(initial=0.0)), float(rel.max(initial=0.0))


# ---------------------------------------------------------------------------
# 1. engine
# ---------------------------------------------------------------------------

def compare_evaluations(got, want) -> dict[str, tuple[float, float]]:
    """Per statistic (reward, latency and every float the scenario reports),
    the largest deviation over the population.  Validity and every integer
    statistic must agree exactly."""
    check([g.valid for g in got] == [w.valid for w in want],
          "jax and reference disagree on which points are valid")
    stats = {"reward": ([g.reward for g in got], [w.reward for w in want]),
             "latency_ms": ([g.latency_ms for g in got],
                            [w.latency_ms for w in want])}
    for g, w in zip(got, want):
        for k, v in w.detail.items():
            if isinstance(v, float):
                stats.setdefault(k, ([], []))
                stats[k][0].append(g.detail[k])
                stats[k][1].append(v)
            else:
                check(g.detail.get(k) == v, f"detail {k!r}: {g.detail.get(k)}"
                      f" != {v}")
    return {k: deviation(*v) for k, v in stats.items()}


def engine_phase(points: int = 32, n_requests: int = 256,
                 study_steps: int = 96, repeats: int = 3) -> None:
    backend = get_backend("jax")
    scenario, cfgs = backend_population(points, n_requests)
    envs = {name: system_env("qwen2-1.5b", "system2", scenario=scenario,
                             objective="goodput", backend=name)
            for name in ("jax", "reference")}
    t0 = time.perf_counter()
    envs["jax"].step_batch(cfgs)             # trace, plan, compile, run
    first_s = time.perf_counter() - t0
    steady = []
    for _ in range(repeats):
        envs["jax"].clear_memo()
        t0 = time.perf_counter()
        got = envs["jax"].step_batch(cfgs)
        steady.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    want = envs["reference"].step_batch(cfgs)
    ref_s = time.perf_counter() - t0
    say("engine", population=points, n_requests=n_requests,
        first_call_s=first_s, compile_s=first_s - min(steady),
        steady_s=min(steady),
        durations_s=backend.last_timings["durations_s"],
        sweep_s=backend.last_timings["sweep_s"], reference_s=ref_s,
        valid=sum(w.valid for w in want))
    worst = 0.0
    for k, (d_abs, d_rel) in compare_evaluations(got, want).items():
        say("engine", stat=k, max_abs_dev=d_abs, max_rel_dev=d_rel)
        worst = max(worst, d_rel)
    check(worst <= ENGINE_RTOL,
          f"engine statistics deviate by {worst} > {ENGINE_RTOL}")

    study = dataclasses.replace(agents_study(study_steps), agents=("ga",),
                                batch_size=32)
    runs = {}
    for name in ("jax", "reference"):
        t0 = time.perf_counter()
        res = runs[name] = run_study(
            dataclasses.replace(study, backend=name)).outcomes[0].result
        say("engine", study=study.name, backend=name, steps=res.steps,
            wall_s=time.perf_counter() - t0, best_reward=res.best_reward)
    g, w = runs["jax"], runs["reference"]
    check(g.best_config == w.best_config,
          "the GA campaign found another best design on the chip")
    d_abs, d_rel = deviation(g.reward_curve, w.reward_curve)
    say("engine", stat="study_reward_curve", max_abs_dev=d_abs,
        max_rel_dev=d_rel, tolerance=ENGINE_RTOL)
    check(d_rel <= ENGINE_RTOL,
          f"campaign rewards deviate by {d_rel} > {ENGINE_RTOL}")
    say("engine", peak_bytes_in_use=memory()[0])


# ---------------------------------------------------------------------------
# 2. serve
# ---------------------------------------------------------------------------

def serve_phase(spec, batch: int = 8, prompt_len: int = 512,
                new: int = 32, seed: int = 0) -> None:
    params = M.init_params(jax.random.PRNGKey(seed), spec)
    eng = Engine(spec, params, max_len=prompt_len + new)
    prompts = np.random.default_rng(seed).integers(
        0, spec.vocab_size, (batch, prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    out, _ = eng.generate(prompts, max_new=new)          # compiles both steps
    first_s = time.perf_counter() - t0
    out2, stats = eng.generate(prompts, max_new=new)
    check(np.array_equal(out, out2), "greedy decoding is not deterministic")
    say("serve", arch=spec.name, layers=spec.n_layers, batch=batch,
        prompt_len=prompt_len, new_tokens=new, first_call_s=first_s,
        compile_s=first_s - stats.prefill_s - stats.decode_s,
        prefill_s=stats.prefill_s, decode_s=stats.decode_s,
        decode_tok_per_s=stats.decode_tok_per_s)

    # replay generate()'s steps on the tokens it chose, keeping every
    # step's logits: prefill's, then one per token fed through the cache
    def replay() -> np.ndarray:
        caches = M.init_caches(spec, batch, eng.max_len, dtype=eng.dtype)
        logits, caches = eng.prefill(params, jnp.asarray(prompts), caches)
        got = [np.asarray(logits, np.float64)]
        for i in range(new - 1):
            logits, caches = eng.decode(
                params, caches, jnp.asarray(out[:, i]),
                jnp.asarray(prompt_len + i, jnp.int32))
            got.append(np.asarray(logits, np.float64))
        return np.stack(got, axis=1)

    tokens = jnp.asarray(np.concatenate([prompts, out[:, :-1]], axis=1))
    default = replay()
    with jax.default_matmul_precision("highest"):
        highest = replay()
        ref, _ = jax.jit(lambda p, t: M.forward(
            p, t, spec, compute_dtype=jnp.float32, remat="none"))(params, tokens)
        want = np.asarray(ref[:, prompt_len - 1:], np.float64)
    del ref, params, eng
    for precision, got in (("default", default), ("highest", highest)):
        for name, sl in (("prefill", slice(0, 1)), ("decode", slice(1, None))):
            rel = float(np.linalg.norm(got[:, sl] - want[:, sl])
                        / np.linalg.norm(want[:, sl]))
            say("serve", logits=name, matmul_precision=precision,
                rel_l2_dev=rel, max_abs_dev=deviation(got[:, sl],
                                                      want[:, sl])[0],
                ref_max_abs=float(np.abs(want[:, sl]).max()),
                tolerance=SERVE_RTOL if precision == "highest" else "none")
            if precision == "highest":
                check(rel <= SERVE_RTOL, f"{name} logits deviate by {rel} > "
                      f"{SERVE_RTOL} (relative L2)")
    say("serve", greedy_token_agreement=float(np.mean(want.argmax(-1) == out)),
        peak_bytes_in_use=memory()[0])


# ---------------------------------------------------------------------------
# 3. train
# ---------------------------------------------------------------------------

TRAIN_LAYERS = 8


def train_args(mesh: str = "", steps: int = 4, batch: int = 4,
               seq: int = 1024, seed: int = 0):
    return parse_args(["--steps", str(steps), "--batch", str(batch),
                       "--seq", str(seq), "--seed", str(seed), "--bf16",
                       "--remat", "full", "--mesh", mesh,
                       "--log-every", "1"])


def train_spec():
    return dataclasses.replace(get_arch("qwen2-1.5b"), n_layers=TRAIN_LAYERS)


def say_cut(spec, args) -> None:
    n = spec.param_count()
    full = get_arch("qwen2-1.5b")
    say("train", arch=spec.name, layers=f"{spec.n_layers}_of_{full.n_layers}",
        params=n, state_bytes=n * 14, tokens=args.batch * args.seq,
        remat=args.remat,
        why="bf16_params+fp32_master+adam_m_v=14B/param;"
            "8_layers_compile_to_10.2GB_of_15.75GB_HBM_with_remat_full;"
            "remat_none_needs_15.76GB")


def step_walls(run) -> list[float]:
    """Wall seconds of each step of ``run``, the loop that ran last: the
    rows its ``repro.train.step`` units left (the first step compiles)."""
    rows = spans.rows("repro.train.step")[-len(run.losses):]
    return rows["repro.train.step"].tolist()


def steady_s(walls: list[float]) -> float:
    return statistics.median(walls[1:])


def train_phase(spec, args) -> None:
    say_cut(spec, args)
    run = train_loop(args, spec)
    walls = step_walls(run)
    del run.state
    check(all(np.isfinite(run.losses)), f"non-finite loss: {run.losses}")
    say("train", steps=len(run.losses), first_step_s=walls[0],
        compile_s=walls[0] - steady_s(walls), steady_step_s=steady_s(walls),
        tokens_per_s=args.batch * args.seq / steady_s(walls),
        losses=",".join(repr(x) for x in run.losses))

    params = M.init_params(jax.random.PRNGKey(args.seed), spec)
    batch = SyntheticLM(spec, DataConfig(args.batch, args.seq,
                                         seed=args.seed)).batch_at(0)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: cross_entropy(M.forward(
            p, b["inputs"], spec, compute_dtype=jnp.float32,
            remat="full")[0], b["labels"]))(params, batch))
    rel = abs(run.losses[0] - ref) / abs(ref)
    say("train", step0_loss=run.losses[0], reference_loss=ref,
        rel_dev=rel, tolerance=TRAIN_LOSS_RTOL,
        peak_bytes_in_use=memory()[0])
    check(rel <= TRAIN_LOSS_RTOL,
          f"step-0 loss {run.losses[0]} vs reference {ref}: {rel} > "
          f"{TRAIN_LOSS_RTOL}")


def mesh_phase(spec, args_one, args_mesh) -> None:
    """The same steps on one device and on a 2x2 mesh: equal losses, and the
    mesh run's state spread over all four devices."""
    say_cut(spec, args_mesh)
    one = train_loop(args_one, spec)
    walls = {"one_device": step_walls(one)}
    del one.state
    run = train_loop(args_mesh, spec)
    walls["mesh"] = step_walls(run)
    held = {d: 0 for d in jax.devices()[:4]}
    for leaf in jax.tree.leaves(run.state):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(run.state))
    in_use = memory("bytes_in_use")[:4]
    say("mesh", mesh=args_mesh.mesh, state_bytes=total,
        state_bytes_per_device=",".join(str(b) for b in held.values()),
        bytes_in_use_per_device=",".join(str(b) for b in in_use),
        peak_bytes_in_use_per_device=",".join(
            str(b) for b in memory()[:4]))
    check(max(held.values()) <= 0.5 * total,
          "a device holds more than half of the train state: not sharded")
    check(max(in_use) <= 2 * min(in_use),
          f"device memory in use is lopsided: {in_use}")
    del run.state
    for name, r in (("one_device", one), ("mesh", run)):
        say("mesh", run=name, first_step_s=walls[name][0],
            steady_step_s=steady_s(walls[name]),
            losses=",".join(repr(x) for x in r.losses))
    check(all(np.isfinite(run.losses)), f"non-finite loss: {run.losses}")
    d_abs, d_rel = deviation(run.losses, one.losses)
    say("mesh", loss_max_abs_dev=d_abs, loss_max_rel_dev=d_rel,
        tolerance=MESH_LOSS_RTOL)
    check(d_rel <= MESH_LOSS_RTOL,
          f"mesh losses deviate from one device by {d_rel} > "
          f"{MESH_LOSS_RTOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the trainer on a 2x2 mesh against one "
                         "device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX finds "
                 f"{len(devices)} device(s)")
    say("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), compile_cache=use_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(train_spec(), train_args(seed=args.seed),
                   train_args(mesh="2x2", seed=args.seed))
    else:
        engine_phase()
        serve_phase(get_arch("qwen2-1.5b"), seed=args.seed)
        train_phase(train_spec(), train_args(seed=args.seed))
    say("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
