"""End-to-end training driver.

Wires together: config registry (--arch), synthetic data pipeline with
prefetch, sharded train step (any mesh), async atomic checkpointing with
auto-resume, heartbeats, straggler monitoring, and failure injection for
fault-tolerance drills.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import contextlib
import functools
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ARCHS, get_arch, reduced
from repro.ckpt.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.core.hlo_analysis import analyze_compiled_text
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import NULL_PLAN, plan_for_mesh, tree_shardings
from repro.runtime import spans
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.fault import Heartbeat, StragglerMonitor
from repro.train import optimizer as opt
from repro.train.train_step import (RunConfig, batch_abstract, batch_axes,
                                    init_train_state, make_train_step,
                                    train_state_axes)


class CompiledStep:
    """The step compiled once, ahead of the loop, for a mesh.  Each call
    runs that executable and counts, in the open ``repro.train.step`` row,
    the bytes its collectives move: ``collectives`` holds the compiled HLO's
    totals by kind (``core/hlo_analysis``, through the layer scan's trip
    count), each collective's output bytes on one device.  The all-to-alls'
    share is counted on its own as well."""

    def __init__(self, compiled) -> None:
        self.compiled = compiled
        self.collectives = analyze_compiled_text(compiled.as_text())
        self.collective_bytes = self.collectives.total_collective_bytes()
        self.all_to_all_bytes = self.collectives.collective_bytes.get(
            "all-to-all", 0.0)

    def __call__(self, state, batch):
        spans.count("repro.train.collective_bytes", self.collective_bytes)
        spans.count("repro.train.all_to_all_bytes", self.all_to_all_bytes)
        return self.compiled(state, batch)


def build(spec, mesh, cfg: RunConfig, seed: int = 0, batch=None):
    """The step, the initial state and the batch shardings for ``mesh``
    (None: the default device, a jitted step, and shardings None).  The
    state is initialised inside one jitted call whose outputs are already
    sharded, so no device ever holds more than its own shards of it.  Under
    a mesh the step is a ``CompiledStep``, lowered for the abstract
    ``batch`` (``batch_abstract``'s tree) and compiled here."""
    plan = plan_for_mesh(mesh) if mesh is not None else NULL_PLAN
    step_fn = make_train_step(spec, plan, cfg)
    init = functools.partial(init_train_state, spec=spec, cfg=cfg)
    rng = jax.random.PRNGKey(seed)
    state_sh = batch_sh = None
    if mesh is not None:
        ax = train_state_axes(spec, cfg)
        specs = jax.tree.map(lambda a, s: plan.spec(a, s.shape), ax,
                             jax.eval_shape(init, rng),
                             is_leaf=lambda x: isinstance(x, tuple) and all(
                                 isinstance(e, (str, type(None))) for e in x))
        state_sh = tree_shardings(mesh, specs)
        batch_sh = {k: NamedSharding(mesh, plan.spec(a))
                    for k, a in batch_axes(spec).items()}
    state = jax.jit(init, out_shardings=state_sh)(rng)
    step = jax.jit(step_fn, donate_argnums=(0,))
    if mesh is not None:
        laid = {k: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=batch_sh[k])
                for k, a in batch.items()}
        step = CompiledStep(step.lower(state, laid).compile())
    return step, state, batch_sh


@dataclass
class TrainRun:
    """What ``train_loop`` leaves behind: the step it stopped at, the loss
    of every step it ran, and the final state.  Each step is a
    ``repro.train.step`` unit of ``repro.runtime.spans``: its row holds the
    step's wall seconds (the first includes compilation) and the split."""
    final: int
    losses: list[float]
    state: Any


def train_loop(args, spec, fail_at: int | None = None) -> TrainRun:
    cfg = RunConfig(
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        param_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=args.remat, microbatches=args.microbatches,
        opt=opt.OptConfig(lr=args.lr, warmup_steps=args.warmup),
    )
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model")[: len(shape)]
        mesh = make_mesh(shape, names)

    # the model's sharding constraints name mesh axes: they resolve
    # against the mesh in scope while the step is traced
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        # the pipeline's batches: int32 token ids, or float32 embeddings
        jit_step, state, batch_sh = build(
            spec, mesh, cfg, args.seed,
            batch=batch_abstract(spec, args.batch, args.seq, jnp.float32))

        ckpt = AsyncCheckpointer(args.ckpt_dir, keep=2) if args.ckpt_dir else None
        start = 0
        if ckpt and latest_step(args.ckpt_dir) is not None:
            # back into the layout the step was compiled for
            state, start = restore(args.ckpt_dir, state, shardings=jax.tree.map(
                lambda a: a.sharding, state) if mesh is not None else None)
            print(f"[train] resumed from step {start}", flush=True)

        data = SyntheticLM(spec, DataConfig(args.batch, args.seq, seed=args.seed))
        prefetch = Prefetcher(data, start_step=start, depth=2)
        hb = Heartbeat(Path(args.ckpt_dir) / "heartbeat.json") if args.ckpt_dir else None
        straggler = StragglerMonitor(k_sigma=args.straggler_sigma)

        losses: list[float] = []
        it = iter(prefetch)
        step = start - 1
        try:
            while True:
                with spans.unit("repro.train.step") as unit:
                    with spans.span("repro.train.input"):
                        item = next(it, None)
                    if item is None or item[0] >= args.steps:
                        unit.drop()
                        break
                    step, batch = item
                    if fail_at is not None and step == fail_at:
                        raise RuntimeError(f"injected failure at step {step}")
                    with spans.span("repro.train.put"):
                        batch = jax.device_put(batch, batch_sh)
                    with spans.span("repro.train.dispatch"):
                        state, metrics = jit_step(state, batch)
                    with spans.span("repro.train.loss_sync"):
                        loss = float(metrics["loss"])
                    losses.append(loss)
                    with spans.span("repro.train.bookkeeping"):
                        dt = unit.elapsed()
                        if straggler.observe(step, dt):
                            print(f"[straggler] step {step} took {dt:.3f}s "
                                  f"(mean {straggler.mean:.3f}s) — mitigation "
                                  f"hook fired", flush=True)
                        if hb:
                            hb.beat(step)
                        if ckpt and (step + 1) % args.ckpt_every == 0:
                            ckpt.save(state, step + 1)
                        if step % args.log_every == 0:
                            print(f"[train] step {step} loss {loss:.4f} "
                                  f"({dt*1e3:.0f} ms)", flush=True)
            final = min(args.steps, step + 1)
        finally:
            prefetch.close()
        if ckpt:
            ckpt.save(state, final, block=True)
        print(f"[train] done at step {final}; loss {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)
        return TrainRun(final, losses, state)


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="", help="e.g. 2x2 (requires host devices)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--straggler-sigma", type=float, default=3.0)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (fault drill)")
    return ap.parse_args(argv)


def main() -> None:
    args = parse_args()
    use_compile_cache()
    spec = get_arch(args.arch)
    if args.reduced:
        spec = reduced(spec)
    train_loop(args, spec, fail_at=args.fail_at)


if __name__ == "__main__":
    main()
