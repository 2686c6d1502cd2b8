"""Read the numbers that decide ``correct`` for many seeds in one process:
for the program as it stands (the lower readings), for the control, and for
each planted fault (the upper readings).  The benchmark's own runs never
run this.

    python bench/checks.py --workload <name> --variant program --seeds 1,2,3
    python bench/checks.py --workload <name> --variant control --seeds 1,2,3
    python bench/checks.py --workload <name> --variant half_batch --seeds 1,2,3

Each seed drives a whole run of the cell (set-up, a short window of
``--seconds`` at the cell's own load, the comparison); the training
control is the float32 reference against itself with float8 matmul
operands.  Prints one JSON line per seed and, last, the largest reading of
each number.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, variants  # noqa: E402


def train_control(cell: harness.Cell) -> list[tuple[str, float, float]]:
    import jax
    import jax.numpy as jnp

    from bench import generate
    from bench.drivers import train
    from bench.reference import qwen2 as ref

    cfg, t = cell.config, cell.traffic
    opt = cfg["training"]["optimizer"]
    key = jax.random.PRNGKey(harness.seed32(cell.seed))
    batches = [generate.lm_batch(cell.seed, i, t["batch"], t["seq"],
                                 cfg["vocab_size"]) for i in range(train.WARM)]
    want = ref.train3(cfg, opt, key, batches)
    low = ref.train3(cfg, opt, key, batches, operand_dtype=jnp.float8_e4m3fn)
    checks, _ = train.compare_run(low["losses"], low["grad"], low["change"], want)
    return checks


def reading(cell: harness.Cell, variant: str, devices) -> dict:
    if variant == "control" and cell.config["driver"] == "train":
        return {"checks": train_control(cell)}
    patch = (variants.x64_off if variant == "control"
             else variants.PATCHES.get(variant, contextlib.nullcontext))
    tools = harness.Tools(time.perf_counter(), False,
                          harness.ROOT / ".bench_trace" / "checks")
    with patch():
        out = harness.load_driver(cell.config["driver"]).run(cell, tools, devices)
    return {"checks": out.checks, "correct": out.correct,
            "metrics": out.metrics, "notes": out.notes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", default="program",
                    choices=("program", "control", *variants.PATCHES))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    harness.import_program()
    probe = harness.find_cell(args.workload, 0, args.seconds, False)
    try:
        devices, _ = harness.accelerator(probe.chips)
    except harness.NoChip as e:
        print(f"checks: {e}", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    worst: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.find_cell(args.workload, seed, args.seconds, False)
        r = reading(cell, args.variant, devices)
        for name, v, _ in r["checks"]:
            worst[name] = max(worst.get(name, 0.0), v)
        print(json.dumps({"seed": seed, "variant": args.variant,
                          **{k: v for k, v in r.items() if k != "checks"},
                          "checks": {n: v for n, v, _ in r["checks"]}},
                         default=str), flush=True)
    print(json.dumps({"variant": args.variant, "workload": args.workload,
                      "largest": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
