"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a short tail after the window and from the benchmark's own spans.
The last line of standard output is one JSON object; the numbers that
decided ``correct`` are the last lines of standard error and the last key
of that object.  No accelerator, or fewer chips than the cell asks for: exit
code 1 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's trace to this directory")
    ap.add_argument("--record", default="",
                    help="write what the run observed (per-step or "
                         "per-generation times, set-up split, GC pauses) "
                         "to this JSON file")
    return ap.parse_args(argv)


def per_layer(cell: harness.Cell, out: harness.Outcome, reduced,
              peaks: dict) -> dict:
    """Each per-layer metric's reader, given what the run observed; a
    reader that finds nothing to read returns None and is left out."""
    ctx = {"cell": cell, "obs": out.obs, "metrics": out.metrics,
           "trace": reduced, "peaks": peaks}
    metrics = {}
    for m in cell.per_layer:
        value = harness.load_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.find_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    harness.import_program()
    try:
        devices, peaks = harness.accelerator(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    trace_dir = harness.ROOT / ".bench_trace" / cell.name
    tools = harness.Tools(T_START, cell.trace, trace_dir)
    driver = harness.load_driver(cell.config["driver"])
    out = driver.run(cell, tools, devices)

    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed}
    device = dict(out.device)
    if cell.trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir, len(devices),
                                          tools.tail_units)
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["metrics"] = per_layer(cell, out, reduced, peaks)
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = {m["name"]: {"value": out.metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    # a comparison that could not be made reads inf: kept as text, since
    # JSON has no infinity
    result["checks"] = {name: {"value": v if math.isfinite(v) else repr(v),
                               "limit": lim}
                        for name, v, lim in out.checks}
    if args.record:
        with open(args.record, "w") as f:
            json.dump({"workload": cell.name, "seed": cell.seed,
                       "metrics": out.metrics, "obs": out.obs,
                       "notes": out.notes, "checks": out.checks,
                       "result": result}, f, default=str)
    for k, v in out.notes.items():
        print(f"bench: {k} = {v}", file=sys.stderr)
    for name, v, lim in out.checks:
        print(f"check {name} = {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
