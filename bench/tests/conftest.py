"""Tiny cells for rehearsing the drivers on a CPU: the harness's look for
a chip is skipped and the rest of a run is driven."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 256}
TINY_TRAFFIC = {
    "stream256.p32": {"scenario": {"type": "request_stream", "n_requests": 8,
                                   "seq": 512, "decode_tokens": 8,
                                   "rate_rps": 32.0, "seed": 0,
                                   "max_batch": 32, "ttft_slo_ms": 4000.0,
                                   "tpot_slo_ms": 200.0},
                      # the 462-op trace of these 8 requests
                      "trace_sha256": "334e8f0631d4dc8df70c2b081121dd880f99e96274b58ed5a9f3aa9a886004e3",
                      "population": 4, "warmup_generations": 1,
                      "max_generations": 20000, "check_points": 6,
                      "trace_generations": 1},
    "train.p32": {"population": 4, "warmup_generations": 1,
                  "max_generations": 20000, "check_points": 6,
                  "trace_generations": 1},
    "b4s1024": {"batch": 2, "seq": 16, "trace_steps": 1},
}


def tiny_cell(workload: str, seed: int = 12345, seconds: float = 0.3,
              trace: bool = False) -> harness.Cell:
    """The committed cell with its traffic, and a training model, cut to a
    size a CPU test holds."""
    cell = harness.find_cell(workload, seed, seconds, trace)
    cell.traffic = {**copy.deepcopy(cell.traffic), **TINY_TRAFFIC[cell.workload["traffic"]]}
    if cell.config["driver"] == "train":
        cell.config = {**copy.deepcopy(cell.config), **TINY_MODEL}
    return cell


def run_cell(cell: harness.Cell, tmp_path: Path) -> harness.Outcome:
    import jax

    harness.import_program()
    tools = harness.Tools(time.perf_counter(), cell.trace, tmp_path / "trace")
    return harness.load_driver(cell.config["driver"]).run(
        cell, tools, jax.devices()[:cell.chips])


@pytest.fixture
def tiny():
    return tiny_cell
