"""Training cells over a mesh of several chips: ``launch/train.train_loop``
with ``--mesh`` (``data`` x ``model``), the program's own sharding plan and
its step compiled once by ``build``.

The run is ``bench/drivers/train.py``'s ``run``, called as it is: the same
batches, initial weights, window, capture and the same three steps
compared.  What differs is patched in around it:

* the cell's chips must all be there;
* the reference is ``bench/reference/qwen2_spread.py``: float32 weights,
  gradients and moments of the whole model do not fit one chip, so they are
  spread over the cell's chips;
* the limits are this driver's (``LIMITS``);
* ``obs`` holds each chip's memory peak, read before the reference runs
  (the result's ``memory_peak_bytes`` is the largest), and the bytes the
  compiled step's collectives move in one step, by kind, as the program
  counts them (left out where the program's step counts none).
"""
from __future__ import annotations

from bench import harness
from bench.drivers import train
from bench.reference import qwen2_spread

# Limits, under the names ``train.compare_run`` reads them by, each between
# the largest reading of sound runs (bf16 program over the mesh against the
# float32 reference over the same chips, 6 seeds) and the smallest of the
# float8 control, the half batch and the no-exchange fault (readings in
# PERF.md, one four-chip TPU v5e host, zoo-qwen2-1.5b-28L).
LIMITS = {
    # sound 1.4e-5 .. 5.5e-5; no exchange 1.3e-4 .. 2.9e-4, half batch
    # 2.1e-4 .. 6.4e-4, control 9.8e-4: the 8-layer cell's limit holds
    "LOSS_RTOL": train.LOSS_RTOL,
    # sound 3.1e-3 .. 8.9e-3; half batch 0.21, no exchange 0.21 .. 0.22,
    # control 4.2: the 8-layer cell's limit holds, 2.3 times above the sound
    # runs
    "GRAD_GAP": train.GRAD_GAP,
    # sound 6.9e-4 .. 1.06e-3; no exchange 0.023 .. 0.034, half batch
    # 0.028 .. 0.037, control 0.997: 9.4 times above the sound runs, 2.3
    # times under the faults (the 8-layer cell's 5e-2 lies above both)
    "CHANGE_GAP": 1e-2,
}


def compare_run(losses, grad, change, want) -> tuple[list, dict]:
    """``train.compare_run``'s numbers, each beside this driver's limit."""
    with train.patched(train, **LIMITS):
        return train.compare_run(losses, grad, change, want)


def run(cell: harness.Cell, tools: harness.Tools, devices) -> harness.Outcome:
    from types import SimpleNamespace

    from repro.launch import train as T

    if len(devices) < cell.chips:
        raise harness.NoChip(f"the cell asks for {cell.chips} chips; "
                             f"it was given {len(devices)}")
    peaks, built, base_build = [], [], T.build

    def build(*a, **kw):
        out = base_build(*a, **kw)
        built.append(out[0])
        return out

    def train3(cfg, opt, key, batches):
        # the program's peaks, before the reference's arrays add to them
        peaks.extend(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
        return qwen2_spread.train3(cfg, opt, key, batches, devices)

    ref = SimpleNamespace(**{**vars(train.ref), "train3": train3})
    with train.patched(T, build=build), \
            train.patched(train, ref=ref, **LIMITS):
        out = train.run(cell, tools, devices)

    obs = {"memory_peak_bytes_per_device": peaks}
    # a program whose step counts no collectives leaves them out
    coll = getattr(built[0], "collectives", None)
    if coll is not None:
        obs.update(collective_bytes_per_step=coll.total_collective_bytes(),
                   collective_bytes_by_kind=dict(coll.collective_bytes),
                   collective_count_by_kind=dict(coll.collective_counts))
    out.obs.update(obs)
    out.notes.update({k: obs[k] for k in ("memory_peak_bytes_per_device",
                                          "collective_bytes_per_step",
                                          "collective_count_by_kind") if k in obs})
    return out
