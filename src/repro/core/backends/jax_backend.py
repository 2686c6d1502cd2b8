"""JaxBackend: one jit+vmap-compiled call per plan that prices every
op's duration for a whole population and sweeps the dependency DAG with
them.

The reference event loop is inherently sequential per design point.  This
backend lowers the shared ``_SimPlan`` into a fixed-structure longest-path
sweep that XLA compiles once per trace shape and ``vmap`` evaluates for a
whole agent population in a single call.

The lowering: under an issue-order schedule, each resource runs its ops in
uid order (a topological order by the ``TraceBuilder``/
``compose_request_waves`` contract), so ``free[resource]`` at op *i* is
exactly the finish time of the previous op on *i*'s resource.  That turns
the whole schedule into a max-plus longest-path recurrence over the DAG
augmented with per-resource chain edges::

    finish[i] = dur[i] + max(finish[j] for j in deps[i] + {prev_on_res[i]})

The augmented-parent table is static per trace (built once, piggybacked on
the plan).  The per-design-point durations are the ONLY population-varying
input: ``simulator.plan_duration_tables`` packs the whole population's
collective dim tables + roofline coefficients host-side (memoized per
design-point key), and one jit-compiled function per plan prices every
duration class x population member with the vectorized collective
evaluator (``collectives.multidim_collective_time_vec``) and feeds the
durations straight into the scheduling sweep — no host round-trip between
pricing and scheduling.  The same call reduces on the device what the host
reads: each member's makespan, its busy time per resource, and the finish
times of the trace's marked uids (``workload.wave_mark_uids``) where a call
records finish times — a (1 + n_res + k, P) array instead of two
(n_ops, P) matrices.  The full duration and finish matrices come back only
for a batch with a ``record_per_op`` call, or with a ``record_finish`` call
on a trace that marks no waves.

On a TPU the sweep is one Pallas call (``_sweep_pairs``).  Pallas on the
chip has no float64, and XLA's float64 there is already pairs of float32,
so the kernel carries each finish time as a float32 (hi, lo) pair: the
durations are split on the device, the max over an op's parents compares
hi and then lo (exact on normalized pairs), and the add is the two-sum
double-float add.  It sweeps blocks of ops, each grid step reading its
durations from HBM and its parents' ring rows from SMEM, and keeps the
last block's worth of finish rows and a pinned zero row (the padding
parent) in VMEM.  That needs every parent within a block of its op: the
block is at least the plan's largest parent distance (233 ops on the
25,872-op stream trace), and where that block and the population would
not fit the kernel's memory the float64 ``fori_loop`` runs instead.  The
CPU always runs the loop: float64 is native there, and the loop matches
the reference bit for bit.  The two paths are chosen by platform because
neither serves both (``sweep_kernel_fits``).  Against the float64 loop the
pairs deviate by at most 7.2e-13 (the stream trace's finish times, CPU
interpret mode).  The counters ``repro.engine.sweep_ops`` and
``repro.engine.sweep_kernel_ops`` hold the ops each call swept, and of
those the ones the kernel swept.

Every ``simulate_batch`` runs under the spans of ``repro.runtime.spans``:
``repro.engine.pack`` (the memoized table packing),
``repro.engine.dispatch`` (the compiled call returning),
``repro.engine.device_wait`` (the outputs ready),
``repro.engine.copy_back`` (to host numpy; its bytes are the counter
``repro.engine.copy_back_bytes``) and ``repro.engine.assemble`` (the
``SimResult``s).  ``last_timings`` keeps the split of the most recent
call, from the same span durations: ``durations_s`` is the pack,
``sweep_s`` the dispatch, wait and copy back.  On the device the call's
operations sit under the named scopes ``repro.engine.price``,
``repro.engine.sweep`` and ``repro.engine.reduce``.

Fidelity: each resource serializes its ops in issue order instead of the
reference loop's arrival-order (FIFO) / freshest-first (LIFO) queue
discipline, so makespans can deviate where a resource's queue reorders —
parity tests pin the tolerance (exact on every trace family shipped:
per-resource ready order follows issue order there).  Use the reference
backend when bit-exact schedules matter; use this one to sweep large
populations over large traces.
"""
from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Any, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.simulator import (SimResult, SystemConfig, _SimPlan,
                                  batch_op_durations, build_sim_result,
                                  plan_duration_tables)
from repro.core.workload import Parallelism, Trace, wave_mark_uids
from repro.runtime import spans


@jax.jit
def _sweep_population(dur_t: jnp.ndarray,
                      parents_pad: jnp.ndarray) -> jnp.ndarray:
    """Finish time of every op for every population member.

    ``dur_t`` is (n_ops, P) — population on the trailing axis so the
    vmapped carry writes whole contiguous rows; ``parents_pad`` (n_ops, D)
    holds each op's augmented parents (deps + same-resource predecessor)
    padded with ``n_ops``, a dummy slot pinned to finish 0.  Returns
    (n_ops + 1, P) finish times (the dummy row last)."""
    n_ops = dur_t.shape[0]

    def one(d: jnp.ndarray) -> jnp.ndarray:
        def body(i, finish):
            fin = finish[parents_pad[i]].max() + d[i]
            return finish.at[i].set(fin)

        # modest unroll amortizes the while-loop dispatch overhead that
        # dominates this intrinsically sequential recurrence (~12% on the
        # 26k-op request-stream trace; measured 4/8/16/32, 16 is best)
        return lax.fori_loop(0, n_ops, body, jnp.zeros(n_ops + 1, d.dtype),
                             unroll=16)

    return jax.vmap(one, in_axes=1, out_axes=1)(dur_t)


# The sweep on a TPU: a Pallas kernel over blocks of ops (see the module
# docstring).  A grid step sweeps at least SWEEP_BLOCK ops; the kernel runs
# where its buffers fit SWEEP_VMEM_BUDGET bytes of VMEM and a block's parent
# slots SWEEP_SMEM_WORDS int32 words of SMEM.
SWEEP_BLOCK = 512
SWEEP_VMEM_BUDGET = 8 << 20
SWEEP_SMEM_WORDS = 4096
# ops per iteration of the kernel's loop (Mosaic unrolls no loop itself)
SWEEP_UNROLL = 8


class SweepLayout(NamedTuple):
    """The kernel's static shape for one plan: ``block`` ops a grid step
    (a multiple of 8, at least the plan's parent distance), ``stride``
    parent slots an op in the flattened table (a power of two at least
    ``depth``) and ``depth`` parents an op reads."""

    block: int
    stride: int
    depth: int


def _sweep_window(parents: np.ndarray) -> int:
    """The largest distance from an op back to one of its augmented
    parents (0 where no op has one)."""
    n = len(parents)
    real = parents < n
    if not real.any():
        return 0
    return int((np.arange(n)[:, None] - parents)[real].max())


def sweep_layout(n_ops: int, depth: int, window: int) -> SweepLayout:
    """The kernel's layout for a plan of ``n_ops`` ops whose ops read
    ``depth`` parents at most ``window`` ops back.  XLA tiles a 1-D int32
    operand by 1,024, so a block's parent slots are a multiple of it."""
    stride = 1 << max(depth - 1, 0).bit_length()
    unit = max(8, 1024 // stride)
    block = -(-max(window, min(n_ops, SWEEP_BLOCK), 1) // unit) * unit
    return SweepLayout(block, stride, depth)


def sweep_kernel_fits(platform: str, layout: SweepLayout, p: int) -> bool:
    """Whether the Pallas sweep runs: on a TPU only (the CPU keeps the
    native float64 loop, bit-exact with the reference), and only where a
    population of ``p`` fits the kernel's memory — its ring of a block of
    (hi, lo) rows and the double-buffered blocks in and out, every row
    padded to whole 128-lane vectors."""
    lanes = -(-p // 128) * 128
    vmem = 4 * lanes * (10 * layout.block + 16)
    return (platform == "tpu" and vmem <= SWEEP_VMEM_BUDGET
            and layout.block * layout.stride <= SWEEP_SMEM_WORDS)


def _ring_parents(parents: np.ndarray, layout: SweepLayout) -> np.ndarray:
    """The parents table as the kernel reads it: each parent's row in the
    ring of ``block`` finish rows (op ``j`` at ``j mod block``), the
    padding parent at the pinned zero row ``block``; ``stride`` slots an
    op, whole blocks of ops, flattened to 1-D (SMEM pads the last
    dimension of a 2-D block to 128 words)."""
    n, depth = parents.shape
    b = layout.block
    n_pad = -(-max(n, 1) // b) * b
    ring = np.full((n_pad, layout.stride), b, dtype=np.int32)
    ring[:n, :depth] = np.where(parents < n, parents % b, b)
    return ring.reshape(-1)


def _two_sum(a, b):
    """``a + b`` as a float and its exact rounding error."""
    s = a + b
    v = s - a
    return s, (a - (s - v)) + (b - v)


def _fast_two_sum(a, b):
    """``_two_sum`` where ``|a| >= |b|``."""
    s = a + b
    return s, b - (s - a)


def _pair_add(ahi, alo, bhi, blo):
    """The sum of two float32 (hi, lo) pairs, normalized: the two-sums of
    both halves, each error folded in and renormalized."""
    s, e = _two_sum(ahi, bhi)
    t, f = _two_sum(alo, blo)
    s, e = _fast_two_sum(s, e + t)
    return _fast_two_sum(s, e + f)


def _sweep_kernel(par_ref, dhi_ref, dlo_ref, fhi_ref, flo_ref, ring_hi,
                  ring_lo, *, layout: SweepLayout):
    """One grid step: the finish rows of a block of ops.  ``ring_hi`` and
    ``ring_lo`` hold the last ``block`` ops' finish rows (op ``j`` at row
    ``j mod block``) and the zero row of the padding parent.  Every parent
    lies within ``block`` ops of its op, so its row is not yet overwritten
    when the op reads it."""
    block, stride, depth = layout

    @pl.when(pl.program_id(0) == 0)
    def _():
        zero = jnp.zeros((8, ring_hi.shape[1]), jnp.float32)
        ring_hi[pl.ds(block, 8), :] = zero
        ring_lo[pl.ds(block, 8), :] = zero

    def op(j):
        k = j * stride
        p = par_ref[k]
        hi, lo = ring_hi[pl.ds(p, 1), :], ring_lo[pl.ds(p, 1), :]
        for d in range(1, depth):
            p = par_ref[k + d]
            h, l = ring_hi[pl.ds(p, 1), :], ring_lo[pl.ds(p, 1), :]
            # the later of two normalized pairs: hi first, then lo
            take = (h > hi) | ((h == hi) & (l > lo))
            hi, lo = jnp.where(take, h, hi), jnp.where(take, l, lo)
        hi, lo = _pair_add(hi, lo, dhi_ref[pl.ds(j, 1), :],
                           dlo_ref[pl.ds(j, 1), :])
        ring_hi[pl.ds(j, 1), :] = hi
        ring_lo[pl.ds(j, 1), :] = lo

    def body(g, carry):
        for u in range(SWEEP_UNROLL):
            op(g * SWEEP_UNROLL + u)
        return carry

    # int32 bounds: the call runs under x64, and Mosaic takes no int64
    lax.fori_loop(jnp.int32(0), jnp.int32(block // SWEEP_UNROLL), body,
                  jnp.int32(0))
    fhi_ref[...] = ring_hi[:block, :]
    flo_ref[...] = ring_lo[:block, :]


def _sweep_pairs(dur_t: jnp.ndarray, ring_parents: jnp.ndarray,
                 layout: SweepLayout, interpret: bool = False) -> jnp.ndarray:
    """``_sweep_population`` as one Pallas call on float32 (hi, lo) pairs:
    the durations split on the device, swept block by block, and the
    finish times recombined into ``dur_t``'s dtype — (n_ops + 1, P), the
    dummy row last.  ``ring_parents`` is ``_ring_parents``'s table."""
    n, p = dur_t.shape
    block = layout.block
    n_pad = ring_parents.shape[0] // layout.stride
    hi = dur_t.astype(jnp.float32)
    lo = (dur_t - hi.astype(dur_t.dtype)).astype(jnp.float32)
    pad = ((0, n_pad - n), (0, 0))
    rows = pl.BlockSpec((block, p), lambda b: (b, jnp.int32(0)))
    fhi, flo = pl.pallas_call(
        functools.partial(_sweep_kernel, layout=layout),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((block * layout.stride,), lambda b: (b,),
                               memory_space=pltpu.SMEM), rows, rows],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct((n_pad, p), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block + 8, p), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ring_parents, jnp.pad(hi, pad), jnp.pad(lo, pad))
    finish = fhi[:n].astype(dur_t.dtype) + flo[:n].astype(dur_t.dtype)
    return jnp.concatenate([finish, jnp.zeros((1, p), dur_t.dtype)])


def _plan_parents(trace: Trace, plan: _SimPlan) -> np.ndarray:
    """The plan's augmented-parent table, built once and piggybacked on the
    plan (plans are piggybacked on cached immutable traces)."""
    cached = getattr(plan, "_jax_parents", None)
    if cached is not None:
        return cached
    n = plan.n_ops
    last_on_res: dict[int, int] = {}
    rows: list[list[int]] = []
    for op in trace.ops:
        if any(d >= op.uid for d in op.deps):
            # the sweep reads parents' finish times in uid order; a forward
            # dep would silently read 0 where the reference loop deadlocks
            raise ValueError(f"op {op.uid} depends on a later op — the jax "
                             f"backend needs topologically-ordered uids "
                             f"(TraceBuilder/compose_request_waves traces)")
        r = plan.res_of[op.uid]
        row = list(op.deps)
        prev = last_on_res.get(r)
        if prev is not None:
            row.append(prev)
        last_on_res[r] = op.uid
        rows.append(row)
    width = max((len(row) for row in rows), default=0)
    parents = np.full((n, max(width, 1)), n, dtype=np.int32)
    for i, row in enumerate(rows):
        parents[i, :len(row)] = row
    plan._jax_parents = parents
    return parents


# ops per partial sum of the busy accounting (see ``_busy_chunks``)
BUSY_CHUNK = 32


def _busy_chunks(plan: _SimPlan) -> tuple[np.ndarray, np.ndarray]:
    """Static gather indices for the busy sums: each resource's ops in uid
    order, cut into rows of ``BUSY_CHUNK`` padded with ``n_ops`` (a zero
    row), and each row's resource (sorted).  Summing each row on the device
    and then scattering only the rows per resource is far cheaper on the
    TPU than scattering every op: on one TPU v5e, over a 25,872-op
    request-stream trace and 32 members, a sorted ``segment_sum`` of every
    op added 2.2 ms to a 41.9 ms call, the row sums 0.2 ms (rows of 16 to
    128 ops alike)."""
    n, c = plan.n_ops, BUSY_CHUNK
    res_of = np.asarray(plan.res_of, dtype=np.intp)
    counts = np.bincount(res_of, minlength=len(plan.res_names))
    rows = -(-counts // c)
    # each op's rank within its resource, in uid order
    order = np.argsort(res_of, kind="stable")
    rank = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
    row = np.repeat(np.cumsum(rows) - rows, counts) + rank // c
    chunks = np.full((int(rows.sum()), c), n, dtype=np.int32)
    chunks[row, rank % c] = order
    return chunks, np.repeat(np.arange(len(counts), dtype=np.int32), rows)


def _plan_statics(trace: Trace, plan: _SimPlan,
                  sweep: SweepLayout | None) -> tuple[jnp.ndarray, ...]:
    """The fused call's static per-plan inputs, on the device: the sweep's
    table — the augmented-parent table for the loop, its ring rows
    (``_ring_parents``) for the kernel's ``sweep`` layout — and the busy
    sums' row indices (``_busy_chunks``).  Uploaded once and kept on the
    plan — re-uploading them every batch costs more than the entire
    class-table pack."""
    key = ("_statics_dev", sweep)
    statics = plan.pack_memo.get(key)
    if statics is None:
        parents = _plan_parents(trace, plan)
        statics = plan.pack_memo[key] = tuple(jnp.asarray(a) for a in (
            parents if sweep is None else _ring_parents(parents, sweep),
            *_busy_chunks(plan)))
    return statics


def _plan_sweep(trace: Trace, plan: _SimPlan, p: int) -> SweepLayout | None:
    """The kernel's layout where the sweep of a population of ``p`` runs as
    the Pallas kernel here, else None (the loop runs).  The layout comes
    from the parents table and is kept on the plan."""
    layout = plan.pack_memo.get("_sweep_layout")
    if layout is None:
        parents = _plan_parents(trace, plan)
        layout = plan.pack_memo["_sweep_layout"] = sweep_layout(
            plan.n_ops, parents.shape[1], _sweep_window(parents))
    return layout if sweep_kernel_fits(jax.default_backend(), layout, p) else None


def _plan_marks(trace: Trace, plan: _SimPlan) -> tuple[np.ndarray, jnp.ndarray]:
    """The trace's marked uids (``workload.wave_mark_uids``), on the host
    and on the device, kept on the plan."""
    marks = plan.pack_memo.get("_marks")
    if marks is None:
        uids = wave_mark_uids(trace)
        marks = plan.pack_memo["_marks"] = (uids, jnp.asarray(uids.astype(np.int32)))
    return marks


def _fused_eval(plan: _SimPlan, full: bool, sweep: SweepLayout | None = None):
    """The per-plan fused kernel: population duration tables in, one
    (1 + n_res + k, P) array out — each member's makespan, its busy time
    per resource, and the finish times of the ``k`` uids asked for (none
    where ``uids`` is None) — all from one jit-compiled call.  ``full``
    also returns every op's duration and finish time (op-major, the finish
    matrix with its dummy row last).  With a ``sweep`` layout the sweep is
    the Pallas kernel, and ``parents`` is its ring table
    (``_plan_statics``); without, the float64 loop.

    Compiled per plan (the plan's scatter index arrays are closure
    constants, so the function identity must be plan-specific) and cached
    on it; XLA re-specializes per (population size, padded dim count, k) —
    all stable across the generations of a search."""
    key = ("_fused_full" if full else "_fused", sweep)
    fn = plan.pack_memo.get(key)
    if fn is None:
        n_ops, n_res = plan.n_ops, len(plan.res_names)

        def fused(tables, parents, chunks, chunk_res, uids):
            # op-major durations feed the sweep with contiguous per-op rows
            # (the loop body reads one row per step)
            with jax.named_scope("repro.engine.price"):
                dur_t = batch_op_durations(plan, tables, xp=jnp, op_major=True)
            with jax.named_scope("repro.engine.sweep"):
                finish = (_sweep_population(dur_t, parents) if sweep is None
                          else _sweep_pairs(dur_t, parents, sweep))
            with jax.named_scope("repro.engine.reduce"):
                p = dur_t.shape[1]
                makespan = (finish[:n_ops].max(axis=0, keepdims=True) if n_ops
                            else jnp.zeros((1, p), finish.dtype))
                zero = jnp.zeros((1, p), dur_t.dtype)
                part = jnp.concatenate([dur_t, zero])[chunks].sum(axis=1)
                busy = jax.ops.segment_sum(part, chunk_res, num_segments=n_res,
                                           indices_are_sorted=True)
                rows = [makespan, busy] + ([] if uids is None else [finish[uids]])
                out = jnp.concatenate(rows)
            return (out, dur_t, finish) if full else out
        fn = plan.pack_memo[key] = jax.jit(fused)
    return fn


class FinishTimes(Mapping):
    """``SimResult.op_finish_us`` backed by one member's finish times from
    the sweep — dict semantics (uid -> finish time) without materializing
    tens of thousands of boxed floats per design point.  ``uids`` (sorted)
    names the ops ``row`` holds; None means every op, in uid order.
    Scenarios read the wave times through ``take``."""

    __slots__ = ("_row", "_uids")

    def __init__(self, row: np.ndarray, uids: np.ndarray | None = None) -> None:
        self._row, self._uids = row, uids

    def take(self, uids: np.ndarray) -> np.ndarray:
        """The finish times of ``uids``, as one array (a positional
        gather); KeyError for a uid this mapping does not hold."""
        uids = np.asarray(uids, dtype=np.intp)
        if self._uids is None:
            pos, held = uids, (uids >= 0) & (uids < len(self._row))
        else:
            pos = np.searchsorted(self._uids, uids)
            held = pos < len(self._uids)
            held[held] = self._uids[pos[held]] == uids[held]
        if not held.all():
            # dict semantics, not array semantics: never wrap negatively
            raise KeyError(int(uids[~held][0]))
        return self._row[pos]

    def __getitem__(self, uid: int) -> float:
        return float(self.take([uid])[0])

    def __len__(self) -> int:
        return len(self._row)

    def __iter__(self):
        return iter(range(len(self._row)) if self._uids is None
                    else self._uids.tolist())


class JaxBackend:
    """Population-vectorized scheduling: durations priced inside the same
    compiled call as the sweep (registered as ``jax``)."""

    name = "jax"
    vectorized = True

    def __init__(self) -> None:
        # pack vs compiled-evaluation wall-time split of the most recent
        # simulate_batch, from its spans (see module docstring)
        self.last_timings: dict[str, float] = {}

    def simulate(self, trace: Trace, cfg: SystemConfig, par: Parallelism, *,
                 pools: dict[int, Any] | None = None,
                 record_per_op: bool = False,
                 record_finish: bool = False) -> SimResult:
        from repro.core.backends.base import SimCall

        return self.simulate_batch(
            trace, [SimCall(trace, cfg, par, pools=pools,
                            record_per_op=record_per_op,
                            record_finish=record_finish)])[0]

    def simulate_batch(self, trace: Trace,
                       calls: Sequence[Any]) -> list[SimResult]:
        if not calls:
            return []
        # the full matrices only where a call asks for every op, or for
        # finish times on a trace that marks no waves; otherwise the
        # makespan, the busy time and the marked uids' finish times
        wants = any(c.record_finish for c in calls)
        marked = wants and bool(trace.meta.get("wave_marks"))
        full = any(c.record_per_op for c in calls) or (wants and not marked)
        with spans.span("repro.engine.pack") as pack:
            plan, tables = plan_duration_tables(trace, calls)
        sweep = _plan_sweep(trace, plan, len(calls))
        spans.count("repro.engine.sweep_ops", plan.n_ops)
        spans.count("repro.engine.sweep_kernel_ops",
                    0 if sweep is None else plan.n_ops)
        # double precision scoped to the sweep (the global default stays
        # f32 for the model and kernel code paths)
        with jax.enable_x64(True):
            with spans.span("repro.engine.dispatch") as dispatch:
                uids, uids_d = (_plan_marks(trace, plan)
                                if marked and not full else (None, None))
                got = _fused_eval(plan, full, sweep)(
                    tables, *_plan_statics(trace, plan, sweep), uids_d)
            with spans.span("repro.engine.device_wait") as wait:
                jax.block_until_ready(got)
            with spans.span("repro.engine.copy_back") as copy:
                host = [np.asarray(a) for a in (got if full else (got,))]
        spans.count("repro.engine.copy_back_bytes", sum(a.nbytes for a in host))
        self.last_timings = {
            "durations_s": pack.seconds,
            "sweep_s": dispatch.seconds + wait.seconds + copy.seconds}
        with spans.span("repro.engine.assemble"):
            n_res = len(plan.res_names)
            # (P, 1 + n_res + k) views: makespan, busy, marked finish
            cols = host[0].T
            makespan, busy2d = cols[:, 0], cols[:, 1:1 + n_res]
            if full:
                dur = host[1].T                          # (P, n_ops) views
                finish = host[2][:plan.n_ops].T
            out: list[SimResult] = []
            for k, call in enumerate(calls):
                fin: Mapping = {}
                if call.record_per_op or call.record_finish:
                    fin = (FinishTimes(finish[k]) if full
                           else FinishTimes(cols[k, 1 + n_res:], uids))
                out.append(build_sim_result(
                    plan, makespan=float(makespan[k]), busy=busy2d[k].tolist(),
                    dur=dur[k] if full else (), finish=fin,
                    record_per_op=call.record_per_op))
        return out
