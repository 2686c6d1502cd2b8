"""JaxBackend: a jit+vmap-compiled levelized sweep over the dependency DAG,
optionally fused with the batched duration pass into one compiled call.

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
input, and they come in two flavours:

  * FUSED (default): ``simulator.plan_duration_tables`` packs the whole
    population's collective dim tables + roofline coefficients host-side
    (memoized per design-point key), and one jit-compiled function per plan
    prices every duration class x population member with the vectorized
    collective evaluator (``collectives.multidim_collective_time_vec``) and
    feeds the durations straight into the scheduling sweep — no host
    round-trip between pricing and scheduling.
  * UNFUSED (``JaxBackend(fused=False)``, registered as ``jax-unfused``):
    the scalar per-call duration pass (vectorized roofline + memoized
    scalar collective model via ``simulator.plan_durations``) feeding the
    compiled sweep — the pre-fusion behaviour, kept as the measurable
    baseline for the duration-pass-vs-sweep time split.

Every ``simulate_batch`` runs under the spans of ``repro.runtime.spans``:
``repro.engine.pack`` (the host-side duration pass: the scalar loop when
unfused, the memoized table packing when fused), ``repro.engine.dispatch``
(the compiled call returning), ``repro.engine.device_wait`` (both outputs
ready), ``repro.engine.copy_back`` (to host numpy) and
``repro.engine.assemble`` (busy accounting and the ``SimResult``s).
``last_timings`` keeps the split of the most recent call, from the same
span durations: ``durations_s`` is the pack, ``sweep_s`` the dispatch,
wait and copy back (pricing + sweep together when fused).  On the device
the fused call's operations sit under the named scopes
``repro.engine.price`` and ``repro.engine.sweep``.

Fidelity: each resource serializes its ops in issue order instead of the
reference loop's arrival-order (FIFO) / freshest-first (LIFO) queue
discipline, so makespans can deviate where a resource's queue reorders —
parity tests pin the tolerance (exact on every trace family shipped:
per-resource ready order follows issue order there).  Use the reference
backend when bit-exact schedules matter; use this one to sweep large
populations over large traces.
"""
from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.simulator import (SimResult, SystemConfig, _SimPlan,
                                  batch_op_durations, build_sim_result,
                                  plan_duration_tables, plan_durations)
from repro.core.workload import Parallelism, Trace
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


def _fused_eval(plan: _SimPlan):
    """The per-plan fused kernel: population duration tables in, per-op
    durations AND finish times out, one jit-compiled call.

    Compiled per plan (the plan's scatter index arrays are closure
    constants, so the function identity must be plan-specific) and cached
    on it; XLA re-specializes per (population size, padded dim count) —
    both stable across the generations of a search."""
    fn = plan.pack_memo.get("_fused")
    if fn is None:
        def fused(tables, parents):
            # op-major durations feed the sweep with contiguous per-op rows
            # (the loop body reads one row per step) and ship to host
            # without a transpose — busy accounting scatters op-major too
            with jax.named_scope("repro.engine.price"):
                dur_t = batch_op_durations(plan, tables, xp=jnp, op_major=True)
            with jax.named_scope("repro.engine.sweep"):
                return dur_t, _sweep_population(dur_t, parents)
        fn = plan.pack_memo["_fused"] = jax.jit(fused)
    return fn


class FinishTimes(Mapping):
    """``SimResult.op_finish_us`` backed by the sweep's finish row — dict
    semantics (uid -> finish time) without materializing tens of thousands
    of boxed floats per design point; scenarios only read the wave-mark
    uids off it."""

    __slots__ = ("_row",)

    def __init__(self, row: np.ndarray) -> None:
        self._row = row

    def __getitem__(self, uid: int) -> float:
        # dict semantics, not array semantics: unknown uids must raise
        # KeyError (so `in`/`.get()` work) and never wrap negatively
        if not 0 <= uid < len(self._row):
            raise KeyError(uid)
        return float(self._row[uid])

    def __len__(self) -> int:
        return len(self._row)

    def __iter__(self):
        return iter(range(len(self._row)))


class JaxBackend:
    """Population-vectorized scheduling on the XLA-compiled levelized sweep.

    ``fused=True`` (the default, registered as ``jax``) prices durations
    inside the same compiled call as the sweep; ``fused=False`` (registered
    as ``jax-unfused``) keeps the scalar per-call duration pass feeding the
    sweep — the measurable pre-fusion baseline."""

    vectorized = True

    def __init__(self, fused: bool = True) -> None:
        self.fused = fused
        self.name = "jax" if fused else "jax-unfused"
        # duration-pass vs compiled-evaluation wall-time split of the most
        # recent simulate_batch, from its spans (see module docstring)
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
        if self.fused:
            with spans.span("repro.engine.pack") as pack:
                plan, tables = plan_duration_tables(trace, calls)
                parents = plan.pack_memo.get("_parents_dev")
            # double precision scoped to the sweep (the global default stays
            # f32 for the model and kernel code paths)
            with jax.enable_x64(True):
                with spans.span("repro.engine.dispatch") as dispatch:
                    if parents is None:
                        # keep the static parent table resident on device —
                        # it is the same every batch and re-uploading it
                        # costs more than the entire class-table pack
                        parents = jnp.asarray(_plan_parents(trace, plan))
                        plan.pack_memo["_parents_dev"] = parents
                    dur_d, finish_d = _fused_eval(plan)(tables, parents)
                with spans.span("repro.engine.device_wait") as wait:
                    jax.block_until_ready((dur_d, finish_d))
                with spans.span("repro.engine.copy_back") as copy:
                    dur = np.asarray(dur_d).T    # (P, n_ops) view, op-major data
                    finish = np.asarray(finish_d)[:plan.n_ops].T
        else:
            with spans.span("repro.engine.pack") as pack:
                plans_durs = [plan_durations(trace, c.cfg, c.par, c.pools)
                              for c in calls]
                plan = plans_durs[0][0]
                parents = _plan_parents(trace, plan)
                dur = np.asarray([d for _, d in plans_durs], dtype=np.float64)
            with jax.enable_x64(True):
                with spans.span("repro.engine.dispatch") as dispatch:
                    finish_d = _sweep_population(jnp.asarray(dur.T),
                                                 jnp.asarray(parents))
                with spans.span("repro.engine.device_wait") as wait:
                    finish_d.block_until_ready()
                with spans.span("repro.engine.copy_back") as copy:
                    finish = np.asarray(finish_d)[:plan.n_ops].T
        self.last_timings = {
            "durations_s": pack.seconds,
            "sweep_s": dispatch.seconds + wait.seconds + copy.seconds}
        with spans.span("repro.engine.assemble"):
            makespan = finish.max(axis=1) if plan.n_ops else np.zeros(len(calls))
            res_of = np.asarray(plan.res_of, dtype=np.intp)
            n_res = len(plan.res_names)
            # whole-population busy accounting in one 2D scatter over
            # (population, resource).  Either broadcast orientation
            # accumulates each (member, resource) cell in increasing-uid
            # order — the same order as the per-call np.bincount it replaces
            # — so every row is bit-identical; iterate the orientation
            # matching the duration matrix's memory layout (op-major from
            # the fused kernel)
            busy2d = np.zeros((len(calls), n_res), dtype=np.float64)
            if self.fused:
                np.add.at(busy2d.T,
                          (res_of[:, None],
                           np.arange(len(calls))[None, :]), dur.T)
            else:
                np.add.at(busy2d,
                          (np.arange(len(calls))[:, None], res_of[None, :]), dur)
            out: list[SimResult] = []
            for k, call in enumerate(calls):
                fin: Mapping = {}
                if call.record_per_op or call.record_finish:
                    fin = FinishTimes(finish[k])
                out.append(build_sim_result(
                    plan, makespan=float(makespan[k]), busy=busy2d[k].tolist(),
                    dur=dur[k], finish=fin,
                    record_per_op=call.record_per_op))
        return out
