"""Controls and planted faults that must make ``correct`` come out false.

The benchmark's own runs never apply these; ``bench/checks.py`` reads their
numbers on the chip and ``bench/tests/test_faults.py`` drives a whole run
under each one on a CPU.

* ``x64_off`` (engine control): the fused backend without its float64
  scope, so pricing and sweep run in float32: the step that would tempt a
  later change.
* ``answer`` (engine fault): answers altered where they are produced: the
  makespan and busy times the backend hands back for every other point,
  off by one part in 10^6.
* ``half_batch`` (training fault): the step sees half of the batch and takes
  its mean over that half.
* ``frozen`` (training fault): the step returns its state unchanged.

The training control is not a patch of the program: it is the reference
itself computed with float8 (e4m3) matmul operands, one step below the
configuration's bf16 (``bench/checks.py``).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def x64_off():
    import jax

    keep = jax.enable_x64
    jax.enable_x64 = lambda *a, **k: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.enable_x64 = keep


@contextlib.contextmanager
def answer():
    from repro.core.backends import jax_backend

    keep = jax_backend.build_sim_result
    built = [0]

    def altered(plan, *, makespan, busy, **kw):
        built[0] += 1
        if built[0] % 2:
            makespan *= 1 + 1e-6
            busy = [b * (1 + 1e-6) for b in busy]
        return keep(plan, makespan=makespan, busy=busy, **kw)

    jax_backend.build_sim_result = altered
    try:
        yield
    finally:
        jax_backend.build_sim_result = keep


def _step_patch(wrap):
    @contextlib.contextmanager
    def patch():
        from repro.launch import train as T

        keep = T.make_train_step

        def make(*a, **kw):
            return wrap(keep(*a, **kw))

        T.make_train_step = make
        try:
            yield
        finally:
            T.make_train_step = keep

    return patch


def _half(step):
    import jax

    return lambda state, batch: step(
        state, jax.tree.map(lambda a: a[: a.shape[0] // 2], batch))


def _frozen(step):
    return lambda state, batch: (state, step(state, batch)[1])


half_batch = _step_patch(_half)
frozen = _step_patch(_frozen)


PATCHES = {"x64_off": x64_off, "answer": answer, "half_batch": half_batch,
           "frozen": frozen}
ENGINE = ("x64_off", "answer")
TRAIN = ("half_batch", "frozen")
