"""Pluggable simulation backends (see ``backends.base`` for the API).

Built-ins:

  ``reference``  the discrete-event heapq loop — the semantics oracle,
                 bit-identical to the pre-backend ``simulate()``.
  ``jax``        jit+vmap-compiled levelized DAG sweep fused with the
                 batched duration pass — an entire population's collective
                 pricing, roofline and schedule evaluate in one compiled
                 call (requires the ``jax`` optional extra).
"""
from __future__ import annotations

from repro.core.backends.base import (BACKEND_REGISTRY, SimBackend, SimCall,
                                      SimJob, backend_available, get_backend,
                                      list_backends, register_backend,
                                      run_sim_job, run_sim_jobs)


def _reference_factory() -> SimBackend:
    from repro.core.backends.reference import ReferenceBackend

    return ReferenceBackend()


def _jax_factory() -> SimBackend:
    try:
        from repro.core.backends.jax_backend import JaxBackend
    except ImportError as e:
        raise ImportError(
            "the 'jax' simulation backend needs jax installed — "
            "pip install 'cosmic-repro[jax]'") from e
    return JaxBackend()


register_backend("reference", _reference_factory,
                 doc="discrete-event heapq loop (bit-exact oracle, default)")
register_backend("jax", _jax_factory,
                 doc="fused jit+vmap evaluation — vectorized collective + "
                     "roofline pricing and the levelized DAG sweep in one "
                     "compiled call (needs the jax extra)")

__all__ = [
    "BACKEND_REGISTRY", "SimBackend", "SimCall", "SimJob",
    "backend_available", "get_backend", "list_backends", "register_backend",
    "run_sim_job", "run_sim_jobs",
]
