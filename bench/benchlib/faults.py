"""Faults planted under the timed path, for the checks that the
comparison catches them (the benchmark's tests on the CPU, and
``calibrate.py --fault-seeds`` on the chip at a cell's own size).

Each is a context manager around one run of a job: ``plant(job)`` is
called with the built job before its first call, and whatever it
patched is restored on exit.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator


@contextlib.contextmanager
def unchanged_state() -> Iterator[Callable]:
    """The local update returns the global parameters as it got them."""
    def plant(job):
        lu = job.server._lu

        def frozen(params, extra, *args, **kw):
            _, new_extra, metrics = lu(params, extra, *args, **kw)
            return params, new_extra, metrics
        job.server._lu = frozen
    yield plant


@contextlib.contextmanager
def half_cohort() -> Iterator[Callable]:
    """Aggregation averages the first half of the cohort only."""
    import jax
    import jax.numpy as jnp
    import repro.fed.server as srv

    original = srv.aggregate_params

    def half(new_params, weights=None):
        return jax.tree_util.tree_map(
            lambda s: jnp.mean(s[:s.shape[0] // 2], axis=0), new_params)
    srv.aggregate_params = half
    try:
        yield lambda job: None
    finally:
        srv.aggregate_params = original


@contextlib.contextmanager
def altered_ids() -> Iterator[Callable]:
    """Each round's selection is altered where it is produced."""
    def plant(job):
        fn = job.server.selector.fn
        n = job.fed_cfg.num_clients

        def select(state, t, key=None):
            ids, state = fn.select(state, t, key)
            return (ids + 1) % n, state
        job.server.selector.fn = fn._replace(select=select)
    yield plant


@contextlib.contextmanager
def unannealed() -> Iterator[Callable]:
    """The two-stage sampler's γ_t stays at γ₀: the annealing schedule
    left out (a wrong t in the sampler reads the same)."""
    import repro.core.selectors.hics as hics

    original = hics.anneal_device
    hics.anneal_device = lambda gamma0, t, total_rounds: gamma0 + 0.0 * t
    try:
        yield lambda job: None
    finally:
        hics.anneal_device = original


FAULTS: Dict[str, Callable] = {
    "unchanged_state": unchanged_state,
    "half_cohort": half_cohort,
    "altered_ids": altered_ids,
    "unannealed": unannealed,
}
