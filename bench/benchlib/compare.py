"""The comparison that decides ``correct``: what the program produced
on the timed path against the plain reference, as a handful of numbers,
each held to the limit the cell's workload file states.

The program's side of each followed call (``Record``) is copied to
the host as the call returns: the history of the call's rounds (ids,
train loss, Ĥ of every client), the global parameters, and the
selector's Δb buffer and distance cache.  The reference's side comes
from ``reference.follow_call`` over the same calls.  Neither side keeps
a parameter set: each keeps the norm of every leaf's change from θ₀
(``change_norms``), taken one leaf at a time.

Numbers (all ≥ 0, larger is worse):

* ``loss``: largest |L_prog − L_ref| / |L_ref| over the followed rounds
  (the cohort's mean local training loss): the local update's forward.
* ``update``: worst leaf of | ‖θ_prog − θ₀‖ − ‖θ_ref − θ₀‖ | over
  max(‖θ_ref − θ₀‖ of the leaf, the median leaf's), at the end of each
  followed call: local SGD and aggregation.  A leaf the reference moves
  by under a thousandth of the median leaf is left out.
* ``delta_b``: worst client of ‖Δb_prog − Δb_ref‖ over max(‖Δb_ref‖ of
  the client, the median client's), at the end of each call.
* ``entropy``: largest |Ĥ_prog − Ĥ_ref| over every client and round.
* ``distance``: largest |D_cache − Eq. 9(Δb_prog)| over the cache
  entries whose rows are not waiting for a refresh, at the end of each
  call: the incremental distance cache and its Ĥ against a from-scratch
  Eq. 9 of the program's own Δb.
* ``selection``: the share of checked rounds whose K ids (in order)
  differ from the reference's choice made from the program's state
  before that round (its Δb and seen set) with that round's key.  The
  checked rounds are the first of each followed call after the first
  (the coverage sweep, or Eq. 9, Ward clustering and the two-stage
  sampler at t = 0), and the rounds near a quarter, a half and three
  quarters of the last followed call (the first to start clustered),
  where γ_t = γ₀(1 − t/T) has moved off γ₀.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

NAMES = ("loss", "update", "delta_b", "entropy", "distance", "selection")


@dataclasses.dataclass
class Record:
    """One followed call, program side (host arrays)."""
    ids: np.ndarray          # (R, K)
    loss: np.ndarray         # (R,)
    ent: np.ndarray          # (R, N)
    norms: List[float]       # ‖θ − θ₀‖ of each leaf after the call
    delta_b: np.ndarray      # (N, C)
    dist: np.ndarray         # (N, N) cache
    seen: np.ndarray         # (N,) bool: clients that have trained
    fresh: np.ndarray        # (N,) bool: rows not waiting for a refresh
    #: (t, Δb, seen): the program's state before round t of the call
    mid: List[Tuple[int, np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class RefCall:
    """One followed call, reference side (host arrays)."""
    loss: np.ndarray
    ent: np.ndarray
    norms: List[float]
    delta_b: np.ndarray
    rng0: np.ndarray         # the key chain's state before the call
    #: {t: Δb before round t} for the rounds the record's ``mid`` names
    mid_delta_b: Dict[int, np.ndarray] = dataclasses.field(
        default_factory=dict)


def _leaves(tree) -> list:
    if isinstance(tree, dict):
        return [x for k in sorted(tree) for x in _leaves(tree[k])]
    return [tree]


def change_norms(params, p0) -> List[float]:
    """‖θ − θ₀‖ of each leaf, in the leaves' sorted order, each in
    float64 from its own copy: one leaf of either tree (host or device
    arrays) is read and widened at a time."""
    return [float(np.linalg.norm(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64)))
            for a, b in zip(_leaves(params), _leaves(p0))]


def update_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Worst leaf's gap between the two norms of the parameters' change,
    over the larger of that leaf's and the median leaf's reference
    norm.  Leaves the reference moves by under a thousandth of the
    median leaf (a change that is round-off alone) are left out."""
    floor = max(float(np.median(ref)), 1e-30)
    return max(abs(a - b) / max(b, floor) for a, b in zip(prog, ref)
               if b >= 1e-3 * floor)


def row_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    norms = np.linalg.norm(ref, axis=1)
    floor = max(float(np.median(norms)), 1e-30)
    gaps = np.linalg.norm(prog - ref, axis=1) / np.maximum(norms, floor)
    return float(gaps.max())


def numbers(records: List[Record], refs: List[RefCall], distance_fn,
            expected: List[Tuple[int, int, np.ndarray]]
            ) -> Dict[str, float]:
    """The six numbers over the followed calls; ``expected`` holds the
    reference's selections (call, round, ids) of the checked rounds."""
    loss_p = np.concatenate([r.loss for r in records]).astype(np.float64)
    loss_r = np.concatenate([r.loss for r in refs]).astype(np.float64)
    ent = max(float(np.max(np.abs(np.asarray(a.ent, np.float64)
                                  - np.asarray(b.ent, np.float64))))
              for a, b in zip(records, refs))
    dist = 0.0
    for rec in records:
        d_ref = distance_fn(rec.delta_b)
        f = rec.fresh
        dist = max(dist, float(np.max(np.abs(
            np.asarray(rec.dist, np.float64)[np.ix_(f, f)]
            - np.asarray(d_ref, np.float64)[np.ix_(f, f)]))))
    return {
        "loss": float(np.max(np.abs(loss_p - loss_r)
                             / np.maximum(np.abs(loss_r), 1e-12))),
        "update": max(update_gap(a.norms, b.norms)
                      for a, b in zip(records, refs)),
        "delta_b": max(row_gap(a.delta_b, b.delta_b)
                       for a, b in zip(records, refs)),
        "entropy": ent,
        "distance": dist,
        "selection": float(np.mean([np.any(records[c].ids[r] != ids)
                                    for c, r, ids in expected])),
    }


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in NAMES)


def checks_entry(values: Dict[str, float],
                 limits: Dict[str, float]) -> Dict[str, dict]:
    """The result line's last key: each number beside its limit."""
    return {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
