"""Pallas TPU kernels for the framework's compute hot-spots:

  fused_stats      — single-sweep entropy + L2 norm + RMS over (N, C)
                     (the pre-Gram stage of the HiCS selection step)
  gram_update      — K-row incremental refresh of a cached distance
                     matrix (Alg. 1 replaces K rows per round, so the
                     strip is O(K·N·C) vs the full step's O(N²·C)),
                     ending in one of three distances: arccos+λ|ΔĤ|
                     (Eq. 9, HiCS), cosine (Clustered Sampling) or L2
                     (DivFL)
  hetero_entropy   — fused temperature-softmax entropy over class blocks
                     (entropy-only API; fused_stats supersedes it on the
                     selection path)
  pairwise         — Eq. 9 distance: MXU-tiled Gram/cosine kernel + the
                     arccos/λ|ΔĤ| tail in XLA, plus the end-to-end
                     fused selection step
  decode_attention — GQA flash-decode for the serving hot loop
  ward_merge       — Ward's whole agglomerative merge loop in one launch,
                     the (N, N) distance resident in VMEM; called by
                     ``core.clustering``, whose XLA merge loop is its
                     oracle and its fallback (CPU, N past VMEM)

Each other kernel has a pure-jnp oracle in ref.py; ops.py is the
dispatching public API (TPU -> compiled Pallas, CPU -> interpret/oracle).

All entry points are jit/scan-compatible: ``hics_selection_step`` is
the device half of the functional selector protocol
(``repro.core.selectors.functional``) and runs *inside* the scanned
``round_step`` when ``FederatedServer`` is driven with
``jit_rounds=True`` — no host round trip between the cohort step and
the next selection.
"""
from repro.kernels.ops import (cached_feature_step, estimate_entropies,
                               fused_row_stats, gqa_decode_attention,
                               gram_row_update, hics_selection_step,
                               hics_selection_step_cached,
                               pairwise_distances)

__all__ = ["cached_feature_step", "estimate_entropies",
           "fused_row_stats", "gqa_decode_attention", "gram_row_update",
           "hics_selection_step", "hics_selection_step_cached",
           "pairwise_distances"]
