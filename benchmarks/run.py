"""Benchmark harness entrypoint — one module per paper table/figure.

  python -m benchmarks.run            # quick tier (default)
  python -m benchmarks.run --quick    # same, explicit
  python -m benchmarks.run --full     # paper-scale settings
  python -m benchmarks.run --only selectors,overhead

The quick tier's ``overhead`` module also writes the fused-vs-unfused
selection-step numbers to ``BENCH_selection.json`` at the repo root,
and ``selectors`` writes the scanned-vs-host round-loop numbers to
``BENCH_round_loop.json`` (the per-PR perf trajectory; CI uploads both
as artifacts — see .github/workflows/ci.yml).

Modules:
  selectors  — Tables 1 + 2 (final acc, rounds-to-target, speedup) +
               Fig. 3 (loss variance) across 3 heterogeneity settings
  sweep      — vmapped multi-seed sweep vs python seed loop
               (``BENCH_sweep.json``; see repro.scenarios)
  async      — sync vs buffered-async server under straggler/burst
               latency models (``BENCH_async.json``)
  overhead   — Table 3 (selection compute scaling vs |θ| and C)
  estimation — Figs. 5, 8-11 (Ĥ vs H, Assumption 3.1 envelope)
  kernels    — Pallas kernels vs oracles at LLM-head scale
  roofline   — §Roofline report from the multi-pod dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.cache import enable_compile_cache

MODULES = ("selectors", "sweep", "async", "overhead", "estimation",
           "ablations", "kernels", "roofline")


def main():
    ap = argparse.ArgumentParser()
    tier = ap.add_mutually_exclusive_group()
    tier.add_argument("--full", action="store_true",
                      help="paper-scale rounds/seeds (slow)")
    tier.add_argument("--quick", action="store_true",
                      help="quick tier (the default)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args()
    enable_compile_cache()
    only = set(filter(None, args.only.split(",")))
    todo = [m for m in MODULES if not only or m in only]
    t_all = time.time()
    failures = []
    for name in todo:
        mod = __import__(f"benchmarks.bench_{name}",
                         fromlist=["main"])
        t0 = time.time()
        try:
            mod.main(quick=not args.full)
        except Exception as e:  # keep going; report at the end
            failures.append((name, repr(e)))
            print(f"!! bench_{name} FAILED: {e!r}", flush=True)
        print(f"-- bench_{name}: {time.time()-t0:.1f}s\n", flush=True)
    print(f"== benchmarks done in {time.time()-t_all:.1f}s; "
          f"{len(todo)-len(failures)}/{len(todo)} modules ok ==")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
