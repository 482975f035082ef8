"""Multi-seed / multi-scenario sweep driver — the batched-evaluation
entrypoint over ``repro.scenarios``.

Cross-products scenarios × selectors, vmaps the seeds of every cell
into one XLA program, and writes:

  * ``--out``   full results: per-seed + mean±std accuracy/entropy
                trajectories per (scenario, selector) cell;
  * ``--bench`` ``BENCH_sweep.json``: vmapped-seeds vs python-seed-loop
                wall time (and optionally the FederatedServer host loop
                via ``--host``), the per-PR throughput trajectory CI
                uploads.

Usage:
  PYTHONPATH=src python -m repro.launch.sweep --quick
  PYTHONPATH=src python -m repro.launch.sweep \\
      --scenarios mixed_80_20 dir_severe shards2 --selectors hics random \\
      --seeds 8 --rounds 40 --out SWEEP.json --bench BENCH_sweep.json
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from repro.data import SyntheticSpec
from repro.fed import LocalSpec
from repro.launch.cache import enable_compile_cache
from repro.scenarios import SCENARIOS, SweepSpec, bench_sweep, run_sweep


def _sanitize(obj):
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", nargs="+",
                    default=["mixed_80_20", "dir_mild"],
                    choices=sorted(SCENARIOS))
    ap.add_argument("--selectors", nargs="+", default=["hics", "random"])
    ap.add_argument("--seeds", type=int, default=4,
                    help="number of seeds (0..n-1)")
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--select", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--samples", type=int, default=1000)
    ap.add_argument("--cap", type=int, default=0,
                    help="per-client capacity (0 → 4·S/N)")
    ap.add_argument("--dim", type=int, default=64,
                    help="synthetic feature dim")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--quick", action="store_true",
                    help="CI preset: 2 seeds × 2 scenarios × 2 selectors"
                         ", 6 rounds")
    ap.add_argument("--host", action="store_true",
                    help="also time the FederatedServer host loop")
    ap.add_argument("--telemetry", default="",
                    help="write per-round telemetry to this JSONL path "
                         "(enables the selection/training/fairness "
                         "metric groups; see docs/observability.md)")
    ap.add_argument("--out", default="")
    ap.add_argument("--bench", default="BENCH_sweep.json")
    args = ap.parse_args()
    enable_compile_cache()

    groups = ("selection", "training", "fairness") if args.telemetry else ()

    if args.quick:
        spec = SweepSpec(
            scenarios=("mixed_80_20", "dir_mild"),
            selectors=("hics", "random"), seeds=(0, 1),
            num_clients=10, num_select=3, rounds=6,
            samples_train=400, samples_test=120,
            data=SyntheticSpec(dim=16, rank=2, noise=0.5),
            local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1,
                            epochs=1, batch_size=32),
            telemetry=groups)
        bench_spec = SweepSpec(
            scenarios=("mixed_80_20", "dir_mild"),
            selectors=("hics", "random"), seeds=(0, 1, 2, 3),
            num_clients=10, num_select=3, rounds=6,
            samples_train=400, samples_test=120,
            data=SyntheticSpec(dim=16, rank=2, noise=0.5),
            local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1,
                            epochs=1, batch_size=32))
    else:
        spec = SweepSpec(
            scenarios=tuple(args.scenarios),
            selectors=tuple(args.selectors),
            seeds=tuple(range(args.seeds)),
            num_clients=args.clients, num_select=args.select,
            rounds=args.rounds, samples_train=args.samples,
            samples_test=max(64, args.samples // 5),
            cap=args.cap or None,
            data=SyntheticSpec(dim=args.dim, noise=0.5),
            local=LocalSpec(algo="fedavg", optimizer="sgd", lr=args.lr,
                            epochs=args.epochs, batch_size=32),
            telemetry=groups)
        bench_spec = spec

    print(f"== sweep: {len(spec.scenarios)} scenarios × "
          f"{len(spec.selectors)} selectors × {len(spec.seeds)} seeds "
          f"(vmapped) ==", flush=True)
    res = run_sweep(spec, progress=True)
    if args.telemetry:
        from repro.telemetry import write_sweep
        cells = {name: cell["telemetry"]
                 for name, cell in res["grid"].items()}
        write_sweep(args.telemetry, cells,
                    meta={"driver": "launch.sweep",
                          "groups": list(groups),
                          "rounds": spec.rounds,
                          "seeds": list(spec.seeds)})
        print(f"wrote telemetry {args.telemetry}", flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(_sanitize(res), indent=1))
        print(f"wrote {args.out}", flush=True)

    print(f"== bench: vmapped vs serial on {len(bench_spec.seeds)} seeds "
          f"==", flush=True)
    bench = bench_sweep(bench_spec, include_host=args.host or args.quick)
    if args.bench:
        Path(args.bench).write_text(json.dumps(_sanitize(bench), indent=1))
        print(f"wrote {args.bench}", flush=True)


if __name__ == "__main__":
    main()
