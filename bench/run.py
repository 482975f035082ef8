"""Chip benchmark of the HiCS-FL federated system: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository's root on a machine with a TPU.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics and a ``breakdown``), ``device``,
and last ``checks``, each number of the comparison with the reference
beside its limit; the same numbers close standard error.  Without a
TPU (or with fewer chips than the cell asks for) it prints no result
and exits with code 3.  See ``bench/benchlib/session.py`` for what one
run does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prepare(trace: bool) -> None:
    """Environment and import path; must run before JAX is imported.

    The program's scanned round step closes over the client data; JAX
    would otherwise embed it in the compiled program as a constant, so
    the program (and its cache key) would change with every seed's data.
    Hoisted, the data is an argument and one compile serves every seed.
    """
    os.environ["JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"] = "1"
    if trace:
        os.environ["REPRO_TRACE"] = "1"     # the program's host spans
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    args = parse(argv)
    prepare(bool(args.trace))
    from benchlib import session
    try:
        out = session.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except session.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
