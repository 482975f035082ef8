"""Readings that set the limits of a cell's ``correct`` comparison.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2]

For each seed, in one process: the program's followed calls (as a run
makes them) against the float32 reference (the lower reading), and for
each control seed also the control, the same reference computed in
bfloat16 and put in the program's place, against the float32 reference
(the upper reading); for each fault seed the numbers under each planted
fault (``benchlib/faults.py``) and with the program's own bfloat16 Gram
path.  One JSON line per seed on standard output.
Needs a TPU, as ``run.py`` does.
"""
import argparse
import gc
import json
import sys
import time

from run import prepare


def followed(cell, seed: int, plant=None, selector_kw=None):
    """The program's followed calls for ``seed``, as a run makes them."""
    from benchlib import session
    job = session.Job(cell, seed, selector_kw=selector_kw)
    if plant is not None:
        plant(job)
    while job.following:
        job.call()
    out = job.records(), job.prog_seed, job.data
    del job
    gc.collect()
    return out


def readings(cell, seed: int, control: bool) -> dict:
    """The program's numbers for ``seed``, and the control's beside
    them when ``control``: one set-up serves both."""
    from benchlib import session
    records, job_seed, data = followed(cell, seed)
    out = {"program": session.check(cell, job_seed, data, records)}
    if control:
        out["control"] = session.control_check(cell, job_seed, data,
                                               records)
    return out


def fault_readings(cell, seed: int) -> dict:
    """The numbers under each planted fault, and with the program's own
    bfloat16 Gram path (``gram_in_bf16``) switched on: the lower
    precision the program offers for the Eq. 9 distance."""
    from benchlib import faults, session
    out = {}
    for name, fault in faults.FAULTS.items():
        if name == "unchanged_state":     # reads 1 on `update` by design
            continue
        with fault() as plant:
            records, job_seed, data = followed(cell, seed, plant)
        out[name] = session.check(cell, job_seed, data, records)
    records, job_seed, data = followed(cell, seed,
                                       selector_kw={"gram_in_bf16": True})
    out["gram_in_bf16"] = session.check(cell, job_seed, data, records)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="",
                   help="the seeds (among --seeds) that also run the "
                   "control")
    p.add_argument("--fault-seeds", default="",
                   help="seeds on which to read each planted fault and "
                   "the program's bfloat16 Gram path")
    args = p.parse_args(argv)
    prepare(False)
    import jax
    from benchlib import catalog, session
    from repro.launch.cache import enable_compile_cache
    try:
        session.check_devices(1)
    except session.NoAccelerator as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = catalog.cell(args.workload)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for s in [int(s) for s in args.seeds.split(",") if s]:
        t = time.perf_counter()
        out = readings(cell, s, s in control)
        print(json.dumps({"cell": args.workload, "seed": s, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    for s in [int(s) for s in args.fault_seeds.split(",") if s]:
        t = time.perf_counter()
        out = fault_readings(cell, s)
        print(json.dumps({"cell": args.workload, "seed": s, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
