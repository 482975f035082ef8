"""The comparison that decides ``correct``, driven through a whole run
on the CPU at a tiny size with the timed path broken underneath: each
fault, and the control (the reference in bfloat16 in the program's
place), must come out not correct under the limits of a real cell,
while the sound program comes out correct."""
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from benchlib import catalog, faults, session

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the real cell whose limits the tiny cell is held to
LIMITS_OF = "mlp-xsilo-hics"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding one tiny cell: N=8 clients, K=4, jobs of 4
    rounds (2 of coverage sweep, then clustered), the MLP config, a step
    size large enough that the clients' Ĥ (and so γ_t) matter."""
    tmp = tmp_path_factory.mktemp("tiny")
    (tmp / "bench" / "workloads").mkdir(parents=True)
    shutil.copytree(BENCH / "configs", tmp / "bench" / "configs")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "tiny", "config": "paper-mlp",
                          "traffic": "tiny", "chips": 1, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    wl = catalog.load_json(BENCH / "workloads" / f"{LIMITS_OF}.json")
    wl.update(name="tiny", num_clients=8, num_select=4, samples_train=320,
              samples_test=64, job_rounds=4, eval_every=1)
    wl["local"] = dict(wl["local"], lr=0.05)
    (tmp / "bench" / "workloads" / "tiny.json").write_text(json.dumps(wl))
    return tmp


def _run(root, patch=None):
    return session.run("tiny", 2**33 + 5, 0.2, False, time.perf_counter(),
                       require_tpu=False, root=root, cache=False,
                       patch=patch)


def test_sound_program_is_correct(root):
    out = _run(root)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 8 and out["failed"] == 0   # two jobs
    assert list(out["checks"])[-1] == "selection"
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(root, fault):
    with faults.FAULTS[fault]() as plant:
        out = _run(root, plant)
    assert not out["correct"], out["checks"]
    if fault in ("altered_ids", "unannealed"):
        assert out["checks"]["selection"]["value"] > 0


def test_control_is_not_correct(root):
    """The reference computed in bfloat16, put in the program's place,
    fails the float32 comparison."""
    cell = catalog.cell("tiny", root=root)
    job = session.Job(cell, 11)
    while job.following:
        job.call()
    records = job.records()
    values = session.control_check(cell, job.prog_seed, job.data, records)
    limits = cell.workload["limits"]
    assert any(values[k] > limits[k] for k in limits), values
    assert np.isfinite(list(values.values())).all()
