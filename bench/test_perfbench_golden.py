"""Each cell's traffic and the reference's first call, bit for bit as
the harness made them before the model family became a file of its own
(``families/classifier.py``): digests recorded at a fixed seed on the
CPU, reproduced here on the CPU.

The cut: the reference follows a call of ``job_rounds`` 2 (the two
rounds train a fixed cohort each; the call is split before round 1, as
a checked mid-call state splits it), on the cell's own split.  Only
``cnn-xdevice-hics`` is cut further: its rows at full size take 1.9 GB,
so its digests are taken with 6,000 training and 1,000 test samples
over the same 2,000 clients and α mix.
"""
import functools
import hashlib

import jax
import numpy as np
import pytest

from benchlib import catalog, reference, traffic

SEED = 2**33 + 77
ROUNDS = 2
CUTS = {"cnn-xdevice-hics": {"samples_train": 6000, "samples_test": 1000}}
GOLDEN = {
    "cnn-xdevice-hics": {
        "x": "66a1ae04a18b2db217fa", "y": "9730805474caf2284832",
        "mask": "d6e5fc87a95e440f81e3", "test": "2d330e53d50998c904ac",
        "sizes": "2a13bf9d43d4eff926f8", "start": "bc01c8765a89114b78ea",
        "call": "f1bcddad9c63293dd908"},
    "mlp-xsilo-hics": {
        "x": "0df669f89b70d8d225dc", "y": "39e40c798fafa2f99465",
        "mask": "5df56b30864474b5e7a6", "test": "b13cbfb960c449b7f839",
        "sizes": "f495938ac7461a70fef4", "start": "28ba0eb1087cc16b7349",
        "call": "6bf529140ce59dff00dd"},
    "cnn-paper-hics": {
        "x": "61b6f8c864feac38f57f", "y": "ad8f9b2955718caa2cf9",
        "mask": "ae07fc256e4e0530651e", "test": "592fd3021318a957df82",
        "sizes": "df7f201aa38860f1dcd3", "start": "c2004deed1cc3f84b84e",
        "call": "1d01ffaca2b189784030"},
}


def digest(tree) -> str:
    """sha256 of every leaf's dtype, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:20]


@functools.lru_cache(maxsize=1)
def _cell(name):
    cell = catalog.cell(name)
    wl = dict(cell.workload, **CUTS.get(name, {}), job_rounds=ROUNDS)
    return cell, wl, catalog.family(cell.config).make_traffic(wl, SEED)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_traffic_is_unchanged(name):
    _, _, data = _cell(name)
    got = {"x": digest(data.x), "y": digest(data.y),
           "mask": digest(data.mask), "test": digest(data.test),
           "sizes": digest(data.sizes)}
    assert got == {k: GOLDEN[name][k] for k in got}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_first_reference_call_is_unchanged(name):
    cell, wl, data = _cell(name)
    loc = wl["local"]
    rj = reference.Job(
        cfg=cell.config, family=catalog.family(cell.config),
        num_clients=int(wl["num_clients"]), num_select=int(wl["num_select"]),
        job_rounds=ROUNDS, lr=float(loc["lr"]), epochs=int(loc["epochs"]),
        batch_size=int(loc["batch_size"]))
    carry = reference.start(rj, traffic.split_seed(SEED)[1],
                            int(cell.config["num_classes"]))
    assert digest(carry) == GOLDEN[name]["start"]
    n, k = rj.num_clients, rj.num_select
    ids = np.random.default_rng(0).permutation(n)[:ROUNDS * k].reshape(
        ROUNDS, k)
    carry, loss, ent, mid = reference.follow_call(
        rj, data.x, data.y, data.mask, carry, ids, mid=[1])
    assert digest((carry, loss, ent, mid[1])) == GOLDEN[name]["call"]
