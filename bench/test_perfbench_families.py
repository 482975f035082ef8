"""The model-family seam: a family is found from its files alone, no
module of the yardstick imports the program as it is loaded, and the
reference trains its cohort in blocks and keeps integer rows integer."""
import ast
import json
import shutil
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import catalog, reference, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: a family of rows of token ids: a mean-pooled embedding and a
#: bias-free head, whose head update is each class column's mean change
THROWAWAY = '''
import jax
import jax.numpy as jnp

from benchlib import traffic

#: the dtype of every batch ``loss`` was traced with
SEEN = []


def make_traffic(wl, seed):
    d = wl["data"]
    c, length, vocab = (int(d["num_classes"]), int(d["tokens"]),
                        int(d["vocab"]))
    s = traffic.split(wl, seed, c)
    k_rows, k_test = jax.random.split(s.key)

    def rows(y, key):      # topic c draws its tokens from [3c, 3c + 3)
        y = jnp.asarray(y)
        return (3 * y[:, None] + jax.random.randint(
            key, (y.shape[0], length), 0, 3)) % vocab

    flat = jnp.concatenate([rows(s.y_flat, k_rows),
                            jnp.zeros((1, length), jnp.int32)])
    y_pad = jnp.concatenate([jnp.asarray(s.y_flat),
                             jnp.zeros((1,), jnp.int32)])
    idx = jnp.asarray(s.idx)
    test = {"x": rows(s.y_test, k_test), "y": jnp.asarray(s.y_test),
            "mask": jnp.ones(s.y_test.shape, jnp.float32)}
    return traffic.Traffic(x=flat[idx], y=y_pad[idx],
                           mask=(idx < s.y_flat.shape[0]).astype(
                               jnp.float32),
                           test=test, sizes=s.sizes)


def _apply(params, x):
    return params["emb"][x].mean(axis=1) @ params["head"]["w"]


def init_params(cfg, key):
    ke, kh = jax.random.split(key)
    d = int(cfg["width"])
    return {"emb": jax.random.normal(ke, (int(cfg["vocab"]), d)),
            "head": {"w": jax.random.normal(kh, (d, int(cfg["num_classes"])))
                     / d ** 0.5}}


def program_model(cfg, wl):
    return (lambda key: init_params(cfg, key)), _apply


def loss(cfg, params, x, y, mask):
    SEEN.append(x.dtype)
    logits = _apply(params, x)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def head_update(cfg, new, old):
    return jnp.mean(new["head"]["w"] - old["head"]["w"][None], axis=1)


def train_flops_per_row(cfg, wl):
    return 6 * int(cfg["width"]) * int(cfg["num_classes"])
'''


def _job(cell, fam, **kw):
    wl, loc = cell.workload, cell.workload["local"]
    return reference.Job(
        cfg=cell.config, family=fam, num_clients=int(wl["num_clients"]),
        num_select=int(wl["num_select"]), job_rounds=int(wl["job_rounds"]),
        lr=float(loc["lr"]), epochs=int(loc["epochs"]),
        batch_size=int(loc["batch_size"]), **kw)


def _cohorts(job, seed=0):
    rounds, n, k = job.job_rounds, job.num_clients, job.num_select
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n)[:k] for _ in range(rounds)])


def test_a_new_family_is_found_from_its_files_alone(tmp_path):
    """A throwaway family, its configuration and a cell, each a new file
    (and BENCHMARK.json entries): the harness loads it and the reference
    follows a call of it, with no file of the harness edited."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH / "configs", bench / "configs")
    shutil.copytree(BENCH / "workloads", bench / "workloads")
    (bench / "benchlib" / "families").mkdir(parents=True)
    (bench / "benchlib" / "families" / "throwaway.py").write_text(THROWAWAY)
    (bench / "configs" / "tokens.json").write_text(json.dumps(
        {"name": "tokens", "family": "throwaway", "num_classes": 4,
         "vocab": 16, "width": 8}))
    wl = catalog.load_json(BENCH / "workloads" / "mlp-xsilo-hics.json")
    wl.update(name="tokens-tiny", config="tokens", num_clients=6,
              num_select=2, samples_train=120, samples_test=8, job_rounds=3,
              alphas=[0.1, 1.0], data={"num_classes": 4, "tokens": 5,
                                        "vocab": 16})
    wl["local"] = dict(wl["local"], lr=0.5, batch_size=8)
    (bench / "workloads" / "tokens-tiny.json").write_text(json.dumps(wl))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tokens", "source": "test",
                            "file": "bench/configs/tokens.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tokens-tiny", "config": "tokens",
                              "traffic": "tokens-tiny", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = catalog.cell("tokens-tiny", root=tmp_path)
    assert cell.per_layer == []         # every metric lists its cells
    fam = catalog.family(cell.config, bench_dir=bench)
    assert Path(fam.__file__) == bench / "benchlib" / "families" / \
        "throwaway.py"
    assert catalog.family(cell.config, bench_dir=bench) is fam
    data = fam.make_traffic(cell.workload, 2**33 + 1)
    assert data.x.dtype == jnp.int32 and data.x.shape[::2] == (6, 5)

    job = _job(cell, fam)
    carry = reference.start(job, 7, int(cell.config["num_classes"]))
    assert carry[1].shape == (6, 4)
    ids = _cohorts(job)
    carry, loss, ent, mid = reference.follow_call(
        job, data.x, data.y, data.mask, carry, ids, mid=[1])
    assert set(fam.SEEN) == {jnp.dtype(jnp.int32)}
    assert np.isfinite(loss).all() and loss.shape == (3,)
    assert ent.shape == (3, 6) and set(mid) == {1}
    db = np.asarray(carry[1])
    assert np.all(db[np.unique(ids)] != 0.0)
    assert fam.train_flops_per_row(cell.config, cell.workload) == 192


#: the yardstick's modules that must load without the program beside it
PLAIN = ["benchlib/reference.py", "benchlib/layers.py",
         "benchlib/traffic.py",
         *sorted(str(p.relative_to(BENCH))
                 for p in (BENCH / "benchlib" / "families").glob("*.py"))]


def _module_level_imports(tree):
    """Modules imported by statements that run as the file is loaded:
    everything outside a function body."""
    out, todo = [], list(tree.body)
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Import):
            out += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            out.append(node.module or "")
        todo.extend(ast.iter_child_nodes(node))
    return out


@pytest.mark.parametrize("path", PLAIN)
def test_yardstick_module_imports_nothing_of_the_program(path):
    tree = ast.parse((BENCH / path).read_text())
    mods = _module_level_imports(tree)
    assert "jax" in mods
    assert not [m for m in mods if m.split(".")[0] == "repro"], mods


def test_module_level_import_check_sees_through_blocks():
    tree = ast.parse("try:\n    import repro.fed\nexcept ImportError:\n"
                     "    pass\n"
                     "def f():\n    from repro import x\n")
    assert _module_level_imports(tree) == ["repro.fed"]


@pytest.fixture(scope="module")
def tiny():
    """paper-mlp on 6 clients, cohorts of K = 4, two rounds with a step
    size large enough that the clients drift apart."""
    cell = catalog.cell("mlp-xsilo-hics")
    wl = dict(cell.workload, num_clients=6, num_select=4, samples_train=600,
              samples_test=8, job_rounds=2)
    wl["local"] = dict(wl["local"], lr=0.05)
    cell = catalog.Cell(name="tiny", entry=cell.entry, workload=wl,
                        config=cell.config, end_to_end=[], per_layer=[])
    fam = catalog.family(cell.config)
    return cell, fam, fam.make_traffic(wl, 3)


def _follow(cell, fam, data, x=None, **kw):
    job = _job(cell, fam, **kw)
    carry = reference.start(job, 5, int(cell.config["num_classes"]))
    carry, loss, _, _ = reference.follow_call(
        job, data.x if x is None else x, data.y, data.mask, carry,
        _cohorts(job))
    return (jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                   carry[0]), np.asarray(carry[1]), loss)


@pytest.mark.parametrize("block", [1, 2])
def test_cohort_blocks_agree_with_the_whole_cohort(tiny, block):
    """Blocks of 1 or 2 clients against one block of the whole cohort
    (K = 4, the default) change only the order in which the K clients'
    float32 parameters are summed for the mean (running block sums, not
    one sum over K): a few float32 roundings of each parameter, about
    1e-7 of it, carried through a second round of SGD.  The head update
    of each client is the same computation either way."""
    cell, fam, data = tiny
    p_all, db_all, loss_all = _follow(cell, fam, data)
    p_blk, db_blk, loss_blk = _follow(cell, fam, data, cohort_block=block)
    for name in p_all:
        for leaf in p_all[name]:
            np.testing.assert_allclose(p_blk[name][leaf], p_all[name][leaf],
                                       rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(db_blk, db_all, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(loss_blk, loss_all, rtol=1e-5)
    assert np.abs(db_all).max() > 1e-3          # the heads did move


def test_a_block_that_does_not_divide_the_cohort_is_an_error(tiny):
    cell, fam, _ = tiny
    with pytest.raises(ValueError, match="does not divide"):
        _job(cell, fam, cohort_block=3)


@pytest.mark.parametrize("rows, dtype, seen", [
    (jnp.int32, jnp.float32, jnp.int32),     # token ids stay integers
    (jnp.float32, jnp.bfloat16, jnp.bfloat16),   # the control's cast
])
def test_rows_reach_the_loss_cast_only_where_floating(tiny, rows, dtype,
                                                      seen):
    cell, fam, data = tiny
    dtypes = []

    def loss(cfg, params, x, y, mask):
        dtypes.append(x.dtype)
        return fam.loss(cfg, params, x, y, mask)

    spy = types.SimpleNamespace(**{k: getattr(fam, k) for k in (
        "init_params", "head_update")}, loss=loss)
    x = jnp.round(data.x * 4).astype(rows)
    _, _, loss_values = _follow(cell, spy, data, x=x, dtype=dtype)
    assert set(dtypes) == {jnp.dtype(seen)}
    assert np.isfinite(loss_values).all()
