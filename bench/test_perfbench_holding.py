"""What the comparison holds of the parameters: the reference donates
its carry to each call, and both sides keep each leaf's change norm,
taken one leaf at a time, in place of a float64 parameter set, with the
`update` number the same bit for bit as from whole float64 copies."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import catalog, compare, reference


def _old_update_gap(p_prog, p_ref, p0) -> float:
    """`update` as it was taken from whole float64 copies of the three
    parameter sets: the oracle."""
    def leaves(tree):
        if isinstance(tree, dict):
            return [x for k in sorted(tree) for x in leaves(tree[k])]
        return [np.asarray(tree, np.float64)]
    prog = [np.linalg.norm(a - b) for a, b in zip(leaves(p_prog),
                                                 leaves(p0))]
    ref = [np.linalg.norm(a - b) for a, b in zip(leaves(p_ref),
                                                leaves(p0))]
    floor = max(float(np.median(ref)), 1e-30)
    return max(abs(a - b) / max(b, floor) for a, b in zip(prog, ref)
               if b >= 1e-3 * floor)


def _tree(rng, scale: dict, base=None):
    """A nested parameter tree of float32 leaves: ``base`` moved by
    ``scale[name]`` times a normal draw (a draw alone without base)."""
    shapes = {"conv": {"w": (5, 5, 3, 8), "b": (8,)},
              "fc": {"w": (64, 10), "b": (10,)}, "emb": (40, 16)}

    def make(shape, b, name):
        if isinstance(shape, dict):
            return {k: make(v, None if b is None else b[k], f"{name}/{k}")
                    for k, v in shape.items()}
        d = rng.standard_normal(shape).astype(np.float32)
        return d if b is None else (b + scale.get(name, 1e-2) * d
                                    ).astype(np.float32)
    return make(shapes, base, "")


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("ref_dtype", [jnp.float32, jnp.bfloat16])
def test_update_per_leaf_is_bit_for_bit_the_full_copy_gap(seed, ref_dtype):
    """The program's side as host float32 leaves, the reference's as
    device leaves (bfloat16 for the control), a leaf the reference
    moves by round-off alone among them."""
    rng = np.random.default_rng(seed)
    p0 = _tree(rng, {})
    prog = _tree(rng, {"/fc/b": 3e-2}, p0)
    ref = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, ref_dtype),
        _tree(rng, {"/conv/b": 1e-9}, p0))
    got = compare.update_gap(compare.change_norms(prog, p0),
                             compare.change_norms(ref, p0))
    assert got == _old_update_gap(prog, ref, p0)
    assert got > 0


def test_reference_call_donates_its_carry():
    cell = catalog.cell("mlp-xsilo-hics")
    wl = cell.workload
    rj = reference.Job(
        cfg=cell.config, family=catalog.family(cell.config), num_clients=4,
        num_select=2, job_rounds=2, lr=0.01, epochs=1, batch_size=4)
    carry = reference.start(rj, 3, int(cell.config["num_classes"]))
    x = jnp.zeros((4, 8, int(wl["data"]["dim"])), jnp.float32)
    y = jnp.zeros((4, 8), jnp.int32)
    m = jnp.ones((4, 8), jnp.float32)
    ts = jnp.arange(2, dtype=jnp.int32)
    ids = jnp.zeros((2, 2), jnp.int32)
    text = reference._call.lower(rj, x, y, m, carry, ts, ids).as_text()
    main = re.search(r"func\.func public @main\((.*?)\) ->", text).group(1)
    args = re.split(r", (?=%arg\d+:)", main)
    donated = ["tf.aliasing_output" in a or "jax.buffer_donor" in a
               for a in args]
    n = len(jax.tree_util.tree_leaves(carry))
    assert donated == [False] * 3 + [True] * n + [False] * 2
