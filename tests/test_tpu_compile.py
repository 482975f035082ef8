"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a primitive Mosaic cannot lower, a misaligned tile, a
VMEM overrun.  These tests compile each kernel with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology — no chip is attached
and nothing runs — and check that the compiled program holds the kernel
(``tpu_custom_call``); the Ward merge loop, that it updates its
distance matrix in place with no whole-matrix copy.  The topology is
described inside a fixture, so a worker that cannot describe one skips
these tests, and no other module or worker loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_stats import fused_stats_pallas
from repro.kernels.gram_update import (cached_feature_step_pallas,
                                       cached_selection_step_pallas,
                                       gram_row_update_pallas)
from repro.kernels.pairwise import hics_selection_step_pallas

N, K, SEEDS = 512, 10, 4
WIDTHS = (10, 32_768)        # paper-cnn head; a vocabulary-width head
T = 0.0025

_I32 = jnp.int32


def _cached(x, d, s, i):
    return cached_selection_step_pallas(x, d, s, i, T, interpret=False)


#: name -> (kernel, argument shapes at head width c); dtype f32 unless
#: given.  The vmapped cached step is the sweep engine's shape.
CASES = {
    "fused_stats": (lambda x: fused_stats_pallas(x, T, interpret=False),
                    lambda c: [(N, c)]),
    "hics_selection_step": (
        lambda x: hics_selection_step_pallas(x, T, interpret=False),
        lambda c: [(N, c)]),
    "cached_selection_step": (
        _cached, lambda c: [(N, c), (N, N), (N, 2), ((K,), _I32)]),
    "cached_selection_step_vmapped": (
        jax.vmap(_cached),
        lambda c: [(SEEDS, N, c), (SEEDS, N, N), (SEEDS, N, 2),
                   ((SEEDS, K), _I32)]),
    **{f"gram_row_update[{ep}]": (
        lambda x, s, i, ep=ep: gram_row_update_pallas(
            x, s, i, epilogue=ep, interpret=False),
        lambda c: [(N, c), (N, 2), ((K,), _I32)])
       for ep in ("arccos", "cosine", "l2")},
    **{f"cached_feature_step[{m}]": (
        lambda x, d, s, i, m=m: cached_feature_step_pallas(
            x, d, s, i, metric=m, interpret=False),
        lambda c: [(N, c), (N, N), (N, 2), ((K,), _I32)])
       for m in ("cosine", "l2")},
}


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case, c):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple)
                                   else (s, jnp.float32)),
                                 sharding=one_chip)
            for s in shapes(c)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _loop_ops(hlo: str, shape: str) -> set:
    """Kinds of the ops producing ``shape`` inside every while loop's
    body and condition (and what they call) of an optimized HLO text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    called = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
    todo = [c for lines in comps.values() for ln in lines
            if " while(" in ln for c in called.findall(ln)]
    seen, kinds = set(), set()
    op = re.compile(r"= %s\{[^}]*\} ([a-z\-]+)\(" % re.escape(shape))
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ln in comps[c]:
            todo += called.findall(ln)
            m = op.search(ln)
            if m:
                kinds.add(m.group(1))
    return kinds


def test_ward_merge_loop_is_copy_free_for_v5e(one_chip):
    """At the cross-device cell's N, the only op on the (N, N) matrix
    inside the merge loop is the in-place row update: no copy, no
    relayout, no whole-matrix reduction."""
    from repro.core.clustering import agglomerate_device
    n = 2000
    d = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    hlo = jax.jit(lambda d: agglomerate_device(d, 10, precomputed=True)
                  ).lower(d).compile().as_text()
    kinds = _loop_ops(hlo, f"f32[{n},{n}]")
    assert kinds - {"parameter", "get-tuple-element", "tuple"} == {
        "dynamic-update-slice"}
