"""The one-per-value spread kernel reads per-node state without a gather.

Inside ``place_spread_opv_kernel``'s loop the column heads come out of the
node-minor ``[J, N]`` planes by a one-hot select (``score._column_heads``)
and the per-value boost / allowance tables through a ``[B, V, N]``
membership compare (``score._value_reads``). The gather forms those two
replaced left the package; they live on here as the reference:

- the new reads equal the gathers bit for bit (``uint32`` view) on random
  planes with +-inf, ``jn == max_j`` and value-less nodes;
- the whole kernel, through ``PlacementKernel.place``, returns the rows and
  ``uint32`` scores (overflow slots included) that the parent commit
  (c2ca2e0, the gather form) returned on this CPU backend, recorded in
  ``opv_dense_reads_parent.json``, and the ones it returns with the gathers
  patched back in;
- no gather and no dynamic slice over the node or the column axis is left
  in the traced loop body.

A sum of one selected value and zeros is exact for every value but -0.0
(which would read +0.0); no plane or table holds one (each is a sum that
starts from +0.0), and the fixtures draw none.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.device import score
from nomad_tpu.device.score import (
    BLOCK_EVEN_SPREAD,
    BLOCK_TARGET_SPREAD,
    PlacementKernel,
)

from test_value_scan import blocks_of, make_ask, make_cluster

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "opv_dense_reads_parent.json",
)


# -- the gather forms (the parent's reads), kept as the reference ------------


def gather_heads(num, den, fits, jn):
    """Column heads of the ``[N, J]`` planes by ``take_along_axis``."""
    max_j = num.shape[1]
    head_j = jnp.minimum(jn, max_j - 1)
    gather = lambda plane: jnp.take_along_axis(
        plane, head_j[:, None], axis=1
    )[:, 0]
    return gather(num), gather(den), gather(fits) & (jn < max_j)


def gather_tables(vids, tbl, allow):
    """``[B, N]`` reads of the ``[B, V]`` tables by ``take_along_axis``."""
    safe_vids = jnp.maximum(vids, 0)
    return (
        jnp.take_along_axis(tbl, safe_vids, axis=1),
        jnp.take_along_axis(allow, safe_vids, axis=1),
    )


def bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


# -- the reads alone ----------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_j", [16, 24])
def test_column_heads_equal_the_gather(max_j, seed):
    rng = np.random.default_rng(100 * max_j + seed)
    n = 1024
    num = rng.normal(size=(n, max_j)).astype(np.float32)
    den = rng.integers(1, 5, size=(n, max_j)).astype(np.float32)
    num[rng.random((n, max_j)) < 0.05] = np.inf
    num[rng.random((n, max_j)) < 0.05] = -np.inf
    fits = rng.random((n, max_j)) < 0.6
    # every column position, the last one, one past it (jn == max_j: the
    # head stays on the last column and stops fitting) and beyond
    jn = rng.integers(0, max_j + 3, size=n).astype(np.int32)
    jn[:8] = max_j
    jn[8:16] = max_j - 1
    jn[16:24] = 0

    want = jax.jit(gather_heads)(num, den, fits, jn)
    got = jax.jit(score._column_heads)(num.T, den.T, fits.T, jn)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(bits(g), bits(w))
    assert not np.asarray(got[2])[:8].any()


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("nv", [8, 32])
@pytest.mark.parametrize("nb", [1, 2])
def test_value_reads_equal_the_gather(nb, nv, seed):
    rng = np.random.default_rng(1000 * nb + 10 * nv + seed)
    n = 1024
    vids = rng.integers(-1, nv, size=(nb, n)).astype(np.int32)
    vids[:, :16] = -1
    vids[:, 16:32] = nv - 1
    tbl = rng.normal(size=(nb, nv)).astype(np.float32)
    tbl[:, 0] = -1.0
    tbl[rng.random((nb, nv)) < 0.1] = np.inf  # never selected unharmed
    allow = rng.random((nb, nv)) < 0.5

    def dense(vids, tbl, allow):
        member = vids[:, None, :] == jnp.arange(nv)[None, :, None]
        return score._value_reads(member, tbl, allow)

    has_value = vids >= 0
    want = jax.jit(gather_tables)(vids, tbl, allow)
    got = jax.jit(dense)(vids, tbl, allow)
    # the kernel overwrites both reads where a node has no value
    for w, g, fill in zip(want, got, (np.float32(-1.0), True)):
        np.testing.assert_array_equal(
            bits(np.where(has_value, g, fill)),
            bits(np.where(has_value, w, fill)),
        )
    assert not np.asarray(got[0])[~has_value].any()
    assert not np.asarray(got[1])[~has_value].any()


# -- the whole kernel ---------------------------------------------------------


def _even(ct, n, nv, every_valueless=0, counts0=None, weight=1.0):
    vids = (np.arange(n) % nv).astype(np.int32)
    if every_valueless:
        vids[::every_valueless] = -1
    c0 = np.zeros(nv) if counts0 is None else np.asarray(counts0, float)
    return (BLOCK_EVEN_SPREAD, vids, c0, None, None, weight)


def fixture_c2m_like():
    """2,000 nodes, 25 values, 250 instances, affinity, value-less and
    ineligible nodes (the c2m cell's job on a fifth of its fleet)."""
    n = 2000
    ct = make_cluster(n, seed=3, load_max=0.7)
    a = make_ask(
        ct, 250, seed=5, cpu=250, mem=256, affinities=True,
        blocks=blocks_of(ct, [_even(ct, n, 25, every_valueless=37)]),
    )
    a.eligible[:n:11] = False
    return ct, [a], {}


def fixture_even_and_target():
    """700 nodes, an even block over 8 values beside a target block over
    4 (B = 2), 120 instances."""
    n = 700
    ct = make_cluster(n, seed=7)
    target = (
        BLOCK_TARGET_SPREAD,
        ((np.arange(n) // 3) % 4).astype(np.int32),
        np.array([3.0, 0.0, 1.0, 0.0]),
        np.array([60.0, 30.0, 20.0, -1.0]),
        None,
        0.4,
    )
    a = make_ask(
        ct, 120, seed=9, cpu=500, mem=512,
        blocks=blocks_of(ct, [_even(ct, n, 8, weight=0.6), target]),
    )
    return ct, [a], {}


def fixture_small_with_counts():
    """256 nodes, 8 values with allocations of the job already counted,
    96 instances."""
    n = 256
    ct = make_cluster(n, seed=11)
    a = make_ask(
        ct, 96, seed=13, cpu=250, mem=256, affinities=True,
        blocks=blocks_of(
            ct, [_even(ct, n, 8, counts0=[2, 0, 1, 1, 0, 3, 1, 0])]
        ),
    )
    a.job_counts[:n:9] = 1
    return ct, [a], {}


def fixture_two_lanes():
    """One pass of two registrations, as a batching worker sends it: the
    group axis padded to 16, each lane on its stripe, tie-break jitter."""
    n = 700
    ct = make_cluster(n, seed=17, load_max=0.6)
    asks = [
        make_ask(
            ct, 60, seed=19 + g, cpu=250 * (g + 1), mem=256,
            affinities=bool(g),
            blocks=blocks_of(ct, [_even(ct, n, 25, every_valueless=41)]),
        )
        for g in range(2)
    ]
    return ct, asks, {"decorrelate": True, "decorrelate_salt": 3}


def fixture_one_lane_with_jitter():
    """One registration beside a deregistration: one lane with jitter,
    what an open-loop arrival of the c2m cell produces."""
    ct, asks, _ = fixture_c2m_like()
    return ct, asks, {"decorrelate": True, "decorrelate_salt": 1}


FIXTURES = {
    "c2m_like": fixture_c2m_like,
    "even_and_target": fixture_even_and_target,
    "small_with_counts": fixture_small_with_counts,
    "two_lanes": fixture_two_lanes,
    "one_lane_with_jitter": fixture_one_lane_with_jitter,
}


def run_fixture(name):
    """``[{rows, scores, overflow_rows, overflow_scores}]`` per lane, the
    scores as ``uint32``; every lane must have taken the opv kernel."""
    ct, asks, kwargs = FIXTURES[name]()
    kernel = PlacementKernel("binpack")
    assert not any(kernel._needs_exact_scan(a) for a in asks)
    out = []
    for res in kernel.place(ct, asks, **kwargs):
        out.append({
            "rows": res.node_rows.tolist(),
            "scores": res.scores.view(np.uint32).tolist(),
            "overflow_rows": res.overflow_rows.tolist(),
            "overflow_scores": res.overflow_scores.view(np.uint32).tolist(),
        })
    return out


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_kernel_returns_the_parents_rows_and_scores(name):
    with open(RECORDED) as f:
        recorded = json.load(f)
    assert recorded["commit"].startswith("c2ca2e0")
    got = run_fixture(name)
    want = recorded["fixtures"][name]
    assert len(got) == len(want)
    for lane_got, lane_want in zip(got, want):
        assert lane_got == lane_want
        assert sum(r >= 0 for r in lane_got["rows"]) > 0


@pytest.fixture
def gather_reads(monkeypatch):
    """The kernel traced with the gathers back in place of the dense
    reads (and the dense program restored afterwards)."""
    jitted = score.place_spread_opv_kernel.jitted

    def heads(num_t, den_t, fits_t, jn):
        return gather_heads(num_t.T, den_t.T, fits_t.T, jn)

    def tables(member, tbl, allow):
        # the membership plane back to ids; a node without a value reads
        # entry 0, as ``maximum(vids, 0)`` did
        vids = jnp.argmax(member, axis=1).astype(jnp.int32)
        return gather_tables(vids, tbl, allow)

    def patch():
        monkeypatch.setattr(score, "_column_heads", heads)
        monkeypatch.setattr(score, "_value_reads", tables)
        jitted.clear_cache()

    yield patch
    monkeypatch.undo()
    jitted.clear_cache()


@pytest.mark.parametrize("name", ["even_and_target", "two_lanes"])
def test_kernel_equals_itself_with_the_gathers_patched_in(name, gather_reads):
    dense = run_fixture(name)
    gather_reads()
    assert run_fixture(name) == dense


def _scan_bodies(jaxpr):
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name == "scan":
                yield sub
            yield from _scan_bodies(sub)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_nothing_is_indexed_dynamically_over_nodes_or_columns():
    """No gather and no dynamic slice inside the scan reads an array with
    a node or a column axis: a step reads the picks' values and scores by
    masked reduces too. (The enforce block's row of the [B, V] counts,
    ``c1[eidx]``, is still an index, over the block axis.)"""
    g, n, nb, nv, max_j = 1, 256, 2, 8, 16
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        functools.partial(
            score.place_spread_opv_kernel.jitted,
            max_j=max_j, k_seg=9, n_chunks=8,
        )
    )(
        S((n, 4), f32), S((n, 4), f32),
        asks=S((g, 4), f32), eligible=S((g, n // 8), jnp.uint8),
        job_counts=S((g, 1), i32), desired_totals=S((g,), f32),
        penalty_nodes=S((g, 1), bool), affinity_scores=S((g, n), f32),
        has_affinities=S((g,), bool), distinct_hosts=S((g,), bool),
        slot_caps=S((g, 1), f32), block_value_ids=S((g, nb, n), i32),
        block_counts0=S((g, nb, nv), f32), block_desired=S((g, nb, nv), f32),
        block_caps=S((g, nb, nv), f32), block_weights=S((g, nb), f32),
        block_kinds=S((g, nb), i32), enforce_idx=S((g,), i32),
        algorithm_spread=S((), bool), counts=S((g,), i32),
    )
    bodies = list(_scan_bodies(jaxpr.jaxpr))
    assert bodies, "the spread loop is a scan"
    indexed = [
        (eqn.primitive.name, eqn.invars[0].aval.shape)
        for body in bodies
        for eqn in _primitives(body)
        if eqn.primitive.name in ("gather", "dynamic_slice")
        and {n, max_j} & set(eqn.invars[0].aval.shape)
    ]
    assert indexed == []
