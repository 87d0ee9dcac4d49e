"""The exact scan kernel reads per-node state without a gather.

Inside ``place_value_scan_kernel``'s loop the column heads come out of the
node-minor ``[J, N]`` planes by a one-hot select (``score._column_heads``),
the per-value boost / allowance tables through a ``[B, V, N]`` membership
compare (``score._value_reads``), the picked node's values by a masked sum
over a one-hot of its row and its score as the maximum.
``place_spread_chunked_kernel`` reads its tables the same way. The gather
form left the package; it lives on here as the reference
(``gather_scan_kernel``, the parent's kernel whole):

- through ``PlacementKernel.place``, rows and ``uint32`` views of the
  scores (overflow slots included) equal what the parent commit (c109d9b,
  the gather form) returned on this CPU backend, recorded in
  ``scan_dense_reads_parent.json``;
- and what the kernel returns with the gather form patched back in;
- no gather and no dynamic slice or update over the node or the column
  axis is left in the traced loop body.

A sum of one selected value and zeros is exact for every value but -0.0
(which would read +0.0); the kernel's docstring says why that cannot reach
a score. Every check runs under ``jit``: no eager JAX pass.
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
    BLOCK_DISTINCT_CAP,
    BLOCK_EVEN_SPREAD,
    BLOCK_TARGET_SPREAD,
    PlacementKernel,
)

from test_opv_dense_reads import _primitives, _scan_bodies, gather_tables
from test_value_scan import blocks_of, make_ask, make_cluster

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "scan_dense_reads_parent.json",
)


# -- the gather form (the parent's kernel), kept as the reference ------------


@functools.partial(jax.jit, static_argnames=("max_j", "max_steps"))
def gather_scan_kernel(
    capacity, used0, asks, eligible, job_counts, desired_totals,
    penalty_nodes, affinity_scores, has_affinities, distinct_hosts,
    slot_caps, block_value_ids, block_counts0, block_desired, block_caps,
    block_weights, block_kinds, algorithm_spread, counts, max_j, max_steps,
    jitter=None,
):
    """``place_value_scan_kernel`` as c109d9b had it: five
    ``take_along_axis`` reads and two dynamic slices a step."""
    eligible, job_counts, penalty_nodes = score._unpack_lane_inputs(
        capacity, eligible, job_counts, penalty_nodes
    )

    def one_group(
        ask, elig, jc0, dt, pen, aff, has_aff, dh, caps,
        vids, c0, desired, vcaps, weights, kinds, count,
    ):
        num, den, fits = score._score_planes(
            capacity, used0, ask, elig, jc0, dt, pen, aff, has_aff, dh,
            caps, algorithm_spread, max_j, jitter=jitter,
        )
        n = num.shape[0]
        is_spread = (kinds == BLOCK_TARGET_SPREAD) | (kinds == BLOCK_EVEN_SPREAD)
        has_spread_any = jnp.any(is_spread)
        safe_vids = jnp.maximum(vids, 0)  # [B, N]

        def step(state, i):
            jn, c = state
            head_j = jnp.minimum(jn, max_j - 1)
            gather = lambda plane: jnp.take_along_axis(
                plane, head_j[:, None], axis=1
            )[:, 0]
            head_num = gather(num)
            head_den = gather(den)
            head_fit = gather(fits) & (jn < max_j)

            tbl, allow = score._block_tables(c, desired, vcaps, weights, kinds)
            per_block = jnp.take_along_axis(tbl, safe_vids, axis=1)  # [B, N]
            contrib = jnp.where(vids >= 0, per_block, -1.0)
            boost = jnp.sum(
                jnp.where(is_spread[:, None], contrib, 0.0), axis=0
            )
            allow_pb = jnp.take_along_axis(allow, safe_vids, axis=1)
            allowed = jnp.all(
                jnp.where(
                    (kinds == BLOCK_DISTINCT_CAP)[:, None] & (vids >= 0),
                    allow_pb,
                    True,
                ),
                axis=0,
            )

            spread_on = has_spread_any & (boost != 0.0)
            den_t = head_den + jnp.where(spread_on, 1.0, 0.0)
            sc = (head_num + jnp.where(spread_on, boost, 0.0)) / den_t
            sc = jnp.where(head_fit & allowed, sc, -jnp.inf)

            best = jnp.argmax(sc)
            ok = (sc[best] > -jnp.inf) & (i < count)
            onehot = (jnp.arange(n) == best) & ok
            jn = jn + onehot.astype(jn.dtype)
            bumped = vids[:, best]
            c = c + jnp.where(
                (ok & (bumped >= 0))[:, None],
                jax.nn.one_hot(
                    jnp.maximum(bumped, 0), c.shape[1], dtype=c.dtype
                ),
                0.0,
            )
            return (jn, c), (
                jnp.where(ok, best, -1).astype(jnp.int32),
                jnp.where(ok, sc[best], -jnp.inf).astype(jnp.float32),
            )

        state0 = (jnp.zeros(n, dtype=jnp.int32), c0)
        _, (choices, scores) = jax.lax.scan(
            step, state0, jnp.arange(max_steps)
        )
        return choices, scores

    return jax.vmap(one_group)(
        asks, eligible, job_counts, desired_totals, penalty_nodes,
        affinity_scores, has_affinities, distinct_hosts, slot_caps,
        block_value_ids, block_counts0, block_desired, block_caps,
        block_weights, block_kinds, counts,
    )


# -- fixtures -----------------------------------------------------------------


def _block(kind, vids, nv, counts0=None, desired=None, cap=None, weight=1.0):
    c0 = np.zeros(nv) if counts0 is None else np.asarray(counts0, float)
    des = None if desired is None else np.asarray(desired, np.float32)
    caps = None if cap is None else np.full(nv, cap, dtype=np.float32)
    return (kind, np.asarray(vids, np.int32), c0, des, caps, weight)


def _round_robin(n, nv, every_valueless=0):
    vids = (np.arange(n) % nv).astype(np.int32)
    if every_valueless:
        vids[::every_valueless] = -1
    return vids


def fixture_even():
    """300 nodes, an even block over 5 values, 20 instances."""
    n = 300
    ct = make_cluster(n, seed=21)
    a = make_ask(
        ct, 20, seed=1, cpu=500, mem=512,
        blocks=blocks_of(
            ct, [_block(BLOCK_EVEN_SPREAD, _round_robin(n, 5), 5)]
        ),
    )
    return ct, [a], {}


def fixture_target_with_untargeted_value():
    """200 nodes, a target block over 3 values of which the last has no
    target (flat -1), counts already on the first."""
    n = 200
    ct = make_cluster(n, seed=22)
    a = make_ask(
        ct, 12, seed=2, cpu=250, mem=256,
        blocks=blocks_of(ct, [_block(
            BLOCK_TARGET_SPREAD, _round_robin(n, 3), 3,
            counts0=[2, 0, 1], desired=[8.0, 6.0, -1.0],
        )]),
    )
    return ct, [a], {}


def fixture_two_blocks_and_affinity():
    """400 nodes, a target block over 4 racks beside an even block over 2
    datacenters (B = 2), node affinities, 24 instances."""
    n = 400
    ct = make_cluster(n, seed=23, load_max=0.7)
    a = make_ask(
        ct, 24, seed=3, cpu=500, mem=512, affinities=True,
        blocks=blocks_of(ct, [
            _block(BLOCK_TARGET_SPREAD, _round_robin(n, 4), 4,
                   desired=[9.0, 6.0, 6.0, 3.0], weight=0.75),
            _block(BLOCK_EVEN_SPREAD, (np.arange(n) // 7) % 2, 2,
                   weight=0.25),
        ]),
    )
    return ct, [a], {}


def fixture_cap_with_existing_counts():
    """64 nodes, a distinct_property cap of 3 a value with 6 of the 12
    places already taken: the lane runs out of allowed nodes after 6 of
    its 12 instances."""
    n = 64
    ct = make_cluster(n, seed=24)
    a = make_ask(
        ct, 12, seed=4, cpu=250, mem=256,
        blocks=blocks_of(ct, [_block(
            BLOCK_DISTINCT_CAP, _round_robin(n, 4), 4,
            counts0=[3, 1, 0, 2], cap=3.0, weight=0.0,
        )]),
    )
    return ct, [a], {}


def fixture_cap_at_a_large_count():
    """300 nodes, 80 instances under a cap of 30 a value: a cap block
    keeps a group on this kernel at any count (128 steps, J = 80)."""
    n = 300
    ct = make_cluster(n, seed=25, load_max=0.3)
    a = make_ask(
        ct, 80, seed=5, cpu=100, mem=128, affinities=True,
        blocks=blocks_of(ct, [_block(
            BLOCK_DISTINCT_CAP, _round_robin(n, 4, every_valueless=29), 4,
            counts0=[5, 0, 12, 1], cap=30.0, weight=0.0,
        )]),
    )
    return ct, [a], {}


def fixture_spread_and_cap():
    """240 nodes, an even block and a cap of 9 over the same 3 values:
    27 of the 30 instances can land."""
    n = 240
    ct = make_cluster(n, seed=26)
    vids = _round_robin(n, 3)
    a = make_ask(
        ct, 30, seed=6, cpu=500, mem=512,
        blocks=blocks_of(ct, [
            _block(BLOCK_EVEN_SPREAD, vids, 3),
            _block(BLOCK_DISTINCT_CAP, vids, 3, cap=9.0, weight=0.0),
        ]),
    )
    return ct, [a], {}


def _rollout_round(seed):
    """A service's rollout round on a fifth of the rollout cell's fleet:
    2,000 nodes, 25 racks, 225 live allocations of the job counted on the
    racks and on their nodes, 25 replacements, affinity, value-less and
    ineligible nodes."""
    n = 2000
    ct = make_cluster(n, seed=seed, load_max=0.7)
    a = make_ask(
        ct, 25, seed=seed + 1, cpu=250, mem=256, affinities=True,
        blocks=blocks_of(ct, [_block(
            BLOCK_EVEN_SPREAD, _round_robin(n, 25, every_valueless=37), 25,
            counts0=[9] * 25,
        )]),
    )
    a.desired_total = 250
    a.job_counts[np.arange(225) * 8 + 3] = 1
    a.eligible[:n:11] = False
    return ct, a


def fixture_rollout_round():
    ct, a = _rollout_round(27)
    return ct, [a], {}


def fixture_rollout_round_with_jitter():
    """The same round beside another eval of its pass: one lane, jitter."""
    ct, a = _rollout_round(27)
    return ct, [a], {"decorrelate": True, "decorrelate_salt": 5}


def fixture_node_fills_to_max_j():
    """Two nodes, one of them with room for three more: the other takes
    16 instances, all of its columns (``jn == max_j``), and the 32 steps
    (16 asked + 16 overflow) end after 19 picks."""
    ct = make_cluster(2, seed=28, load_max=0.0)
    ct.used[1, :2] = ct.capacity[1, :2] - np.array([330.0, 330.0])
    a = make_ask(
        ct, 16, seed=8, cpu=100, mem=100,
        blocks=blocks_of(
            ct, [_block(BLOCK_EVEN_SPREAD, [0, 1], 2, counts0=[1, 4])]
        ),
    )
    return ct, [a], {}


def fixture_fuzzed_values():
    """180 nodes whose values are drawn at random, a quarter without one,
    target block with random counts and targets, affinities."""
    n = 180
    rng = np.random.default_rng(29)
    ct = make_cluster(n, seed=29, load_max=0.6)
    vids = rng.integers(-1, 6, n)
    vids[rng.random(n) < 0.25] = -1
    a = make_ask(
        ct, 28, seed=9, cpu=1500, mem=512, affinities=True,
        blocks=blocks_of(ct, [_block(
            BLOCK_TARGET_SPREAD, vids, 6,
            counts0=rng.integers(0, 4, 6),
            desired=rng.uniform(1, 9, 6), weight=1.0,
        )]),
    )
    return ct, [a], {}


def fixture_two_lanes():
    """One pass of two evals: the group axis padded to 16 with dummy
    lanes, each lane on its stripe, tie-break jitter; a rollout round
    beside a capped group (B and V padded to the wider lane's)."""
    n = 700
    ct = make_cluster(n, seed=30, load_max=0.6)
    spread = make_ask(
        ct, 25, seed=10, cpu=250, mem=256, affinities=True,
        blocks=blocks_of(ct, [_block(
            BLOCK_EVEN_SPREAD, _round_robin(n, 25, every_valueless=41), 25,
            counts0=[9] * 25,
        )]),
    )
    vids = _round_robin(n, 4)
    capped = make_ask(
        ct, 10, seed=11, cpu=500, mem=512,
        blocks=blocks_of(ct, [
            _block(BLOCK_EVEN_SPREAD, vids, 4, counts0=[1, 0, 2, 0]),
            _block(BLOCK_DISTINCT_CAP, vids, 4, cap=2.0, weight=0.0),
        ]),
    )
    return ct, [spread, capped], {"decorrelate": True, "decorrelate_salt": 3}


def fixture_chunked_target():
    """700 nodes, 120 instances under two target blocks and no even one:
    ``place_spread_chunked_kernel`` (its table reads share
    ``_value_reads``)."""
    n = 700
    ct = make_cluster(n, seed=31)
    a = make_ask(
        ct, 120, seed=12, cpu=500, mem=512, affinities=True,
        blocks=blocks_of(ct, [
            _block(BLOCK_TARGET_SPREAD, _round_robin(n, 4, 43), 4,
                   counts0=[3, 0, 1, 0], desired=[60.0, 30.0, 20.0, -1.0],
                   weight=0.6),
            _block(BLOCK_TARGET_SPREAD, (np.arange(n) // 3) % 2, 2,
                   desired=[80.0, 40.0], weight=0.4),
        ]),
    )
    return ct, [a], {}


SCAN_FIXTURES = {
    "even": fixture_even,
    "target_with_untargeted_value": fixture_target_with_untargeted_value,
    "two_blocks_and_affinity": fixture_two_blocks_and_affinity,
    "cap_with_existing_counts": fixture_cap_with_existing_counts,
    "cap_at_a_large_count": fixture_cap_at_a_large_count,
    "spread_and_cap": fixture_spread_and_cap,
    "rollout_round": fixture_rollout_round,
    "rollout_round_with_jitter": fixture_rollout_round_with_jitter,
    "node_fills_to_max_j": fixture_node_fills_to_max_j,
    "fuzzed_values": fixture_fuzzed_values,
    "two_lanes": fixture_two_lanes,
}
CHUNKED_FIXTURES = {"chunked_target": fixture_chunked_target}
FIXTURES = {**SCAN_FIXTURES, **CHUNKED_FIXTURES}


def run_fixture(name):
    """``[{rows, scores, overflow_rows, overflow_scores}]`` per lane, the
    scores as ``uint32``; every lane must have taken the kernel the
    fixture is for."""
    ct, asks, kwargs = FIXTURES[name]()
    kernel = PlacementKernel("binpack")
    for a in asks:
        if name in SCAN_FIXTURES:
            assert kernel._needs_exact_scan(a)
        else:  # no even block either: that one goes to the opv kernel
            assert not kernel._needs_exact_scan(a)
            assert not (a.blocks.kinds == BLOCK_EVEN_SPREAD).any()
    out = []
    for res in kernel.place(ct, asks, **kwargs):
        out.append({
            "rows": res.node_rows.tolist(),
            "scores": res.scores.view(np.uint32).tolist(),
            "overflow_rows": res.overflow_rows.tolist(),
            "overflow_scores": res.overflow_scores.view(np.uint32).tolist(),
        })
    return out


# -- the whole kernels --------------------------------------------------------


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_kernel_returns_the_parents_rows_and_scores(name):
    with open(RECORDED) as f:
        recorded = json.load(f)
    assert recorded["commit"].startswith("c109d9b")
    got = run_fixture(name)
    want = recorded["fixtures"][name]
    assert len(got) == len(want)
    for lane_got, lane_want in zip(got, want):
        assert lane_got == lane_want
        assert sum(r >= 0 for r in lane_got["rows"]) > 0


def test_the_fixtures_reach_the_corners_they_name():
    """A node's every column taken and a lane that ends early, as the
    recorded results show them."""
    with open(RECORDED) as f:
        fixtures = json.load(f)["fixtures"]
    (lane,) = fixtures["node_fills_to_max_j"]
    picks = lane["rows"] + lane["overflow_rows"]
    assert picks.count(0) == 16 and picks.count(1) == 3
    assert picks[19:] == [-1] * 13
    (lane,) = fixtures["cap_with_existing_counts"]
    assert sum(r >= 0 for r in lane["rows"]) == 6
    assert all(r < 0 for r in lane["overflow_rows"])
    (lane,) = fixtures["spread_and_cap"]
    assert sum(r >= 0 for r in lane["rows"]) == 27
    assert len(fixtures["two_lanes"]) == 2


@pytest.fixture
def gather_form(monkeypatch):
    """The gather form patched back in: the parent's scan kernel whole,
    and the table gathers in place of ``_value_reads`` for the chunked
    kernel (the dense programs restored afterwards)."""
    chunked = score.place_spread_chunked_kernel.jitted

    def tables(member, tbl, allow):
        # the membership plane back to ids; a node without a value reads
        # entry 0, as ``maximum(vids, 0)`` did
        vids = jnp.argmax(member, axis=1).astype(jnp.int32)
        return gather_tables(vids, tbl, allow)

    def patch():
        monkeypatch.setattr(
            score, "place_value_scan_kernel", gather_scan_kernel
        )
        monkeypatch.setattr(score, "_value_reads", tables)
        chunked.clear_cache()

    yield patch
    monkeypatch.undo()
    chunked.clear_cache()


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_kernel_equals_itself_with_the_gathers_patched_in(name, gather_form):
    dense = run_fixture(name)
    gather_form()
    assert run_fixture(name) == dense


# -- the traced loop bodies ---------------------------------------------------


def _lane_shapes(g, n, nb, nv):
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    return (S((n, 4), f32), S((n, 4), f32)), dict(
        asks=S((g, 4), f32), eligible=S((g, n // 8), jnp.uint8),
        job_counts=S((g, 1), i32), desired_totals=S((g,), f32),
        penalty_nodes=S((g, 1), bool), affinity_scores=S((g, n), f32),
        has_affinities=S((g,), bool), distinct_hosts=S((g,), bool),
        slot_caps=S((g, 1), f32), block_value_ids=S((g, nb, n), i32),
        block_counts0=S((g, nb, nv), f32), block_desired=S((g, nb, nv), f32),
        block_caps=S((g, nb, nv), f32), block_weights=S((g, nb), f32),
        block_kinds=S((g, nb), i32), algorithm_spread=S((), bool),
        counts=S((g,), i32),
    )


def _indexed_in_scan(kernel, g, n, max_j, **static):
    """``(primitive, operand shape)`` of every gather, dynamic slice and
    dynamic update inside the kernel's scan of which an operand, an index
    or the result has a node or a column axis."""
    args, kwargs = _lane_shapes(g, n, nb=2, nv=8)
    jaxpr = jax.make_jaxpr(
        functools.partial(kernel, max_j=max_j, **static)
    )(*args, **kwargs)
    bodies = list(_scan_bodies(jaxpr.jaxpr))
    assert bodies, "the placement loop is a scan"
    axes = {n, max_j, n * max_j}
    return [
        (eqn.primitive.name, eqn.invars[0].aval.shape)
        for body in bodies
        for eqn in _primitives(body)
        if eqn.primitive.name
        in ("gather", "dynamic_slice", "dynamic_update_slice")
        and any(
            axes & set(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
        )
    ]


@pytest.mark.parametrize("g", [1, 16])
def test_nothing_is_indexed_dynamically_over_nodes_or_columns(g):
    """No gather, dynamic slice or dynamic update inside the scan touches
    an array with a node or a column axis: a step reads the pick's values
    and score by masked reduces too."""
    indexed = _indexed_in_scan(
        score.place_value_scan_kernel.jitted, g, n=256, max_j=16,
        max_steps=64,
    )
    assert indexed == []


def test_the_walk_finds_the_gather_forms_reads():
    """The same walk over the reference finds its reads (under ``vmap``
    the dynamic slices are gathers too), so an empty list above is a
    finding."""
    n, max_j = 256, 16
    indexed = _indexed_in_scan(
        gather_scan_kernel, 1, n=n, max_j=max_j, max_steps=64
    )
    assert sorted(shape for _, shape in indexed) == sorted(
        [(1, n, max_j)] * 3  # the column heads
        + [(1, 2, 8)] * 2  # boost and allowance tables
        + [(1, 2, n)]  # vids[:, best]
        + [(1, n)] * 2  # score[best], twice
    )


@pytest.mark.parametrize("g", [1, 16])
def test_chunked_kernel_reads_its_tables_without_a_gather(g):
    """``place_spread_chunked_kernel``'s loop keeps the reads of the nodes
    its top-k picked (their true scores out of the flattened plane, their
    values); the two ``[B, N]`` reads of the ``[B, V]`` tables are gone."""
    n, max_j = 256, 16
    indexed = _indexed_in_scan(
        score.place_spread_chunked_kernel.jitted, g, n=n, max_j=max_j,
        chunk=score.CHUNK, n_chunks=4,
    )
    assert sorted(indexed) == [
        ("gather", (g, 2, n)), ("gather", (g, n * max_j)),
    ]
