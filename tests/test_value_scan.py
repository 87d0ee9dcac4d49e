"""Value-scan kernel correctness: the gather-scan placement path (spread +
distinct_property groups) against a naive per-step NumPy greedy oracle
re-derived independently from the reference's scoring rules
(scheduler/spread.go:110-228, scheduler/feasible.go:604-707,
nomad/structs/funcs.go:236-256, scheduler/rank.go:740-767).

The oracle recomputes every node's score from scratch each step — no
precomputed planes, no gathers — so any error in the kernel's hoisted
[N, J] planes or per-value boost tables shows up as divergence.
"""

import numpy as np
import pytest

from nomad_tpu.device.flatten import ClusterTensors, GroupAsk, ValueBlocks, node_bucket
from nomad_tpu.device.score import (
    BLOCK_DISTINCT_CAP,
    BLOCK_EVEN_SPREAD,
    BLOCK_TARGET_SPREAD,
    PlacementKernel,
    repair_batch_conflicts,
)

BINPACK_MAX = 18.0


def make_cluster(n_nodes, seed=0, load_max=0.5):
    rng = np.random.default_rng(seed)
    pn = node_bucket(n_nodes)
    capacity = np.zeros((pn, 4), dtype=np.float32)
    capacity[:n_nodes, 0] = rng.choice([4000, 8000, 16000], n_nodes)
    capacity[:n_nodes, 1] = rng.choice([8192, 16384, 32768], n_nodes)
    capacity[:n_nodes, 2] = 100 * 1024
    capacity[:n_nodes, 3] = 1000
    used = np.zeros_like(capacity)
    used[:n_nodes, :2] = capacity[:n_nodes, :2] * rng.uniform(
        0, load_max, (n_nodes, 1)
    ).astype(np.float32)
    ready = np.zeros(pn, dtype=bool)
    ready[:n_nodes] = True
    return ClusterTensors(
        node_ids=[f"n{i}" for i in range(n_nodes)],
        index=1, num_nodes=n_nodes, capacity=capacity, used=used,
        ready=ready,
        dc_ids=np.zeros(pn, dtype=np.int32),
        class_ids=np.zeros(pn, dtype=np.int32),
        dc_vocab={"dc1": 0}, class_vocab={"c": 0}, class_rep=[0],
        node_row={f"n{i}": i for i in range(n_nodes)},
    )


def make_ask(ct, count, seed=0, cpu=500, mem=512, affinities=False,
             blocks=None):
    rng = np.random.default_rng(seed)
    pn = ct.padded_n
    return GroupAsk(
        job_id=f"job-{seed}", tg_name="web", count=count,
        desired_total=count,
        ask=np.array([cpu, mem, 300.0, 0.0], dtype=np.float32),
        eligible=ct.ready.copy(),
        job_counts=np.zeros(pn, dtype=np.int32),
        penalty_nodes=np.zeros(pn, dtype=bool),
        affinity_scores=(
            rng.uniform(-1, 1, pn).astype(np.float32)
            if affinities else np.zeros(pn, dtype=np.float32)
        ),
        has_affinities=affinities,
        distinct_hosts=False,
        blocks=blocks,
    )


def blocks_of(ct, specs):
    """specs: list of (kind, value_ids[N], counts0[V], desired[V]|None,
    cap|None, weight)."""
    nb = len(specs)
    nv = max(len(s[2]) for s in specs)
    pn = ct.padded_n
    value_ids = np.full((nb, pn), -1, dtype=np.int32)
    counts0 = np.zeros((nb, nv), dtype=np.float32)
    desired = np.full((nb, nv), -1.0, dtype=np.float32)
    caps = np.full((nb, nv), np.inf, dtype=np.float32)
    weights = np.zeros(nb, dtype=np.float32)
    kinds = np.zeros(nb, dtype=np.int32)
    for b, (kind, vids, c0, des, cap, w) in enumerate(specs):
        value_ids[b, : len(vids)] = vids
        counts0[b, : len(c0)] = c0
        if des is not None:
            desired[b, : len(des)] = des
        if cap is not None:
            caps[b, : len(c0)] = cap
        weights[b] = w
        kinds[b] = kind
    return ValueBlocks(
        value_ids=value_ids, counts0=counts0, desired=desired,
        caps=caps, weights=weights, kinds=kinds,
    )


# -- the independent oracle --------------------------------------------------


def even_boost(cur, counts):
    """spread.go:178-228 evenSpreadScoreBoost, min over positive counts."""
    pos = counts[counts > 0]
    if pos.size == 0:
        return 0.0
    minc, maxc = pos.min(), pos.max()
    if cur != minc:
        return (minc - cur) / minc
    if minc == maxc:
        return -1.0
    return (maxc - minc) / minc


def naive_greedy(ct, a):
    """Stepwise greedy with full per-step rescoring."""
    capacity = ct.capacity
    used = ct.used.copy()
    pn = ct.padded_n
    placed = np.zeros(pn, dtype=np.int64)
    blocks = a.blocks
    counts = blocks.counts0.copy() if blocks is not None else None
    choices, scores = [], []
    for _ in range(a.count):
        best, best_score = -1, -np.inf
        for n in range(pn):
            if not a.eligible[n]:
                continue
            prop = used[n] + a.ask
            if not np.all(prop <= capacity[n]):
                continue
            # distinct caps
            if blocks is not None:
                capped = False
                for b in range(blocks.num_blocks):
                    if blocks.kinds[b] != BLOCK_DISTINCT_CAP:
                        continue
                    v = blocks.value_ids[b, n]
                    if v < 0 or counts[b, v] >= blocks.caps[b, v]:
                        capped = True
                        break
                if capped:
                    continue
            free = np.where(
                capacity[n] > 0, (capacity[n] - prop) / capacity[n], 1.0
            )
            binpack = min(
                max(20.0 - 10.0 ** free[0] - 10.0 ** free[1], 0.0),
                BINPACK_MAX,
            ) / BINPACK_MAX
            coll = placed[n]  # job_counts 0 in these fixtures
            comps = [binpack]
            if coll > 0:
                comps.append(-(coll + 1.0) / max(a.desired_total, 1))
            if a.has_affinities:
                comps.append(float(a.affinity_scores[n]))
            boost = 0.0
            if blocks is not None:
                for b in range(blocks.num_blocks):
                    k = blocks.kinds[b]
                    v = blocks.value_ids[b, n]
                    if k == BLOCK_TARGET_SPREAD:
                        if v < 0:
                            boost += -1.0
                        else:
                            d = blocks.desired[b, v]
                            if d <= 0:
                                boost += -1.0
                            else:
                                boost += (
                                    (d - (counts[b, v] + 1.0)) / d
                                ) * blocks.weights[b]
                    elif k == BLOCK_EVEN_SPREAD:
                        if v < 0:
                            boost += -1.0
                        else:
                            boost += even_boost(counts[b, v], counts[b])
                if blocks.has_spreads and boost != 0.0:
                    comps.append(boost)
            score = sum(comps) / len(comps)
            if score > best_score:
                best_score = score
                best = n
        if best < 0:
            choices.append(-1)
            scores.append(-np.inf)
            continue
        choices.append(best)
        scores.append(best_score)
        used[best] += a.ask
        placed[best] += 1
        if blocks is not None:
            for b in range(blocks.num_blocks):
                v = blocks.value_ids[b, best]
                if v >= 0:
                    counts[b, v] += 1
    return np.array(choices), np.array(scores)


def run_kernel(ct, a):
    res = PlacementKernel("binpack").place(ct, [a])[0]
    return res.node_rows, res.scores


def assert_against_oracle(ct, a, atol=1e-4):
    rows_k, scores_k = run_kernel(ct, a)
    rows_o, scores_o = naive_greedy(ct, a)
    np.testing.assert_array_equal(rows_k, rows_o)
    ok = rows_o >= 0
    np.testing.assert_allclose(scores_k[ok], scores_o[ok], atol=atol)


def test_even_spread_matches_oracle():
    ct = make_cluster(24, seed=1)
    vids = (np.arange(ct.padded_n) % 4).astype(np.int32)
    b = blocks_of(ct, [(BLOCK_EVEN_SPREAD, vids,
                        np.zeros(4, dtype=np.float32), None, None, 1.0)])
    assert_against_oracle(ct, make_ask(ct, count=12, blocks=b))


def test_even_spread_with_existing_counts():
    ct = make_cluster(24, seed=2)
    vids = (np.arange(ct.padded_n) % 3).astype(np.int32)
    c0 = np.array([5.0, 1.0, 0.0], dtype=np.float32)
    b = blocks_of(ct, [(BLOCK_EVEN_SPREAD, vids, c0, None, None, 1.0)])
    assert_against_oracle(ct, make_ask(ct, count=10, blocks=b))


def test_target_spread_matches_oracle():
    ct = make_cluster(20, seed=3)
    vids = (np.arange(ct.padded_n) % 2).astype(np.int32)
    desired = np.array([7.0, 3.0], dtype=np.float32)  # 70/30 split
    b = blocks_of(ct, [(BLOCK_TARGET_SPREAD, vids,
                        np.zeros(2, dtype=np.float32), desired, None, 1.0)])
    a = make_ask(ct, count=10, blocks=b)
    assert_against_oracle(ct, a)
    # the 70/30 split should be honored
    rows, _ = run_kernel(ct, a)
    placed_v0 = int((vids[rows[rows >= 0]] == 0).sum())
    assert placed_v0 == 7


def test_target_spread_untargeted_value_penalty():
    ct = make_cluster(16, seed=4)
    vids = (np.arange(ct.padded_n) % 3).astype(np.int32)
    # value 2 has no target and no implicit → flat −1 (spread.go:145-152)
    desired = np.array([3.0, 3.0, -1.0], dtype=np.float32)
    b = blocks_of(ct, [(BLOCK_TARGET_SPREAD, vids,
                        np.zeros(3, dtype=np.float32), desired, None, 1.0)])
    a = make_ask(ct, count=6, blocks=b)
    assert_against_oracle(ct, a)
    rows, _ = run_kernel(ct, a)
    assert not np.any(vids[rows[rows >= 0]] == 2)


def test_multi_block_spread_matches_oracle():
    """Two spread blocks with relative weights (multi-block was once
    scored against the first block only)."""
    ct = make_cluster(24, seed=5)
    vids_rack = (np.arange(ct.padded_n) % 4).astype(np.int32)
    vids_dc = (np.arange(ct.padded_n) % 2).astype(np.int32)
    b = blocks_of(ct, [
        (BLOCK_TARGET_SPREAD, vids_rack, np.zeros(4, dtype=np.float32),
         np.array([3.0, 3.0, 3.0, 3.0], dtype=np.float32), None, 0.75),
        (BLOCK_EVEN_SPREAD, vids_dc, np.zeros(4, dtype=np.float32),
         None, None, 0.25),
    ])
    assert_against_oracle(ct, make_ask(ct, count=12, blocks=b))


def test_multi_block_with_affinity_matches_oracle():
    ct = make_cluster(24, seed=6)
    vids = (np.arange(ct.padded_n) % 4).astype(np.int32)
    b = blocks_of(ct, [
        (BLOCK_EVEN_SPREAD, vids, np.zeros(4, dtype=np.float32),
         None, None, 1.0),
    ])
    assert_against_oracle(
        ct, make_ask(ct, count=10, blocks=b, affinities=True)
    )


def test_distinct_property_cap_enforced():
    """feasible.go:604: at most allowed_count allocs per property value,
    counting in-flight placements."""
    ct = make_cluster(16, seed=7)
    vids = (np.arange(ct.padded_n) % 4).astype(np.int32)
    caps = np.full(4, 2.0, dtype=np.float32)
    b = blocks_of(ct, [(BLOCK_DISTINCT_CAP, vids,
                        np.zeros(4, dtype=np.float32), None, caps, 0.0)])
    a = make_ask(ct, count=12, blocks=b)
    assert_against_oracle(ct, a)
    rows, _ = run_kernel(ct, a)
    placed = rows[rows >= 0]
    assert len(placed) == 8  # 4 values × cap 2
    for v in range(4):
        assert int((vids[placed] == v).sum()) == 2


def test_distinct_property_existing_counts():
    ct = make_cluster(16, seed=8)
    vids = (np.arange(ct.padded_n) % 2).astype(np.int32)
    c0 = np.array([2.0, 0.0], dtype=np.float32)  # value 0 already full
    caps = np.full(2, 2.0, dtype=np.float32)
    b = blocks_of(ct, [(BLOCK_DISTINCT_CAP, vids, c0, None, caps, 0.0)])
    a = make_ask(ct, count=4, blocks=b)
    assert_against_oracle(ct, a)
    rows, _ = run_kernel(ct, a)
    placed = rows[rows >= 0]
    assert len(placed) == 2
    assert np.all(vids[placed] == 1)


def test_spread_plus_distinct_cap_combined():
    ct = make_cluster(24, seed=9)
    vids = (np.arange(ct.padded_n) % 3).astype(np.int32)
    b = blocks_of(ct, [
        (BLOCK_EVEN_SPREAD, vids, np.zeros(3, dtype=np.float32),
         None, None, 1.0),
        (BLOCK_DISTINCT_CAP, vids, np.zeros(3, dtype=np.float32),
         None, np.full(3, 3.0, dtype=np.float32), 0.0),
    ])
    a = make_ask(ct, count=12, blocks=b)
    assert_against_oracle(ct, a)
    rows, _ = run_kernel(ct, a)
    placed = rows[rows >= 0]
    assert len(placed) == 9  # capped at 3 per value


def test_fuzz_value_scan_vs_oracle():
    rng = np.random.default_rng(42)
    for trial in range(8):
        n = int(rng.integers(8, 40))
        ct = make_cluster(n, seed=trial, load_max=0.6)
        nv = int(rng.integers(2, 6))
        vids = rng.integers(-1, nv, ct.padded_n).astype(np.int32)
        kind = [BLOCK_EVEN_SPREAD, BLOCK_TARGET_SPREAD][trial % 2]
        desired = (
            rng.uniform(1, 6, nv).astype(np.float32)
            if kind == BLOCK_TARGET_SPREAD else None
        )
        c0 = rng.integers(0, 4, nv).astype(np.float32)
        b = blocks_of(ct, [(kind, vids, c0, desired, None, 1.0)])
        a = make_ask(
            ct,
            count=int(rng.integers(2, 20)),
            seed=trial,
            cpu=float(rng.choice([250, 500, 1500])),
            blocks=b,
            affinities=bool(rng.integers(0, 2)),
        )
        assert_against_oracle(ct, a)


def test_even_spread_zero_count_boundary():
    """Pin the deliberate deviation at the exact
    boundary where this build and the reference can diverge — a value
    whose combined count is (or has been cleared to) ZERO while others
    are positive. The reference's evenSpreadScoreBoost iterates a Go map
    that may retain cleared-to-zero entries, making its min==0 branch
    order-dependent (spread.go:199-215); this build defines min over
    POSITIVE counts, so the zero-count value deterministically gets
    boost (minc − 0)/minc = +1.0 — it is attractive (under-used), but
    less attractive than an at-min positive value's (maxc−minc)/minc
    when that exceeds 1. Both the kernel and its oracle pin this."""
    ct = make_cluster(24, seed=30)
    vids = (np.arange(ct.padded_n) % 3).astype(np.int32)
    # value 0 cleared to zero (e.g. its alloc stopped in-plan); value 1
    # at min=1; value 2 at max=4 ⇒ boosts: v0 = (1-0)/1 = +1,
    # v1 = (4-1)/1 = +3, v2 = (1-4)/1 = −3
    c0 = np.array([0.0, 1.0, 4.0], dtype=np.float32)
    b = blocks_of(ct, [(BLOCK_EVEN_SPREAD, vids, c0, None, None, 1.0)])
    a = make_ask(ct, count=1, blocks=b)
    assert_against_oracle(ct, a)
    rows, _ = run_kernel(ct, a)
    # the at-min positive value wins over the cleared-to-zero value
    assert vids[rows[0]] == 1
    # and with value 1 removed from contention, the zero value wins next
    a2 = make_ask(ct, count=1, blocks=blocks_of(
        ct, [(BLOCK_EVEN_SPREAD, vids,
              np.array([0.0, 2.0, 4.0], dtype=np.float32), None, None, 1.0)]
    ))
    # boosts now: v0 = +1, v1 = (4-2)/2 = +1 at min... v1 at min=2:
    # (4-2)/2 = 1.0 ties v0; argmax tie-break is by score then row order
    assert_against_oracle(ct, a2)


# -- conflict repair ---------------------------------------------------------


def test_repair_batch_conflicts_moves_overcommit():
    """Two identical lanes against a 2-slot cluster: unrepaired they pile
    onto the same argmax node; repair must divert the second lane to its
    overflow candidate."""
    ct = make_cluster(2, seed=10, load_max=0.0)
    # each node fits exactly one ask
    ct.capacity[:2, 0] = 1000
    ct.capacity[:2, 1] = 1024
    a1 = make_ask(ct, count=1, seed=1, cpu=900, mem=900)
    a2 = make_ask(ct, count=1, seed=2, cpu=900, mem=900)
    kernel = PlacementKernel("binpack")
    results = kernel.place(ct, [a1, a2])
    assert results[0].node_rows[0] == results[1].node_rows[0]  # the pile-up
    ok = repair_batch_conflicts(ct, [a1, a2], results)
    assert ok == [True, True]
    assert results[0].node_rows[0] != results[1].node_rows[0]
    # both placements still fit their (now distinct) nodes
    total = np.zeros_like(ct.used)
    for a, r in zip([a1, a2], results):
        total[r.node_rows[0]] += a.ask
    assert np.all(ct.used + total <= ct.capacity + 1e-5)


@pytest.mark.parametrize("exact", [
    (False, False, False),  # runners-up of the shared snapshot, as above
    (False, True, False),   # an exact lane waits for the others
    (False, False, True),
    (True, True, True),     # ahead of the others it keeps its best
])
def test_an_exact_lane_that_loses_its_node_is_placed_after_the_others(exact):
    """A lane marked ``exact`` (the replacement of what its plan stops) is
    never moved to an overflow candidate of the shared snapshot: it is
    placed once every other lane has been, by the exact re-score on the
    usage that holds them, and marked ``deferred``."""
    from nomad_tpu.device.score import _rescore_pick

    ct = make_cluster(3, seed=10, load_max=0.0)
    ct.capacity[:3, 0] = (1000, 1000, 2100)
    ct.capacity[:3, 1] = (1024, 1024, 2100)
    lanes = [make_ask(ct, count=1, seed=s, cpu=900, mem=900)
             for s in (1, 2, 3)]
    for lane, e in zip(lanes, exact):
        lane.exact = e
    results = PlacementKernel("binpack").place(ct, lanes)
    first = [int(r.node_rows[0]) for r in results]
    scores = [float(r.scores[0]) for r in results]
    assert first[0] == first[1] == first[2]
    assert first[0] in (0, 1)  # a node that takes one of the three
    ok = repair_batch_conflicts(ct, lanes, results, lane_groups=[0, 1, 2])
    assert ok == [True, True, True]
    rows = [int(r.node_rows[0]) for r in results]
    assert rows[0] == first[0] and sorted(rows) == [0, 1, 2]
    assert [r.deferred for r in results] == [False, exact[1], exact[2]]
    used = ct.used.copy()
    used[rows[0]] += lanes[0].ask
    zero = np.zeros(ct.padded_n, dtype=np.float32)
    for k in (1, 2):
        if not exact[k]:
            # an overflow candidate, with the score it had on the snapshot
            assert float(results[k].scores[0]) <= scores[k]
            used[rows[k]] += lanes[k].ask
    for k in (1, 2):
        if exact[k]:
            # the best of what every lane before it in this order left
            row, score = _rescore_pick(
                ct.capacity, used, lanes[k], zero, None, False)
            assert (rows[k], float(results[k].scores[0])) == (row, score)
            used[row] += lanes[k].ask


def test_an_exact_lane_beside_one_that_leaves_room_keeps_its_node():
    ct = make_cluster(2, seed=10, load_max=0.0)
    ct.capacity[:2, 0] = 2000
    ct.capacity[:2, 1] = 2048
    lanes = [make_ask(ct, count=1, seed=s, cpu=900, mem=900) for s in (1, 2)]
    lanes[1].exact = True
    results = PlacementKernel("binpack").place(ct, lanes)
    first = [int(r.node_rows[0]) for r in results]
    assert repair_batch_conflicts(ct, lanes, results) == [True, True]
    assert [int(r.node_rows[0]) for r in results] == first


def test_repair_reports_unrepairable_lane():
    ct = make_cluster(1, seed=11, load_max=0.0)
    ct.capacity[0, 0] = 1000
    ct.capacity[0, 1] = 1024
    a1 = make_ask(ct, count=1, seed=1, cpu=900, mem=900)
    a2 = make_ask(ct, count=1, seed=2, cpu=900, mem=900)
    kernel = PlacementKernel("binpack")
    results = kernel.place(ct, [a1, a2])
    ok = repair_batch_conflicts(ct, [a1, a2], results)
    assert ok == [True, False]


def test_repair_respects_distinct_caps():
    ct = make_cluster(8, seed=12, load_max=0.0)
    vids = (np.arange(ct.padded_n) % 2).astype(np.int32)
    caps = np.full(2, 1.0, dtype=np.float32)
    mk = lambda s: make_ask(
        ct, count=1, seed=s, blocks=blocks_of(
            ct, [(BLOCK_DISTINCT_CAP, vids, np.zeros(2, dtype=np.float32),
                  None, caps.copy(), 0.0)]
        )
    )
    lanes = [mk(1), mk(2)]
    kernel = PlacementKernel("binpack")
    results = kernel.place(ct, lanes)
    repair_batch_conflicts(ct, lanes, results)
    # each lane is a separate job: per-job caps are independent, so both
    # may place; but within each lane the cap holds
    for lane, r in zip(lanes, results):
        placed = r.node_rows[r.node_rows >= 0]
        vals = vids[placed]
        for v in range(2):
            assert int((vals == v).sum()) <= 1
