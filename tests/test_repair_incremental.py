"""The repair walk's incremental exact re-score (``_LaneRescore``) against
the full host re-score it replaces (``_rescore_pick``, the oracle).

Within one lane's walk only the row a commit placed on changes between
two placements, besides the lane's per-value counts; the re-scorer keeps
every other row's parts. Each pick must be the oracle's on the same
state: the same row and the same score, bit for bit."""

import struct

import numpy as np
import pytest

from nomad_tpu.device import score as score_mod
from nomad_tpu.device.flatten import ClusterTensors, GroupAsk, ValueBlocks
from nomad_tpu.device.score import (
    BLOCK_DISTINCT_CAP,
    BLOCK_EVEN_SPREAD,
    BLOCK_TARGET_SPREAD,
    EVEN_HELD_AT_ZERO,
    PlacementResult,
    _LaneRescore,
    _rescore_pick,
    repair_batch_conflicts,
)
from nomad_tpu.obs.trace import global_tracer
from nomad_tpu.utils.metrics import global_metrics

RACKS = 25


def fleet(pn, seed, load_max=0.8):
    """A padded fleet of ``pn`` rows, 61 % of them nodes, rack = row % 25."""
    rng = np.random.default_rng(seed)
    n = int(pn * 0.61)
    capacity = np.zeros((pn, 4), dtype=np.float32)
    capacity[:n, 0] = rng.choice([4000, 8000, 16000], n)
    capacity[:n, 1] = rng.choice([8192, 16384, 32768], n)
    capacity[:n, 2] = 100 * 1024
    capacity[:n, 3] = 1000
    used = np.zeros_like(capacity)
    used[:n, :2] = capacity[:n, :2] * rng.uniform(
        0, load_max, (n, 1)).astype(np.float32)
    ready = np.zeros(pn, dtype=bool)
    ready[:n] = True
    return ClusterTensors(
        node_ids=[f"n{i}" for i in range(n)],
        index=1, num_nodes=n, capacity=capacity, used=used, ready=ready,
        dc_ids=np.zeros(pn, dtype=np.int32),
        class_ids=np.zeros(pn, dtype=np.int32),
        dc_vocab={"dc1": 0}, class_vocab={"c": 0}, class_rep=[0],
        node_row={f"n{i}": i for i in range(n)},
    )


def value_blocks(ct, rng, kinds):
    """One block per kind over the racks (block b shifts them by b), 32
    values wide as the kernels pad them; every 7th node has no value."""
    n, pn, nb, nv = ct.num_nodes, ct.padded_n, len(kinds), 32
    vids = np.full((nb, pn), -1, dtype=np.int32)
    for b in range(nb):
        vids[b, :n] = (np.arange(n) + b) % RACKS
        vids[b, :n:7] = -1
    counts0 = np.zeros((nb, nv), dtype=np.float32)
    desired = np.full((nb, nv), -1.0, dtype=np.float32)
    caps = np.full((nb, nv), np.inf, dtype=np.float32)
    for b, kind in enumerate(kinds):
        counts0[b, :RACKS] = rng.integers(0, 6, RACKS)
        if kind == "even_held":
            held = rng.choice(RACKS, 3, replace=False)
            counts0[b, held] = 0.0
            desired[b, held] = EVEN_HELD_AT_ZERO
        elif kind == "target":
            desired[b, :RACKS] = rng.integers(0, 12, RACKS)
        elif kind == "cap":
            caps[b, :RACKS] = counts0[b, :RACKS] + rng.integers(0, 3, RACKS)
    code = {"even": BLOCK_EVEN_SPREAD, "even_held": BLOCK_EVEN_SPREAD,
            "target": BLOCK_TARGET_SPREAD, "cap": BLOCK_DISTINCT_CAP}
    return ValueBlocks(
        value_ids=vids, counts0=counts0, desired=desired, caps=caps,
        weights=np.full(nb, 1.0 / nb, dtype=np.float32),
        kinds=np.array([code[k] for k in kinds], dtype=np.int32),
    )


def lane(ct, seed, count=10, blocks=(), affinities=False, penalties=False,
         distinct_hosts=False, slot_caps=False, eligible_nodes=None,
         exact=False):
    rng = np.random.default_rng(seed)
    pn = ct.padded_n
    eligible = ct.ready.copy()
    if eligible_nodes is not None:
        eligible[:] = False
        eligible[rng.choice(ct.num_nodes, eligible_nodes,
                            replace=False)] = True
    return GroupAsk(
        job_id=f"job-{seed}", tg_name="web", count=count,
        desired_total=count + int(rng.integers(0, 20)),
        ask=np.array([500.0, 512.0, 300.0, 0.0], dtype=np.float32),
        eligible=eligible,
        job_counts=(rng.random(pn) < 0.05).astype(np.int32),
        penalty_nodes=(
            rng.random(pn) < 0.05 if penalties
            else np.zeros(pn, dtype=bool)),
        affinity_scores=(
            rng.uniform(-1, 1, pn).astype(np.float32) if affinities
            else np.zeros(pn, dtype=np.float32)),
        has_affinities=affinities,
        distinct_hosts=distinct_hosts,
        blocks=value_blocks(ct, rng, blocks) if blocks else None,
        slot_caps=(
            rng.integers(0, 3, pn).astype(np.float32) if slot_caps
            else None),
        exact=exact,
    )


def bits(x) -> bytes:
    return struct.pack("<d", float(x))


CASES = {
    "binpack": ({}, False),
    "spread": ({}, True),
    "even_spread_held_at_zero": ({"blocks": ("even_held",)}, False),
    "target_spread": ({"blocks": ("target",)}, True),
    "distinct_cap": ({"blocks": ("cap",)}, False),
    "distinct_hosts": ({"distinct_hosts": True}, False),
    "slot_caps": ({"slot_caps": True}, False),
    "penalty_nodes": ({"penalties": True}, True),
    "affinities": ({"affinities": True}, False),
    "several_blocks": ({"blocks": ("even", "target", "cap"),
                        "affinities": True, "penalties": True}, False),
    # eight eligible nodes, one placement a node: the walk runs dry
    "nothing_fits_partway": ({"eligible_nodes": 8,
                              "distinct_hosts": True}, False),
}


@pytest.mark.parametrize("pn", [1024, 16384])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_incremental_pick_is_the_oracles(case, pn):
    """A walk of up to 24 placements: the re-scorer's pick against
    ``_rescore_pick`` on the same ``used``, lane placements and counts,
    with commits of rows the walk took without a re-score (a kernel row
    still acceptable, an overflow candidate) before the first pick and
    between the picks."""
    kwargs, algorithm_spread = CASES[case]
    ct = fleet(pn, seed=pn + len(case))
    rng = np.random.default_rng(7)
    a = lane(ct, seed=11, **kwargs)
    used = ct.used.copy()
    counts = a.blocks.counts0.copy() if a.blocks is not None else None
    placed: dict = {}
    rescorer = None
    picks = ran_dry = 0
    nodes = np.flatnonzero(a.eligible)

    def commit(row):
        used[row] += a.ask
        placed[row] = placed.get(row, 0) + 1
        if a.blocks is not None:
            for b in range(a.blocks.num_blocks):
                v = a.blocks.value_ids[b, row]
                if v >= 0:
                    counts[b, v] += 1
        if rescorer is not None:
            rescorer.commit(row)

    for row in rng.choice(nodes, 2):
        commit(int(row))  # the kernel's rows before the first re-score
    for k in range(1, 25):
        if rescorer is not None and rng.random() < 0.25:
            commit(int(rng.choice(nodes)))  # taken without a re-score
            continue
        if rescorer is None:
            rescorer = _LaneRescore(
                ct.capacity, used, a, placed, counts, algorithm_spread)
        pm = np.zeros(pn, dtype=np.float32)
        for r, m in placed.items():
            pm[r] = m
        want = _rescore_pick(
            ct.capacity, used, a, pm, counts, algorithm_spread)
        got = rescorer.pick()
        assert got[0] == want[0] and bits(got[1]) == bits(want[1]), (
            f"placement {k}: {got} != {want}")
        picks += 1
        if got[0] < 0:
            ran_dry += 1
            continue
        commit(got[0])
    assert picks >= 12
    assert (ran_dry > 0) == (case == "nothing_fits_partway")


class _OracleRescore:
    """``_LaneRescore``'s interface over a full ``_rescore_pick`` a pick:
    what the walk did before the re-scorer kept its parts."""

    def __init__(self, capacity, used, a, placed_on_node, counts,
                 algorithm_spread):
        self.args = (capacity, used, a, counts, algorithm_spread)
        self.pm = np.zeros(capacity.shape[0], dtype=np.float32)
        for r, m in placed_on_node.items():
            self.pm[r] = m

    def commit(self, row):
        self.pm[row] += 1

    def pick(self):
        capacity, used, a, counts, spread = self.args
        return _rescore_pick(capacity, used, a, self.pm.copy(), counts, spread)


def batched_pass(seed, pn=1024, lanes=12):
    """A seeded batched pass as the kernel leaves it: each lane's rows
    are its own best on the shared snapshot (so lanes pile onto the
    same nodes), a few overflow candidates after them; some lanes
    ``exact``, some with kernel failures, some evals of two lanes."""
    rng = np.random.default_rng(seed)
    ct = fleet(pn, seed=seed, load_max=0.95)
    asks, results = [], []
    for i in range(lanes):
        kinds = [(), ("even",), ("even_held",), ("target", "cap")][i % 4]
        a = lane(ct, seed=seed * 100 + i, count=int(rng.integers(1, 14)),
                 blocks=kinds, affinities=bool(i % 3 == 0),
                 exact=bool(rng.random() < 0.6))
        used = ct.used.copy()
        pm = np.zeros(pn, dtype=np.float32)
        counts = a.blocks.counts0.copy() if a.blocks is not None else None
        rows, scores = [], []
        for _ in range(a.count):
            row, sc = _rescore_pick(ct.capacity, used, a, pm, counts, False)
            rows.append(row)
            scores.append(sc)
            if row < 0:
                continue
            used[row] += a.ask
            pm[row] += 1
            if counts is not None:
                for b in range(a.blocks.num_blocks):
                    v = a.blocks.value_ids[b, row]
                    if v >= 0:
                        counts[b, v] += 1
        if rng.random() < 0.2:
            rows[-1] = -1  # the kernel's stripe ran dry
        over = rng.choice(np.flatnonzero(a.eligible), 3, replace=False)
        asks.append(a)
        results.append(PlacementResult(
            node_rows=np.array(rows, dtype=np.int32),
            scores=np.array(scores, dtype=np.float32),
            overflow_rows=over.astype(np.int32),
            overflow_scores=np.full(3, 0.1, dtype=np.float32),
        ))
    groups = [i // 2 if i < 4 else i for i in range(lanes)]
    return ct, asks, results, groups


def run_pass(seed, fail_on_contention):
    ct, asks, results, groups = batched_pass(seed)
    ok = repair_batch_conflicts(
        ct, asks, results, algorithm_spread=False, lane_groups=groups,
        fail_on_contention=fail_on_contention)
    return ok, [(r.node_rows.tolist(), r.scores.tobytes(), r.deferred)
                for r in results]


@pytest.mark.parametrize("fail_on_contention", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_repair_matches_a_full_rescore_a_placement(
        seed, fail_on_contention, monkeypatch):
    """``repair_batch_conflicts`` end to end: rows, scores, ``deferred``
    and the per-lane ``ok`` are those of a walk that re-scores every
    placement in full."""
    got = run_pass(seed, fail_on_contention)
    monkeypatch.setattr(score_mod, "_LaneRescore", _OracleRescore)
    want = run_pass(seed, fail_on_contention)
    assert got == want
    ok, lanes = got
    # the pass exercises what it is here for
    assert any(deferred for _, _, deferred in lanes)


def counter(name) -> float:
    return global_metrics.snapshot()["counters"].get(name, 0.0)


def rescores_of(ct, asks, results, trace_id):
    """(full, row) from the counters, checked against the span's tags."""
    before = [counter(f"nomad.worker.repair_rescores_{k}")
              for k in ("full", "row")]
    global_tracer.begin(trace_id)
    with global_tracer.activate(trace_id):
        ok = repair_batch_conflicts(ct, asks, results)
    trace = global_tracer.finish(trace_id)
    after = [counter(f"nomad.worker.repair_rescores_{k}")
             for k in ("full", "row")]
    (span,) = [s for s in trace["spans"] if s["name"] == "repair"]
    delta = (after[0] - before[0], after[1] - before[1])
    assert (span["tags"]["full"], span["tags"]["row"]) == delta
    return ok, delta


@pytest.mark.parametrize("k", [1, 2, 10])
def test_a_deferred_member_pays_one_full_pass_and_a_row_pick_for_each_more(k):
    """Two exact lanes whose first choice is one node with room for one:
    the second is deferred and its k placements re-scored, one full pass
    and k − 1 incremental picks; the first lane re-scores nothing."""
    ct = fleet(1024, seed=5, load_max=0.0)
    asks = [lane(ct, seed=s, count=c, exact=True) for s, c in ((1, 1), (2, k))]
    best = 3
    ct.used[best] = ct.capacity[best] - 1.5 * asks[0].ask
    results = [
        PlacementResult(
            node_rows=np.array([best] * a.count, dtype=np.int32),
            scores=np.ones(a.count, dtype=np.float32))
        for a in asks
    ]
    ok, delta = rescores_of(ct, asks, results, f"incr-deferred-{k}")
    assert ok == [True, True]
    assert [r.deferred for r in results] == [False, True]
    assert delta == (1, k - 1)
    assert (results[1].node_rows >= 0).all()


def test_a_lane_that_never_rescores_costs_nothing():
    ct = fleet(1024, seed=6, load_max=0.0)
    asks = [lane(ct, seed=1, count=3)]
    results = [PlacementResult(
        node_rows=np.array([3, 4, 5], dtype=np.int32),
        scores=np.ones(3, dtype=np.float32))]
    ok, delta = rescores_of(ct, asks, results, "incr-none")
    assert ok == [True] and delta == (0, 0)
    assert results[0].node_rows.tolist() == [3, 4, 5]
