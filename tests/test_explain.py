"""Placement explainability (obs/explain.py): schema pin, provenance
parity across seeds and algorithms, observational invariance (explain-off
bit-identity + zero added retraces), structured failure-metric
round-trips (codec + state snapshot), the flight recorder's explanation
ring, the HTTP/plan surfaces, and lint rule NTA014.

All tests here are CPU-only and ride tier-1.
"""

import copy
import json
from types import SimpleNamespace

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.analysis import retrace
from nomad_tpu.device.score import PlacementKernel, repair_batch_conflicts
from nomad_tpu.mock import build_asks, build_cluster
from nomad_tpu.obs import explain as explain_mod
from nomad_tpu.obs.explain import (
    EXPLAIN_SCHEMA_VERSION,
    CandidateExplanation,
    _components_at,
    explain_group,
    explanation_to_dict,
    finalize_explanations,
)
from nomad_tpu.obs.recorder import FlightRecorder, flight_recorder
from nomad_tpu.structs import AllocMetric, Evaluation
from nomad_tpu.structs.alloc import NodeScoreMeta
from nomad_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _clean_ring():
    flight_recorder.clear()
    yield
    flight_recorder.clear()


def _place_explained(ct, asks, algorithm="binpack"):
    kernel = PlacementKernel(algorithm)
    results = kernel.place(ct, asks, explain=True)
    repair_batch_conflicts(
        ct, asks, results, algorithm_spread=kernel.algorithm_spread
    )
    finalize_explanations(ct, asks, results)
    return results


# -- schema pin (the ~4s tier-1 smoke) --------------------------------------


class TestExplanationSchema:
    def test_schema_shape_is_pinned(self):
        """The explanation dict IS the API/CLI contract — key set and
        candidate shape must not drift without a schema_version bump."""
        ct = build_cluster(200)
        asks = build_asks(ct, 2, 10)
        results = _place_explained(ct, asks)
        d = explanation_to_dict(results[0].explanation)
        assert set(d.keys()) == {
            "schema_version",
            "job_id",
            "tg_name",
            "algorithm",
            "policy",
            "nodes_evaluated",
            "feasible_nodes",
            "top_candidates",
            "rejections",
            "placed_nodes",
        }
        assert d["schema_version"] == EXPLAIN_SCHEMA_VERSION == 1
        assert d["algorithm"] == "binpack"
        assert d["nodes_evaluated"] == 200
        assert 0 < d["feasible_nodes"] <= 200
        assert d["top_candidates"], "feasible fleet must yield candidates"
        for i, c in enumerate(d["top_candidates"][:5]):
            assert set(c.keys()) == {
                "node_id",
                "rank",
                "final_score",
                "components",
                "placed",
            }
            assert c["rank"] == i + 1
            assert "binpack" in c["components"]
        assert len(d["placed_nodes"]) == 10
        # the dict is JSON-clean as-is (no numpy scalars)
        json.dumps(d)

    def test_candidates_rank_by_descending_score(self):
        ct = build_cluster(200)
        asks = build_asks(ct, 1, 5)
        d = explanation_to_dict(_place_explained(ct, asks)[0].explanation)
        finals = [c["final_score"] for c in d["top_candidates"]]
        assert finals == sorted(finals, reverse=True)

    def test_infeasible_fleet_yields_rejections_only(self):
        ct = build_cluster(64)
        asks = build_asks(ct, 1, 4)
        a = asks[0]
        a.ask = a.ask + np.float32(1e9)  # nothing fits
        results = _place_explained(ct, [a])
        ex = results[0].explanation
        assert ex.feasible_nodes == 0
        assert not ex.top_candidates
        assert ex.rejections.get("exhausted:cpu", 0) > 0
        assert ex.rejections.get("exhausted:memory_mb", 0) > 0


# -- provenance parity ------------------------------------------------------


class TestProvenanceParity:
    @pytest.mark.parametrize("algorithm", ["binpack", "spread"])
    def test_top1_matches_committed_placement_across_seeds(self, algorithm):
        """On an uncontended (single-lane) pass over a seeded 1k-node
        fleet, the explanation's top-1 candidate is exactly the node the
        greedy placement committed first."""
        for seed in (0, 1, 2):
            ct = build_cluster(1_000, seed=42 + seed)
            asks = build_asks(ct, 1, 50, seed=7 + seed)
            results = _place_explained(ct, asks, algorithm=algorithm)
            ex = results[0].explanation
            assert ex.placed_nodes, f"seed {seed}: nothing placed"
            assert ex.top_candidates[0].node_id == ex.placed_nodes[0], (
                f"{algorithm} seed {seed}: top-1 "
                f"{ex.top_candidates[0].node_id} != committed "
                f"{ex.placed_nodes[0]}"
            )
            assert ex.top_candidates[0].placed >= 1

    @pytest.mark.parametrize("policy", ["maxmin", "makespan", "cost"])
    def test_hetero_top1_matches_committed_placement(self, policy):
        from nomad_tpu.scheduler.hetero import (
            HeteroPlacementKernel,
            build_mixed_asks,
            build_mixed_fleet,
        )

        for seed in (42, 43):
            ct = build_mixed_fleet(1_000, seed=seed)
            asks = build_mixed_asks(ct, 4, 10, seed=7)
            kernel = HeteroPlacementKernel(policy)
            for a in asks:  # uncontended: one lane at a time
                results = kernel.place(ct, [a], explain=True)
                repair_batch_conflicts(
                    ct, [a], results, algorithm_spread=False
                )
                finalize_explanations(ct, [a], results)
                ex = results[0].explanation
                if ex is None or not ex.placed_nodes:
                    continue
                assert ex.algorithm == f"hetero-{policy}"
                assert ex.policy == policy
                assert (
                    ex.top_candidates[0].node_id == ex.placed_nodes[0]
                ), f"{policy} seed {seed} job {a.job_id}"

    def test_instance_meta_aligns_with_committed_rows(self):
        ct = build_cluster(500)
        asks = build_asks(ct, 2, 20)
        results = _place_explained(ct, asks)
        for res in results:
            ex = res.explanation
            metas = ex.instance_meta
            assert len(metas) == len(res.node_rows)
            for row, meta in zip(np.asarray(res.node_rows), metas):
                if row < 0:
                    assert meta is None
                else:
                    assert meta.node_id == ct.node_ids[int(row)]
                    assert "binpack" in meta.scores


# -- array replay vs the sequential oracle ----------------------------------


def _sequential_replay(ct, a, res, ex):
    """The oracle: the replay as it was before it became an array pass.
    One ``_components_at`` call per placed instance against an overlay
    that is stepped through the placement order (usage, the lane's own
    instances per row, the per-value counts ``_host_block_tables`` reads),
    then the candidates' ``placed`` counts and the appended rows. Stamps
    ``ex`` the way ``finalize_explanations`` does."""
    used = np.asarray(ct.used).copy()
    capacity = np.asarray(ct.capacity)
    counts = a.blocks.counts0.copy() if a.blocks is not None else None
    placed_on = {}
    ex.placed_nodes, ex.instance_meta = [], []
    for row in np.asarray(res.node_rows).tolist():
        if row < 0:
            ex.instance_meta.append(None)
            continue
        ex.placed_nodes.append(ct.node_ids[row])
        ((comps, final),) = _components_at(
            capacity, used, a, [row], [placed_on.get(row, 0)], counts,
            ex.algorithm == "spread",
        )
        ex.instance_meta.append(NodeScoreMeta(
            node_id=ct.node_ids[row],
            scores={k: float(v) for k, v in comps.items()},
            norm_score=float(final),
        ))
        used[row] += a.ask
        placed_on[row] = placed_on.get(row, 0) + 1
        if counts is not None:
            for b in range(a.blocks.num_blocks):
                v = a.blocks.value_ids[b, row]
                if v >= 0:
                    counts[b, v] += 1
    by_row = {c.node_row: c for c in ex.top_candidates}
    for row, k in placed_on.items():
        if row in by_row:
            by_row[row].placed = k
            continue
        meta = next(
            m for m in ex.instance_meta
            if m is not None and m.node_id == ct.node_ids[row]
        )
        ex.top_candidates.append(CandidateExplanation(
            node_id=meta.node_id, node_row=int(row),
            final_score=meta.norm_score, components=dict(meta.scores),
            placed=k,
        ))


def _value_blocks(ct, kinds, values=5, counts0=None, desired=None,
                  valueless_every=0):
    """Stacked blocks over ``rack = row % values``, one per entry of
    ``kinds``; block b shifts the racks by b so blocks disagree."""
    from nomad_tpu.device.flatten import ValueBlocks

    n, pn, b = ct.num_nodes, ct.padded_n, len(kinds)
    vids = np.full((b, pn), -1, dtype=np.int32)
    for i in range(b):
        vids[i, :n] = (np.arange(n) + i) % values
    if valueless_every:
        vids[:, :n:valueless_every] = -1
    return ValueBlocks(
        value_ids=vids,
        counts0=(np.zeros((b, values), dtype=np.float32)
                 if counts0 is None else np.asarray(counts0, np.float32)),
        desired=(np.full((b, values), -1.0, dtype=np.float32)
                 if desired is None else np.asarray(desired, np.float32)),
        caps=np.full((b, values), np.inf, dtype=np.float32),
        weights=np.full(b, 1.0 / b, dtype=np.float32),
        kinds=np.array(kinds, dtype=np.int32),
    )


def _replay_case(name):
    """(cluster, ask, committed rows) of one equivalence case: 40 slots
    on 300 nodes, the rows drawn from the seed (a replay does not ask
    whether greedy placement would have picked them)."""
    from nomad_tpu.device.score import (
        BLOCK_DISTINCT_CAP,
        BLOCK_EVEN_SPREAD,
        BLOCK_TARGET_SPREAD,
    )

    ct = build_cluster(300, seed=11)
    (a,) = build_asks(ct, 1, 40, seed=3)
    rng = np.random.default_rng(5)
    rows = rng.choice(ct.num_nodes, size=40, replace=False).astype(np.int32)
    if name == "plain":
        pass
    elif name == "even-rack-spread":
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD])
    elif name == "even-spread-live-counts":
        # the job already runs: uneven counts, and collisions on 12 rows
        a.blocks = _value_blocks(
            ct, [BLOCK_EVEN_SPREAD], counts0=[[3, 0, 1, 1, 7]]
        )
        a.job_counts[rows[:12]] = 1
    elif name == "target-spread-desired":
        a.blocks = _value_blocks(
            ct, [BLOCK_TARGET_SPREAD], counts0=[[2, 0, 0, 5, 0]],
            # value 3 is over its target, value 4 has none (-1)
            desired=[[16, 12, 8, 4, -1]],
        )
    elif name == "two-spreads":
        a.blocks = _value_blocks(
            ct, [BLOCK_TARGET_SPREAD, BLOCK_EVEN_SPREAD],
            desired=[[10, 10, 10, 5, 5], [-1] * 5],
        )
    elif name == "spread-and-distinct-cap":
        a.blocks = _value_blocks(
            ct, [BLOCK_DISTINCT_CAP, BLOCK_EVEN_SPREAD, BLOCK_DISTINCT_CAP]
        )
    elif name == "distinct-cap-alone":
        a.blocks = _value_blocks(ct, [BLOCK_DISTINCT_CAP])
    elif name == "valueless-nodes":
        a.blocks = _value_blocks(
            ct, [BLOCK_EVEN_SPREAD, BLOCK_TARGET_SPREAD],
            desired=[[-1] * 5, [8] * 5], valueless_every=3,
        )
    elif name == "unplaced-slots":
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD])
        rows[[0, 7, 8, 39]] = -1
    elif name == "two-on-one-node":
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD])
        rows[5], rows[20], rows[21] = rows[2], rows[2], rows[9]
    elif name == "spread-affinity-penalty":
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD])
        a.has_affinities = True
        a.affinity_scores = rng.uniform(-1, 1, ct.padded_n).astype(np.float32)
        a.penalty_nodes[rows[[1, 4, 30]]] = True
    elif name == "nothing-placed":
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD])
        rows[:] = -1
    else:
        raise AssertionError(name)
    return ct, a, rows


def _first_step(ct, a, rows, algorithm="binpack"):
    """A lane as ``finalize_explanations`` finds it: the first step's
    explanation on a result that holds the committed rows."""
    ex = explain_group(
        ct, a, ct.used, algorithm=algorithm,
        algorithm_spread=algorithm == "spread",
    )
    return SimpleNamespace(node_rows=rows, scores=None, explanation=ex)


REPLAY_CASES = [
    "plain", "even-rack-spread", "even-spread-live-counts",
    "target-spread-desired", "two-spreads", "spread-and-distinct-cap",
    "distinct-cap-alone", "valueless-nodes", "unplaced-slots",
    "two-on-one-node", "spread-affinity-penalty", "nothing-placed",
]


class TestArrayReplayMatchesSequential:
    @pytest.mark.parametrize("algorithm", ["binpack", "spread"])
    @pytest.mark.parametrize("case", REPLAY_CASES)
    def test_same_provenance_as_the_sequential_replay(self, case, algorithm):
        ct, a, rows = _replay_case(case)
        res = _first_step(ct, a, rows, algorithm)
        ex, want = res.explanation, copy.deepcopy(res.explanation)
        _sequential_replay(ct, a, res, want)
        stamped = finalize_explanations(ct, [a], [res])

        placed = int((rows >= 0).sum())
        assert stamped == {"instances": placed, "sequential_lanes": 0}
        assert ex.placed_nodes == want.placed_nodes
        assert len(ex.placed_nodes) == placed
        assert len(ex.instance_meta) == len(want.instance_meta) == len(rows)
        for i, (got, exp) in enumerate(
            zip(ex.instance_meta, want.instance_meta)
        ):
            if exp is None:
                assert got is None, i
                continue
            assert got.node_id == exp.node_id, i
            # same keys in the same order of insertion
            assert list(got.scores) == list(exp.scores), (i, got, exp)
            assert got.scores == pytest.approx(exp.scores, abs=1e-6), i
            assert got.norm_score == pytest.approx(
                exp.norm_score, abs=1e-6
            ), i
            assert all(type(v) is float for v in got.scores.values())
            assert type(got.norm_score) is float
        assert [
            (c.node_id, c.node_row, c.placed, list(c.components))
            for c in ex.top_candidates
        ] == [
            (c.node_id, c.node_row, c.placed, list(c.components))
            for c in want.top_candidates
        ]
        for got, exp in zip(ex.top_candidates, want.top_candidates):
            assert got.final_score == pytest.approx(exp.final_score, abs=1e-6)
            assert got.components == pytest.approx(exp.components, abs=1e-6)

    def test_cases_reach_the_branches_they_name(self):
        """The oracle agrees on whatever it is shown; this pins that the
        cases show it the spread boost, both of its signs, a node without
        a value, the collision and the penalty."""
        seen = {}
        for case in REPLAY_CASES:
            ct, a, rows = _replay_case(case)
            res = _first_step(ct, a, rows)
            finalize_explanations(ct, [a], [res])
            seen[case] = [
                m.scores for m in res.explanation.instance_meta
                if m is not None
            ]
        boosts = [
            m["allocation-spread"] for m in seen["even-spread-live-counts"]
            if "allocation-spread" in m
        ]
        assert min(boosts) < 0 < max(boosts)
        assert not any(
            "allocation-spread" in m for m in seen["distinct-cap-alone"]
        )
        # the first instance of an even spread with no counts sees boost 0
        assert "allocation-spread" not in seen["even-rack-spread"][0]
        assert "allocation-spread" in seen["even-rack-spread"][-1]
        assert any(
            m.get("allocation-spread") == -2.0
            for m in seen["valueless-nodes"]
        )
        assert sum(
            "job-anti-affinity" in m for m in seen["two-on-one-node"]
        ) == 3
        assert sum(
            "job-anti-affinity" in m
            for m in seen["even-spread-live-counts"]
        ) == 12
        assert sum(
            "node-reschedule-penalty" in m
            for m in seen["spread-affinity-penalty"]
        ) == 3
        assert seen["nothing-placed"] == []

    def test_a_long_lane_is_replayed_in_chunks_that_carry_the_counts(
        self, monkeypatch
    ):
        """The [instances, values] count array is bounded; a lane longer
        than one chunk gives what one pass gives."""
        ct, a, rows = _replay_case("two-spreads")
        metas = []
        for elems in (explain_mod._REPLAY_CHUNK_ELEMS, 5 * 7):
            monkeypatch.setattr(explain_mod, "_REPLAY_CHUNK_ELEMS", elems)
            res = _first_step(ct, a, rows)
            finalize_explanations(ct, [a], [res])
            metas.append([
                (m.node_id, m.scores, m.norm_score)
                for m in res.explanation.instance_meta
            ])
        assert metas[0] == metas[1]

    def test_spread_lane_finalizes_without_a_call_per_instance(
        self, monkeypatch
    ):
        """250 instances under an even rack spread on 2,000 nodes, placed
        by the kernel and repaired: the finalize step reaches neither
        ``_components_at`` nor ``_host_block_tables`` once per instance
        (at most top_k calls; no wall-clock assertion), and still agrees
        with the sequential replay."""
        from nomad_tpu.device import score as score_mod
        from nomad_tpu.device.score import BLOCK_EVEN_SPREAD

        ct = build_cluster(2_000)
        (a,) = build_asks(ct, 1, 250)
        a.blocks = _value_blocks(ct, [BLOCK_EVEN_SPREAD], values=25)
        kernel = PlacementKernel("binpack")
        (res,) = kernel.place(ct, [a], explain=True)
        repair_batch_conflicts(ct, [a], [res], algorithm_spread=False)
        assert int((np.asarray(res.node_rows) >= 0).sum()) == 250
        want = copy.deepcopy(res.explanation)
        _sequential_replay(ct, a, res, want)

        calls = {"_components_at": 0, "_host_block_tables": 0}

        def counted(mod, name):
            inner = getattr(mod, name)

            def wrapper(*args, **kwargs):
                calls[name] += 1
                return inner(*args, **kwargs)

            monkeypatch.setattr(mod, name, wrapper)

        counted(explain_mod, "_components_at")
        counted(score_mod, "_host_block_tables")
        stamped = finalize_explanations(ct, [a], [res])
        assert stamped == {"instances": 250, "sequential_lanes": 0}
        assert calls["_components_at"] <= explain_mod.DEFAULT_TOP_K
        assert calls["_host_block_tables"] <= explain_mod.DEFAULT_TOP_K
        ex = res.explanation
        assert ex.placed_nodes == want.placed_nodes
        # a spread lane commits far outside the first-instance top 5
        assert len(ex.top_candidates) > 200
        assert [(c.node_row, c.placed) for c in ex.top_candidates] == [
            (c.node_row, c.placed) for c in want.top_candidates
        ]
        for got, exp in zip(ex.instance_meta, want.instance_meta):
            assert list(got.scores) == list(exp.scores)
            assert got.scores == pytest.approx(exp.scores, abs=1e-6)
            assert got.norm_score == pytest.approx(exp.norm_score, abs=1e-6)


@pytest.fixture(scope="module")
def final_step_spans():
    """The ``explain`` spans with ``step: final`` of one solo pass (a
    rack-spread job of 6) and one batched pass (a plain job of 3 and a
    rack-spread job of 4, enqueued while the worker is paused), read from
    the recorded traces: {"solo": [...], "batched": [...]}."""
    import time

    from nomad_tpu.obs.trace import global_tracer
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.job import Spread

    def job(job_id, count, spread):
        j = mock.job()
        j.id = job_id
        j.task_groups[0].count = count
        if spread:
            j.spreads = [Spread(attribute="${meta.rack}", weight=100)]
        return j

    global_tracer.set_enabled(True)
    got = {}

    def keep(trace):
        got[trace["eval_id"]] = trace

    flight_recorder.add_listener(keep)
    server = Server(ServerConfig(num_workers=1))
    server.establish_leadership()
    try:
        for i in range(8):
            server.register_node(mock.node(meta={"rack": f"r{i % 4}"}))
        solo = server.register_job(job("tags-solo", 6, spread=True))
        assert server.wait_for_evals(timeout=60)
        for w in server.workers:
            w.pause()
        # the worker may be inside one more dequeue poll (0.2 s): an eval
        # enqueued during it would be taken alone
        time.sleep(0.3)
        batched = [
            server.register_job(job("tags-plain", 3, spread=False)),
            server.register_job(job("tags-spread", 4, spread=True)),
        ]
        for w in server.workers:
            w.resume()
        assert server.wait_for_evals(timeout=60)
        want = {solo.id, *(ev.id for ev in batched)}
        deadline = time.time() + 5.0
        while time.time() < deadline and not want <= set(got):
            time.sleep(0.02)
        allocs = {
            j: server.store.allocs_by_job("default", j)
            for j in ("tags-solo", "tags-plain", "tags-spread")
        }
    finally:
        server.shutdown()
        flight_recorder.remove_listener(keep)
    assert {j: len(v) for j, v in allocs.items()} == {
        "tags-solo": 6, "tags-plain": 3, "tags-spread": 4,
    }

    def final_steps(eval_ids):
        return [
            s for eid in eval_ids for s in got[eid]["spans"]
            if s["name"] == "explain" and s["tags"].get("step") == "final"
        ]

    assert got[solo.id]["tags"]["path"] == "solo"
    assert {got[ev.id]["tags"]["path"] for ev in batched} == {"batched"}
    return {
        "solo": final_steps([solo.id]),
        "batched": final_steps(ev.id for ev in batched),
        "allocs": allocs,
    }


class TestFinalStepTags:
    @pytest.mark.parametrize(
        "path,instances", [("solo", 6), ("batched", 3 + 4)]
    )
    def test_span_says_how_many_rows_it_stamped_and_how(
        self, final_step_spans, path, instances
    ):
        """One final step a pass, written once (a batched pass's in its
        leader's trace): ``instances`` = the rows the pass placed,
        ``sequential_lanes`` = 0 for a spread lane and a plain lane alike
        (both take the array replay)."""
        (span,) = final_step_spans[path]
        assert span["tags"]["instances"] == instances
        assert span["tags"]["sequential_lanes"] == 0

    def test_every_alloc_commits_with_its_own_score_row(
        self, final_step_spans
    ):
        """Solo or batched, spread or plain: one ``score_meta`` row an
        allocation, for the node it landed on; a spread job's rows carry
        the boost once a rack holds an instance."""
        for job_id, allocs in final_step_spans["allocs"].items():
            for alloc in allocs:
                (meta,) = alloc.metrics.score_meta
                assert meta.node_id == alloc.node_id
                assert "binpack" in meta.scores
            boosted = sum(
                "allocation-spread" in alloc.metrics.score_meta[0].scores
                for alloc in allocs
            )
            # all but the first instance, which sees no count yet
            assert boosted == (0 if job_id == "tags-plain" else len(allocs) - 1)

    def test_hetero_lane_counts_as_sequential(self):
        """A hetero lane's per-instance score is the joint pass's own;
        it is stamped one by one and says so."""
        from nomad_tpu.scheduler.hetero import (
            HeteroPlacementKernel,
            build_mixed_asks,
            build_mixed_fleet,
        )

        ct = build_mixed_fleet(200, seed=42)
        asks = build_mixed_asks(ct, 2, 5, seed=7)
        results = HeteroPlacementKernel("maxmin").place(
            ct, asks, explain=True
        )
        repair_batch_conflicts(ct, asks, results, algorithm_spread=False)
        stamped = finalize_explanations(ct, asks, results)
        explained = [r for r in results if r.explanation is not None]
        assert explained
        assert stamped == {
            "instances": sum(
                int((np.asarray(r.node_rows) >= 0).sum()) for r in explained
            ),
            "sequential_lanes": len(explained),
        }
        for r in explained:
            for i, meta in zip(
                np.flatnonzero(np.asarray(r.node_rows) >= 0),
                [m for m in r.explanation.instance_meta if m is not None],
            ):
                assert meta.scores == {"throughput": float(r.scores[i])}
                assert meta.norm_score == float(r.scores[i])


# -- observational invariance ----------------------------------------------


class TestObservationalInvariance:
    def test_explain_off_is_bit_identical_with_zero_added_retraces(self):
        """Explain is host-side reconstruction: no new jitted program
        exists in either mode, so explain-on traces the identical jaxpr
        set and places bit-for-bit like explain-off."""
        ct = build_cluster(500)
        asks = build_asks(ct, 4, 25)
        kernel = PlacementKernel("binpack")
        kernel.place(ct, asks)  # warm the shape bucket
        base = dict(retrace.counts())
        off = kernel.place(ct, asks)
        assert dict(retrace.counts()) == base
        on = kernel.place(ct, asks, explain=True)
        assert dict(retrace.counts()) == base, (
            "explain=True must not add a single retrace"
        )
        for a, b in zip(off, on):
            assert np.array_equal(a.node_rows, b.node_rows)
            assert np.array_equal(a.scores, b.scores)
        assert all(r.explanation is None for r in off)
        assert all(r.explanation is not None for r in on)


# -- structured failure metrics (satellite: codec + snapshot) ---------------


def _failed_metric():
    return AllocMetric(
        nodes_evaluated=100,
        nodes_exhausted=60,
        dimension_exhausted={"cpu": 40, "memory_mb": 20},
        class_exhausted={"tpu-v5e": 8},
        rejections={"exhausted:cpu": 40, "class-infeasible": 8},
        score_meta=[
            NodeScoreMeta(
                node_id="node-7",
                scores={"binpack": 0.81, "job-anti-affinity": -0.1},
                norm_score=0.355,
            )
        ],
        coalesced_failures=3,
    )


class TestStructuredFailureMetrics:
    def test_codec_round_trips_alloc_metric(self):
        from nomad_tpu.api.codec import decode_eval, encode

        ev = Evaluation(job_id="web", type="service")
        ev.failed_tg_allocs = {"web": _failed_metric()}
        wire = json.loads(json.dumps(encode(ev)))
        back = decode_eval(wire)
        m = back.failed_tg_allocs["web"]
        assert isinstance(m, AllocMetric)
        assert m.dimension_exhausted == {"cpu": 40, "memory_mb": 20}
        assert m.class_exhausted == {"tpu-v5e": 8}
        assert m.rejections == {"exhausted:cpu": 40, "class-infeasible": 8}
        assert isinstance(m.score_meta[0], NodeScoreMeta)
        assert m.score_meta[0].node_id == "node-7"
        assert m.score_meta[0].norm_score == pytest.approx(0.355)

    def test_state_snapshot_round_trips_failed_metrics(self, tmp_path):
        from nomad_tpu.state import StateStore
        from nomad_tpu.state.snapshot import (
            restore_snapshot,
            save_snapshot,
        )

        store = StateStore()
        ev = Evaluation(job_id="web", type="service")
        ev.failed_tg_allocs = {"web": _failed_metric()}
        store.upsert_evals(5, [ev])
        path = str(tmp_path / "state.snap")
        save_snapshot(store, path)
        restored = restore_snapshot(path)
        m = restored.eval_by_id(ev.id).failed_tg_allocs["web"]
        assert isinstance(m, AllocMetric)
        assert m.rejections == {"exhausted:cpu": 40, "class-infeasible": 8}
        assert m.score_meta[0].scores["binpack"] == pytest.approx(0.81)

    def test_blocked_eval_carries_structured_metrics(self):
        ev = Evaluation(job_id="web", type="service")
        metric = _failed_metric()
        blocked = ev.create_blocked_eval({}, True, "", {"web": metric})
        carried = blocked.failed_tg_allocs["web"]
        assert carried.rejections["exhausted:cpu"] == 40
        assert carried.score_meta[0].node_id == "node-7"


# -- explanation ring -------------------------------------------------------


class TestExplanationRing:
    def test_ring_evicts_oldest_and_counts(self):
        r = FlightRecorder(capacity=4)
        for i in range(6):
            r.record_explanation(f"ev-{i}", {"eval_id": f"ev-{i}"})
        assert r.explanation("ev-0") is None
        assert r.explanation("ev-1") is None
        assert r.explanation("ev-5") == {"eval_id": "ev-5"}
        assert r.explanations_total == 6
        assert r.explanations_evicted == 2
        # newest first, bounded
        ids = [p["eval_id"] for p in r.explanations()]
        assert ids == ["ev-5", "ev-4", "ev-3", "ev-2"]

    def test_rerecord_moves_to_tail(self):
        r = FlightRecorder(capacity=2)
        r.record_explanation("a", {"eval_id": "a", "v": 1})
        r.record_explanation("b", {"eval_id": "b"})
        r.record_explanation("a", {"eval_id": "a", "v": 2})
        r.record_explanation("c", {"eval_id": "c"})  # evicts b, not a
        assert r.explanation("b") is None
        assert r.explanation("a")["v"] == 2

    def test_metrics_counters_bump(self):
        before = global_metrics.snapshot()["counters"].get(
            "nomad.obs.explanations_recorded", 0
        )
        r = FlightRecorder(capacity=1)
        r.record_explanation("x", {})
        r.record_explanation("y", {})
        counters = global_metrics.snapshot()["counters"]
        assert (
            counters.get("nomad.obs.explanations_recorded", 0) == before + 2
        )
        assert counters.get("nomad.obs.explanations_evicted", 0) >= 1

    def test_clear_drops_explanations(self):
        r = FlightRecorder()
        r.record_explanation("a", {"eval_id": "a"})
        r.clear()
        assert r.explanation("a") is None


# -- scheduler integration --------------------------------------------------


class TestSchedulerIntegration:
    def test_generic_scheduler_records_ring_and_alloc_meta(self):
        from nomad_tpu.scheduler.testing import Harness

        h = Harness()
        for _ in range(4):
            h.store.upsert_node(h.next_index(), mock.node())
        job = mock.job()
        job.task_groups[0].count = 3
        h.store.upsert_job(h.next_index(), job)
        ev = mock.eval_for(job)
        h.process(ev)

        payload = flight_recorder.explanation(ev.id)
        assert payload is not None, "placed eval must land in the ring"
        assert payload["job_id"] == job.id
        group = payload["groups"][job.task_groups[0].name]
        assert group["schema_version"] == 1
        assert group["top_candidates"]
        assert len(group["placed_nodes"]) == 3

        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert allocs
        for a in allocs:
            assert a.metrics.score_meta, "per-alloc breakdown missing"
            meta = a.metrics.score_meta[0]
            assert meta.node_id == a.node_id
            assert "binpack" in meta.scores

    def test_failed_placement_carries_rejections_and_near_miss(self):
        from nomad_tpu.scheduler.testing import Harness

        h = Harness()
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        job = mock.job()
        job.task_groups[0].count = 2
        # ask for more cpu than any node has: placement must fail
        job.task_groups[0].tasks[0].resources.cpu = 10**9
        h.store.upsert_job(h.next_index(), job)
        ev = mock.eval_for(job)
        h.process(ev)

        updated = h.evals[-1]
        m = updated.failed_tg_allocs[job.task_groups[0].name]
        assert m.rejections.get("exhausted:cpu", 0) >= 1
        # a fully infeasible fleet has no candidates — but the histogram
        # must say which axis to resize
        assert m.dimension_exhausted.get("cpu", 0) >= 1

    def test_explain_off_config_skips_ring_and_meta(self):
        from nomad_tpu.scheduler.testing import Harness
        from nomad_tpu.state.store import SchedulerConfiguration

        h = Harness()
        h.store.set_scheduler_config(
            1, SchedulerConfiguration(placement_explanations=False)
        )
        for _ in range(3):
            h.store.upsert_node(h.next_index(), mock.node())
        job = mock.job()
        h.store.upsert_job(h.next_index(), job)
        ev = mock.eval_for(job)
        h.process(ev)
        assert flight_recorder.explanation(ev.id) is None
        for a in h.store.allocs_by_job(job.namespace, job.id):
            assert not a.metrics.score_meta

    def test_system_scheduler_records_explanations(self):
        from nomad_tpu.scheduler.testing import Harness

        h = Harness()
        for _ in range(3):
            h.store.upsert_node(h.next_index(), mock.node())
        job = mock.job()
        job.type = "system"
        h.store.upsert_job(h.next_index(), job)
        ev = mock.eval_for(job)
        ev.type = "system"
        h.process(ev)
        payload = flight_recorder.explanation(ev.id)
        assert payload is not None
        group = payload["groups"][job.task_groups[0].name]
        assert group["nodes_evaluated"] == 3
        allocs = h.store.allocs_by_job(job.namespace, job.id)
        assert allocs
        for a in allocs:
            assert a.metrics.score_meta
            assert a.metrics.score_meta[0].node_id == a.node_id


# -- dry run (job plan) -----------------------------------------------------


class TestAnnotatePlan:
    def test_plan_returns_explanations_without_ringing(self):
        from nomad_tpu.scheduler.annotate import plan_job
        from nomad_tpu.state import StateStore

        store = StateStore()
        for i in range(3):
            store.upsert_node(i + 1, mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        before = flight_recorder.explanations_total
        out = plan_job(store, job)
        assert flight_recorder.explanations_total == before, (
            "dry run must not pollute the explanation ring"
        )
        group = out["placement_explanations"][job.task_groups[0].name]
        assert group["top_candidates"]
        assert len(group["placed_nodes"]) == 2
        assert out["annotations"][job.task_groups[0].name]["place"] == 2

    def test_plan_failed_groups_report_structured_detail(self):
        from nomad_tpu.scheduler.annotate import plan_job
        from nomad_tpu.state import StateStore

        store = StateStore()
        store.upsert_node(1, mock.node())
        job = mock.job()
        job.task_groups[0].tasks[0].resources.cpu = 10**9
        out = plan_job(store, job)
        failed = out["failed_tg_allocs"][job.task_groups[0].name]
        assert failed["dimension_exhausted"].get("cpu", 0) >= 1
        assert failed["rejections"].get("exhausted:cpu", 0) >= 1


# -- HTTP surface -----------------------------------------------------------


class TestHTTPSurface:
    def test_placement_and_explain_endpoints(self):
        from nomad_tpu.api.client import APIException, NomadClient
        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=1))
        server.establish_leadership()
        http = HTTPAgent(server, None, port=0)
        http.start()
        try:
            c = NomadClient(http.address)
            for _ in range(3):
                server.register_node(mock.node())
            job = mock.job()
            job.task_groups[0].count = 2
            ev = server.register_job(job)
            assert server.wait_for_evals(timeout=15)

            placement = c.evaluations.placement(ev.id)
            assert placement["eval_id"] == ev.id
            assert placement["source"] == "ring"
            group = placement["groups"][job.task_groups[0].name]
            assert group["top_candidates"][0]["rank"] == 1

            allocs = c.jobs.allocations(job.id)
            assert allocs
            why = c.allocations.explain(allocs[0]["id"])
            assert why["node_id"] == allocs[0]["node_id"]
            assert why["score_meta"], "alloc explain must carry score rows"
            assert (
                why["score_meta"][0]["node_id"] == allocs[0]["node_id"]
            )
            assert why["explanation"]["placed_nodes"]

            cfg = c.operator.scheduler_config()
            assert cfg["placement_explanations"] is True

            with pytest.raises(APIException):
                c.evaluations.placement("no-such-eval")
            with pytest.raises(APIException):
                c.allocations.explain("no-such-alloc")
        finally:
            http.stop()
            server.shutdown()


# -- lint rule NTA014 -------------------------------------------------------


class TestScoreDumpRule:
    def _findings(self, source, relpath):
        from nomad_tpu.analysis.lint import check_source
        from nomad_tpu.analysis.rules.scoredump import ScoreDumpDiscipline

        return check_source(source, relpath, [ScoreDumpDiscipline()])

    def test_flags_tolist_and_dump_sinks_in_scope(self):
        src = (
            "def f(res):\n"
            "    x = res.scores.tolist()\n"
            "    return json.dumps(res.node_rows)\n"
        )
        found = self._findings(src, "nomad_tpu/scheduler/foo.py")
        assert len(found) == 2
        assert all(f.rule == "NTA014" for f in found)

    def test_out_of_scope_and_compute_uses_pass(self):
        src = "def f(res):\n    return res.scores.tolist()\n"
        assert not self._findings(src, "nomad_tpu/obs/explain.py")
        compute = (
            "def f(res):\n"
            "    rows = res.node_rows[res.node_rows >= 0]\n"
            "    return float(res.scores[0])\n"
        )
        assert not self._findings(compute, "nomad_tpu/scheduler/foo.py")

    def test_repo_is_clean(self):
        from nomad_tpu.analysis.lint import repo_root, run_lint
        from nomad_tpu.analysis.rules.scoredump import ScoreDumpDiscipline

        findings = run_lint(repo_root(), rules=[ScoreDumpDiscipline()])
        assert findings == [], [str(f) for f in findings]
