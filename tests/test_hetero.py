"""Heterogeneity-aware scheduling (scheduler/hetero.py + algorithms.py).

Coverage map (ISSUE 9):
- device_class participates in the compute-class hash: identical nodes
  in different accelerator classes never share a computed class (or a
  device-cache class entry) — the hash-collision regression;
- jobspec/validate_job reject malformed throughput maps with structured
  errors before anything reaches the kernels;
- every hetero policy's device pass is BYTE-identical to its NumPy host
  oracle (the binpack parity discipline, device/parity.py, applied per
  policy);
- class-less fleets place bit-identically through HeteroPlacementKernel
  and the throughput-extended score_matrix_kernel (the None gate);
- mixed-fleet A/B: hetero-maxmin lifts the worst-class normalized share
  and hetero-makespan reduces modeled makespan vs binpack;
- device_class + throughputs round-trip the API codec and the state
  snapshot file;
- the algorithm registry drives selection end-to-end: a scheduler
  config naming hetero-maxmin routes a real eval through the hetero
  kernel onto the job's fast classes.

All tests are CPU-fast tier-1 (the mixed-fleet A/B runs a small fleet;
`run_hetero_ab`'s defaults are the 1k-node version).
"""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.api.codec import decode_job, decode_node, encode
from nomad_tpu.device.cache import DeviceStateCache
from nomad_tpu.device.flatten import (
    flatten_cluster,
    job_throughput_vector,
)
from nomad_tpu.device.score import PlacementKernel, score_matrix_kernel
from nomad_tpu.jobspec import JobspecError, parse_job_file
from nomad_tpu.scheduler import algorithms
from nomad_tpu.scheduler.hetero import (
    POLICY_IDS,
    HeteroPlacementKernel,
    build_hetero_batch,
    build_mixed_asks,
    build_mixed_fleet,
    hetero_place_kernel,
    oracle_hetero_place,
    run_hetero_ab,
)
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import SchedulerConfiguration, StateStore
from nomad_tpu.state.snapshot import restore_snapshot, save_snapshot
from nomad_tpu.structs.job import (
    JobValidationError,
    validate_job,
    validate_throughputs,
)


def _bits(a):
    return np.asarray(a, dtype=np.float32).view(np.uint32)


# -- satellite 1: device_class in the compute-class hash ---------------------


class TestComputeClassHash:
    def test_distinct_device_classes_hash_distinct(self):
        a = mock.node()
        b = mock.node(id=a.id, name=a.name, device_class="tpu-v5e")
        c = mock.node(id=a.id, name=a.name, device_class="tpu-v4")
        assert a.computed_class != b.computed_class
        assert b.computed_class != c.computed_class
        assert a.computed_class != c.computed_class

    def test_same_device_class_still_shares_class(self):
        a = mock.node(device_class="tpu-v5e")
        b = mock.node(device_class="tpu-v5e")
        assert a.computed_class == b.computed_class

    def test_flatten_never_shares_class_rows_across_device_classes(self):
        store = StateStore()
        n1 = mock.node(device_class="tpu-v5e")
        n2 = mock.node(device_class="gpu-a100")
        store.upsert_node(1, n1)
        store.upsert_node(2, n2)
        ct = flatten_cluster(store.snapshot())
        r1, r2 = ct.node_row[n1.id], ct.node_row[n2.id]
        assert ct.class_ids[r1] != ct.class_ids[r2]
        ids, vocab = ct.device_class_column()
        assert ids[r1] == vocab["tpu-v5e"]
        assert ids[r2] == vocab["gpu-a100"]
        assert ct.has_device_classes

    def test_cache_rebuilds_on_device_class_flip(self):
        store = StateStore()
        nodes = [mock.node() for _ in range(4)]
        for i, n in enumerate(nodes):
            store.upsert_node(i + 1, n)
        cache = DeviceStateCache()
        ct = cache.tensors(store.snapshot())
        assert not ct.has_device_classes
        assert cache.full_flattens == 1

        flip = nodes[0]
        flip.device_class = "tpu-v5e"
        flip.compute_class()
        store.upsert_node(50, flip)
        ct2 = cache.tensors(store.snapshot())
        # the class column can never be served stale: the flip forces a
        # full rebuild (device_class folds into computed_class)
        assert cache.full_flattens == 2
        ids, vocab = ct2.device_class_column()
        assert ids[ct2.node_row[flip.id]] == vocab["tpu-v5e"]
        assert ct2.has_device_classes


# -- satellite 2: throughput validation --------------------------------------


class TestThroughputValidation:
    def test_validate_throughputs_rejects_garbage(self):
        assert validate_throughputs({"tpu-v5e": 2.0, "cpu": 0.5}) == []
        for bad in (
            {"tpu-v5e": -1.0},
            {"tpu-v5e": float("nan")},
            {"tpu-v5e": float("inf")},
            {"tpu-v5e": "fast"},
            {"tpu-v5e": True},
            {"": 1.0},
            {3: 1.0},
        ):
            assert validate_throughputs(bad), bad
        assert validate_throughputs("not-a-dict")

    def test_validate_job_rejects_bad_throughputs(self):
        j = mock.job()
        j.throughputs = {"tpu-v5e": float("nan")}
        with pytest.raises(JobValidationError):
            validate_job(j)
        j.throughputs = {"tpu-v5e": 2.0, "cpu": 0.0}
        validate_job(j)  # zero = "cannot progress" is a valid statement

    def test_jobspec_parses_throughput_map(self):
        job = parse_job_file(
            """
job "hetero" {
  datacenters = ["dc1"]
  throughput = {
    "tpu-v5e" = 4.0
    "gpu-a100" = 2.0
    "cpu" = 0.5
  }
  group "g" {
    count = 2
    task "t" { driver = "exec" }
  }
}
"""
        )
        assert job.throughputs == {
            "tpu-v5e": 4.0,
            "gpu-a100": 2.0,
            "cpu": 0.5,
        }
        assert job.throughput_for("tpu-v5e") == 4.0
        assert job.throughput_for("tpu-v4") == 1.0  # unmapped → default
        assert job.throughput_for("") == 1.0

    def test_jobspec_rejects_negative_coefficient(self):
        with pytest.raises(JobspecError, match="invalid throughput"):
            parse_job_file(
                """
job "bad" {
  datacenters = ["dc1"]
  throughput = { "tpu-v5e" = -2.0 }
  group "g" { task "t" { driver = "exec" } }
}
"""
            )

    def test_jobspec_rejects_non_mapping_throughput(self):
        with pytest.raises(JobspecError, match="throughput must be a mapping"):
            parse_job_file(
                """
job "bad" {
  datacenters = ["dc1"]
  throughput = 2.0
  group "g" { task "t" { driver = "exec" } }
}
"""
            )


# -- per-policy oracle parity (byte-identical) -------------------------------


class TestOracleParity:
    @pytest.mark.parametrize("policy", sorted(POLICY_IDS))
    @pytest.mark.parametrize("seed", [42, 7])
    def test_device_pass_byte_identical_to_host_oracle(self, policy, seed):
        ct = build_mixed_fleet(48, seed=seed)
        asks = build_mixed_asks(ct, 6, 4, seed=seed + 1)
        b = build_hetero_batch(ct, asks)
        pid = POLICY_IDS[policy]
        d_choices, d_tp, d_used = hetero_place_kernel(
            b.capacity, b.used, b.asks, b.counts, b.eligible, b.tp,
            b.tpmax, b.cost, policy=pid, steps=b.steps, max_c=b.max_c,
        )
        o_choices, o_tp, o_used = oracle_hetero_place(
            b.capacity, b.used, b.asks, b.counts, b.eligible, b.tp,
            b.tpmax, b.cost, pid, b.steps, b.max_c,
        )
        np.testing.assert_array_equal(np.asarray(d_choices), o_choices)
        np.testing.assert_array_equal(_bits(d_tp), _bits(o_tp))
        np.testing.assert_array_equal(_bits(d_used), _bits(o_used))


# -- class-less fleets: bit-identical to the base kernels --------------------


def _classless_fleet(n=32, seed=3):
    ct = build_mixed_fleet(n, seed=seed)
    ct.device_class_ids = np.zeros(ct.padded_n, dtype=np.int32)
    ct.device_class_vocab = {"": 0}
    return ct


class TestClasslessByteIdentity:
    @pytest.mark.parametrize(
        "name", ["hetero-maxmin", "hetero-makespan", "hetero-cost"]
    )
    def test_hetero_kernels_delegate_bit_identically(self, name):
        ct = _classless_fleet()
        asks = build_mixed_asks(ct, 5, 3, seed=11)
        assert not any(a.has_throughputs for a in asks)
        base = [
            r for r in PlacementKernel("binpack").place(ct, asks)
        ]
        hk = algorithms.make_kernel(name)
        assert isinstance(hk, HeteroPlacementKernel)
        got = hk.place(ct, asks)
        for b, g in zip(base, got):
            np.testing.assert_array_equal(b.node_rows, g.node_rows)
            np.testing.assert_array_equal(_bits(b.scores), _bits(g.scores))

    def test_classed_fleet_with_agnostic_jobs_still_delegates(self):
        ct = build_mixed_fleet(32, seed=5)  # classes present...
        asks = build_mixed_asks(ct, 4, 3, seed=11)
        for a in asks:  # ...but no job differentiates
            a.throughputs = None
            a.has_throughputs = False
        base = PlacementKernel("binpack").place(ct, asks)
        got = HeteroPlacementKernel("maxmin").place(ct, asks)
        for b, g in zip(base, got):
            np.testing.assert_array_equal(b.node_rows, g.node_rows)
            np.testing.assert_array_equal(_bits(b.scores), _bits(g.scores))

    def test_score_matrix_none_gate_is_bit_identical(self):
        """The 11-arg legacy call and the 12-arg call with
        throughputs=None must produce bit-identical matrices — the
        Python-level None gate leaves the compiled program unchanged."""
        ct = _classless_fleet()
        asks = build_mixed_asks(ct, 4, 3, seed=13)
        a = asks[0]
        args = (
            ct.capacity,
            ct.used,
            a.ask[None, :],
            a.eligible[None, :],
            a.job_counts[None, :],
            np.array([4.0], dtype=np.float32),
            a.penalty_nodes[None, :],
            a.affinity_scores[None, :],
            np.array([a.has_affinities]),
            np.array([a.distinct_hosts]),
            np.asarray(False),
        )
        legacy_f, legacy_fit = score_matrix_kernel(*args)
        gated_f, gated_fit = score_matrix_kernel(*args, None)
        np.testing.assert_array_equal(
            _bits(legacy_f), _bits(gated_f)
        )
        np.testing.assert_array_equal(
            np.asarray(legacy_fit), np.asarray(gated_fit)
        )

    def test_score_matrix_throughput_term_scales_and_filters(self):
        ct = build_mixed_fleet(32, seed=5)
        asks = build_mixed_asks(ct, 3, 2, seed=11)
        a = next(x for x in asks if x.has_throughputs)
        tp = a.throughputs / max(
            float(np.max(np.where(a.eligible, a.throughputs, 0.0))), 1e-9
        )
        dead = a.throughputs * 0.0  # zero throughput everywhere
        args = (
            ct.capacity,
            ct.used,
            a.ask[None, :],
            a.eligible[None, :],
            a.job_counts[None, :],
            np.array([4.0], dtype=np.float32),
            a.penalty_nodes[None, :],
            a.affinity_scores[None, :],
            np.array([a.has_affinities]),
            np.array([a.distinct_hosts]),
            np.asarray(False),
        )
        base_f, base_fit = score_matrix_kernel(*args)
        tp_f, tp_fit = score_matrix_kernel(*args, tp[None, :].astype(np.float32))
        _, dead_fit = score_matrix_kernel(*args, dead[None, :])
        base_f, tp_f = np.asarray(base_f)[0], np.asarray(tp_f)[0]
        base_fit = np.asarray(base_fit)[0]
        # zero-throughput classes are infeasible for the job
        assert not np.asarray(dead_fit)[0].any()
        assert np.asarray(tp_fit)[0].sum() == base_fit.sum()
        # best-class nodes gain score relative to slow-class nodes
        fit_rows = np.nonzero(base_fit)[0]
        fast = [r for r in fit_rows if tp[r] == 1.0]
        slow = [r for r in fit_rows if tp[r] < 0.5]
        assert fast and slow
        delta_fast = tp_f[fast[0]] - base_f[fast[0]]
        delta_slow = tp_f[slow[0]] - base_f[slow[0]]
        assert delta_fast > delta_slow


# -- mixed-fleet A/B quality -------------------------------------------------


class TestMixedFleetAB:
    def test_ab_improves_worst_share_and_makespan(self):
        r = run_hetero_ab(n_nodes=200, n_jobs=9, count_per_job=10, seed=42)
        assert r["oracle_mismatches"] == 0
        assert r["ab"]["maxmin_improves_worst_share"]
        assert r["ab"]["makespan_reduced"]
        assert r["ok"]
        mm = r["policies"]["hetero-maxmin"]
        # the fair policy actually uses the heterogeneous fleet
        assert len([c for c in mm["per_class_allocs"] if c]) >= 3
        # cost policy buys at least as much throughput-per-cost as binpack
        assert (
            r["policies"]["hetero-cost"]["throughput_per_cost"]
            >= r["binpack"]["throughput_per_cost"]
        )

    def test_report_is_deterministic(self):
        import json

        a = run_hetero_ab(n_nodes=64, n_jobs=6, count_per_job=4, seed=9)
        b = run_hetero_ab(n_nodes=64, n_jobs=6, count_per_job=4, seed=9)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


# -- round-trips -------------------------------------------------------------


class TestRoundTrip:
    def test_codec_round_trips_device_class_and_throughputs(self):
        n = mock.node(device_class="gpu-a100")
        n2 = decode_node(encode(n))
        assert n2.device_class == "gpu-a100"
        n2.compute_class()
        assert n2.computed_class == n.computed_class

        j = mock.job(throughputs={"gpu-a100": 3.0, "cpu": 0.25})
        j2 = decode_job(encode(j))
        assert j2.throughputs == {"gpu-a100": 3.0, "cpu": 0.25}

    def test_state_snapshot_round_trips(self, tmp_path):
        store = StateStore()
        n = mock.node(device_class="tpu-v4")
        j = mock.job(throughputs={"tpu-v4": 2.5})
        store.upsert_node(1, n)
        store.upsert_job(2, j)
        path = str(tmp_path / "state.snap")
        save_snapshot(store, path)
        restored = restore_snapshot(path)
        rn = restored.node_by_id(n.id)
        rj = restored.job_by_id(j.namespace, j.id)
        assert rn.device_class == "tpu-v4"
        assert rj.throughputs == {"tpu-v4": 2.5}
        # the restored fleet flattens with its class column intact
        ct = flatten_cluster(restored.snapshot())
        assert ct.has_device_classes
        vec, has = job_throughput_vector(ct, rj)
        assert has
        assert vec[ct.node_row[n.id]] == np.float32(2.5)


# -- registry selection ------------------------------------------------------


class TestRegistrySelection:
    def test_builtins_registered(self):
        assert algorithms.available() == [
            "binpack",
            "cp-gang",
            "cp-pack",
            "hetero-cost",
            "hetero-makespan",
            "hetero-maxmin",
            "spread",
        ]
        assert algorithms.is_registered("hetero-maxmin")
        assert not algorithms.is_registered("bogus")
        with pytest.raises(algorithms.UnknownAlgorithmError):
            algorithms.make_kernel("bogus")

    def test_make_kernel_types(self):
        assert isinstance(
            algorithms.make_kernel("binpack"), PlacementKernel
        )
        assert algorithms.make_kernel("spread").algorithm_spread
        k = algorithms.make_kernel("hetero-makespan")
        assert isinstance(k, HeteroPlacementKernel)
        assert k.policy == "makespan"

    def test_scheduler_config_selects_hetero_end_to_end(self):
        """A registered eval processed under scheduler_algorithm =
        hetero-maxmin lands the throughput-carrying job on its fast
        device classes — the registry seam drives the real scheduler."""
        h = Harness()
        for dc in ("tpu-v5e", "tpu-v5e", "gpu-a100", "cpu", "cpu", "cpu"):
            h.store.upsert_node(h.next_index(), mock.node(device_class=dc))
        h.store.set_scheduler_config(
            h.next_index(),
            SchedulerConfiguration(scheduler_algorithm="hetero-maxmin"),
        )
        j = mock.job(throughputs={"tpu-v5e": 4.0, "gpu-a100": 2.0, "cpu": 0.25})
        j.task_groups[0].count = 3
        h.store.upsert_job(h.next_index(), j)
        h.process(mock.eval_for(j))
        allocs = [
            a
            for a in h.store.allocs_by_job(j.namespace, j.id)
            if not a.terminal_status()
        ]
        assert len(allocs) == 3
        placed_classes = {
            h.store.node_by_id(a.node_id).device_class for a in allocs
        }
        # the fair hetero pass never touches the slow cpu tier while
        # accelerators have room
        assert "cpu" not in placed_classes
        assert placed_classes & {"tpu-v5e", "gpu-a100"}
