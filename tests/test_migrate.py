"""Migration plane device/host contract: ``migrate_plan_kernel`` is
byte-identical to its NumPy oracle across seeds and meshes, budget is a
dynamic operand (sweeping it never retraces), the oracle honours its
budget/capacity model, and the ``run_defrag_ab`` gate is a
byte-reproducible tier-1 smoke."""

import json

import numpy as np
import pytest

from nomad_tpu.device.migrate import (
    migrate_plan_kernel,
    oracle_migrate_plan,
    packing_efficiency,
)
from nomad_tpu.scheduler.migrate import (
    DEFRAG_SCHEMA,
    MOVE_COST,
    build_defrag_batch,
    build_defrag_fleet,
    consolidation_scores,
    run_defrag_ab,
    _steps_for,
)
from nomad_tpu.utils import backend


def _batch(n_nodes=32, n_allocs=64, seed=42):
    capacity, used, sizes, cur, ready = build_defrag_fleet(
        n_nodes, n_allocs, seed=seed
    )
    args = build_defrag_batch(capacity, used, sizes, cur)
    lam0 = np.zeros(n_nodes, dtype=np.float32)
    return args, lam0, _steps_for(n_allocs)


def _assert_bitwise(d, o):
    np.testing.assert_array_equal(np.asarray(d[0]), o[0])  # dest i32
    # f32 outputs compare as uint32 views: byte-identical, not close
    np.testing.assert_array_equal(
        np.asarray(d[1]).view(np.uint32), o[1].view(np.uint32)
    )
    np.testing.assert_array_equal(
        np.asarray(d[2]).view(np.uint32), o[2].view(np.uint32)
    )
    assert int(np.asarray(d[3])) == o[3]
    np.testing.assert_array_equal(
        np.asarray(d[5]).view(np.uint32), o[5].view(np.uint32)
    )


# -- device/oracle byte parity ----------------------------------------------


class TestOracleParity:
    @pytest.mark.parametrize("seed", [3, 11, 42])
    def test_device_matches_oracle_bitwise(self, seed):
        args, lam0, steps = _batch(seed=seed)
        d = migrate_plan_kernel(*args, np.int32(8), lam0, steps=steps)
        o = oracle_migrate_plan(*args, np.int32(8), lam0, steps)
        _assert_bitwise(d, o)
        # the pass did real work on a fragmented fleet
        assert (np.asarray(d[0]) >= 0).any()

    @pytest.mark.parametrize("budget", [0, 1, 4, 96])
    def test_parity_across_budgets(self, budget):
        args, lam0, steps = _batch()
        d = migrate_plan_kernel(*args, np.int32(budget), lam0, steps=steps)
        o = oracle_migrate_plan(*args, np.int32(budget), lam0, steps)
        _assert_bitwise(d, o)
        assert int(np.asarray(d[3])) <= budget


# -- mesh equivalence --------------------------------------------------------


@pytest.fixture
def mesh_env(monkeypatch):
    def activate(spec):
        monkeypatch.setenv("NOMAD_TPU_MESH", spec)
        backend.reset_mesh()
        return backend.get_mesh()

    yield activate
    monkeypatch.delenv("NOMAD_TPU_MESH", raising=False)
    backend.reset_mesh()


class TestMeshEquivalence:
    @pytest.mark.parametrize("spec", ["2,4", "1,8", "4,2"])
    def test_mesh_run_byte_equal_to_oracle(self, spec, mesh_env):
        args, lam0, steps = _batch()
        o = oracle_migrate_plan(*args, np.int32(8), lam0, steps)
        mesh_env(spec)
        d = migrate_plan_kernel(*args, np.int32(8), lam0, steps=steps)
        _assert_bitwise(d, o)


# -- retrace discipline ------------------------------------------------------


class TestRetraceDiscipline:
    def test_budget_is_dynamic_zero_added_retraces(self):
        from nomad_tpu.analysis import retrace

        args, lam0, steps = _batch()
        migrate_plan_kernel(*args, np.int32(8), lam0, steps=steps)
        base = dict(retrace.counts())
        for budget in (0, 1, 2, 8, 64):
            migrate_plan_kernel(
                *args, np.int32(budget), lam0, steps=steps
            )
        assert dict(retrace.counts()) == base, (
            "budget is a dynamic operand: sweeping it must not retrace"
        )


# -- oracle invariants -------------------------------------------------------


class TestOracleInvariants:
    def test_used_only_increases_and_fits(self):
        args, lam0, steps = _batch()
        capacity, used0 = args[0], args[1]
        dest, gains, used, moves, rounds, lam = oracle_migrate_plan(
            *args, np.int32(8), lam0, steps
        )
        # sources are never credited back inside a pass (law 16's
        # conservative mid-move capacity model)
        assert (used >= used0 - np.float32(1e-3)).all()
        assert (used <= capacity + np.float32(1e-3)).all()

    def test_budget_caps_moves_exactly(self):
        args, lam0, steps = _batch()
        for budget in (0, 1, 3, 8):
            dest, _, _, moves, _, _ = oracle_migrate_plan(
                *args, np.int32(budget), lam0, steps
            )
            assert moves == int((dest >= 0).sum())
            assert moves <= budget

    def test_moves_strictly_positive_priced_gain(self):
        args, lam0, steps = _batch()
        dest, gains, _, moves, _, _ = oracle_migrate_plan(
            *args, np.int32(8), lam0, steps
        )
        moved = dest >= 0
        assert moves > 0
        assert (gains[moved] > 0.0).all()
        assert (gains[~moved] == 0.0).all()
        # no move "to" the current node
        cur = args[3]
        assert (dest[moved] != cur[moved]).all()

    def test_zero_move_cost_still_capacity_safe(self):
        capacity, used, sizes, cur, _ = build_defrag_fleet(16, 48, seed=9)
        args = list(build_defrag_batch(capacity, used, sizes, cur))
        args[7] = np.zeros_like(args[7])  # move_cost = 0: max pressure
        lam0 = np.zeros(16, dtype=np.float32)
        _, _, u, _, _, _ = oracle_migrate_plan(
            *args, np.int32(48), lam0, _steps_for(48)
        )
        assert (u <= capacity + np.float32(1e-3)).all()


# -- batch assembly ----------------------------------------------------------


class TestBatchAssembly:
    def test_own_contribution_subtracted_from_stay_value(self):
        # uniform smear: every node identically thin. With the alloc's
        # own load counted in its stay-value, every move prices as a
        # loss and consolidation can never start.
        capacity, used, sizes, cur, _ = build_defrag_fleet(24, 48, seed=5)
        args = build_defrag_batch(capacity, used, sizes, cur)
        scores, cur_scores = args[5], args[6]
        arange = np.arange(sizes.shape[0])
        assert (cur_scores <= scores[arange, cur] + np.float32(1e-6)).all()
        assert (cur_scores < scores[arange, cur]).any()

    def test_scores_are_destination_utilization(self):
        capacity, used, sizes, cur, _ = build_defrag_fleet(8, 16, seed=2)
        scores = consolidation_scores(capacity, used, sizes)
        denom = capacity[:, :2].sum(axis=1)
        util = used[:, :2].sum(axis=1) / denom
        np.testing.assert_allclose(scores[0], util.astype(np.float32))
        assert scores.dtype == np.float32
        assert scores.shape == (16, 8)

    def test_fleet_never_built_over_capacity(self):
        for seed in (1, 7, 42):
            capacity, used, _, _, _ = build_defrag_fleet(12, 64, seed=seed)
            assert (used <= capacity).all()

    def test_move_cost_is_exact_f32_power_of_two(self):
        assert MOVE_COST == np.float32(0.0625)
        assert float(MOVE_COST).hex() == "0x1.0000000000000p-4"


# -- packing efficiency gauge ------------------------------------------------


class TestPackingEfficiency:
    def test_consolidated_is_one_fragmented_is_low(self):
        capacity = np.full((8, 2), 100.0, dtype=np.float32)
        ready = np.ones(8, dtype=bool)
        packed = np.zeros((8, 2), dtype=np.float32)
        packed[0] = [100.0, 100.0]
        packed[1] = [100.0, 100.0]
        assert packing_efficiency(capacity, packed, ready) == 1.0
        smeared = np.full((8, 2), 25.0, dtype=np.float32)
        assert packing_efficiency(capacity, smeared, ready) == 0.0

    def test_not_ready_nodes_excluded(self):
        capacity = np.full((4, 1), 10.0, dtype=np.float32)
        used = np.zeros((4, 1), dtype=np.float32)
        used[3] = 5.0
        ready = np.array([True, True, True, False])
        assert packing_efficiency(capacity, used, ready) == 1.0

    def test_empty_fleet_is_one(self):
        capacity = np.zeros((0, 2), dtype=np.float32)
        assert packing_efficiency(
            capacity, capacity, np.zeros(0, dtype=bool)
        ) == 1.0


# -- run_defrag_ab gate smoke (tier-1) ---------------------------------------


def _flatten(d, prefix=""):
    out = []
    for k, v in d.items():
        path = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            out.extend(_flatten(v, path))
        else:
            out.append(path)
    return out


class TestBenchGate:
    def test_defrag_ab_ok_and_schema_pinned(self):
        report = run_defrag_ab(n_nodes=24, n_allocs=48, budget=6, seed=42)
        assert report["ok"], report
        assert tuple(sorted(_flatten(report))) == DEFRAG_SCHEMA
        assert report["oracle_mismatches"] == 0
        assert report["capacity_violations"] == 0
        assert (
            report["after"]["packing_efficiency"]
            > report["before"]["packing_efficiency"]
        )
        assert report["recovered_fraction"] >= 0.5

    def test_defrag_ab_byte_reproducible(self):
        a = run_defrag_ab(n_nodes=24, n_allocs=48, budget=6, seed=42)
        b = run_defrag_ab(n_nodes=24, n_allocs=48, budget=6, seed=42)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
