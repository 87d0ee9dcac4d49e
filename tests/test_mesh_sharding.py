"""Multi-chip sharding tests: the placement kernels under a real
``jax.sharding.Mesh`` (8 virtual CPU devices via conftest) must produce
bit-identical results to the single-device run.

Production layout (SURVEY.md §2.7): node axis model-parallel over ICI,
group/eval axis data-parallel; per-step argmax/top-k is the cross-shard
reduction. This is the sharding the driver's dryrun_multichip validates;
these tests pin its numerical equivalence.
"""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft
from nomad_tpu.device.score import (
    place_closed_form_kernel,
    place_value_scan_kernel,
    score_matrix_kernel,
)


def _mesh(dp=2, mp=4):
    devices = np.array(jax.devices()[: dp * mp]).reshape(dp, mp)
    return Mesh(devices, ("groups", "nodes"))


def _shard(batch, mesh, specs):
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in batch.items()
    }


SPECS = dict(
    capacity=P("nodes", None),
    used0=P("nodes", None),
    asks=P("groups", None),
    eligible=P("groups", "nodes"),
    job_counts=P("groups", "nodes"),
    desired_totals=P("groups"),
    penalty_nodes=P("groups", "nodes"),
    affinity_scores=P("groups", "nodes"),
    has_affinities=P("groups"),
    distinct_hosts=P("groups"),
    block_value_ids=P("groups", None, "nodes"),
    block_counts0=P("groups", None, None),
    block_desired=P("groups", None, None),
    block_caps=P("groups", None, None),
    block_weights=P("groups", None),
    block_kinds=P("groups", None),
    slot_caps=P("groups", "nodes"),
    algorithm_spread=P(),
    counts=P("groups"),
)


def test_value_scan_kernel_sharded_matches_single_device():
    batch = graft._example_batch(n_nodes=512, n_groups=8, max_steps=8)
    batch["counts"] = np.full(8, 8, dtype=np.int32)
    batch["desired_totals"] = np.full(8, 8.0, dtype=np.float32)

    ref_c, ref_s = place_value_scan_kernel(**batch, max_j=16, max_steps=8)

    mesh = _mesh()
    sharded = _shard(batch, mesh, SPECS)
    with mesh:
        c, s = place_value_scan_kernel(**sharded, max_j=16, max_steps=8)
        jax.block_until_ready((c, s))

    np.testing.assert_array_equal(np.asarray(c), np.asarray(ref_c))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    assert (np.asarray(c) >= 0).all()


def _split_fused(fused, k):
    """closed-form kernel returns [G, 2k] i32: rows ++ bitcast scores."""
    fused = np.asarray(fused)
    return fused[:, :k], fused[:, k:].view(np.float32)


def test_closed_form_kernel_sharded_matches_single_device():
    batch = graft._closed_form_batch(n_nodes=512, n_groups=8, count=16)

    ref_c, ref_s = _split_fused(
        place_closed_form_kernel(**batch, max_j=16, k=16), 16
    )

    mesh = _mesh()
    specs = {k: SPECS[k] for k in batch}
    sharded = _shard(batch, mesh, specs)
    with mesh:
        fused = place_closed_form_kernel(**sharded, max_j=16, k=16)
        jax.block_until_ready(fused)
    c, s = _split_fused(fused, 16)

    np.testing.assert_array_equal(c, ref_c)
    np.testing.assert_allclose(s, ref_s, rtol=1e-6)


def test_score_matrix_kernel_node_sharded():
    batch = graft._example_batch(n_nodes=512, n_groups=8, max_steps=8)
    args = dict(
        capacity=batch["capacity"],
        used=batch["used0"],
        asks=batch["asks"],
        eligible=batch["eligible"],
        job_counts=batch["job_counts"],
        desired_totals=batch["desired_totals"],
        penalty_nodes=batch["penalty_nodes"],
        affinity_scores=batch["affinity_scores"],
        has_affinities=batch["has_affinities"],
        distinct_hosts=batch["distinct_hosts"],
        algorithm_spread=batch["algorithm_spread"],
    )
    ref_final, ref_fits = score_matrix_kernel(**args)

    mesh = _mesh()
    specs = dict(SPECS, used=P("nodes", None))
    sharded = _shard(args, mesh, specs)
    with mesh:
        final, fits = score_matrix_kernel(**sharded)
        jax.block_until_ready((final, fits))

    np.testing.assert_allclose(np.asarray(final), np.asarray(ref_final), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(fits), np.asarray(ref_fits))


def test_mesh_shapes_1x8_and_4x2():
    """The layout must work at other mesh aspect ratios (different dp/mp
    splits of the same 8 chips)."""
    batch = graft._closed_form_batch(n_nodes=512, n_groups=8, count=8)
    ref_c, _ = _split_fused(
        place_closed_form_kernel(**batch, max_j=8, k=8), 8
    )
    for dp, mp in [(1, 8), (4, 2)]:
        mesh = _mesh(dp, mp)
        specs = {k: SPECS[k] for k in batch}
        sharded = _shard(batch, mesh, specs)
        with mesh:
            fused = place_closed_form_kernel(**sharded, max_j=8, k=8)
            jax.block_until_ready(fused)
        c, _ = _split_fused(fused, 8)
        np.testing.assert_array_equal(c, ref_c)


def test_dryrun_multichip_in_process():
    """With 8 virtual devices provisioned (conftest), the driver's dryrun
    entry runs in this process and passes."""
    graft.dryrun_multichip(8)


def test_dryrun_multichip_raises_without_enough_devices(monkeypatch):
    """Asked for more devices than jax shows, the dryrun raises — it
    never starts another process to find them."""
    import subprocess

    def no_spawn(*a, **kw):
        raise AssertionError("dryrun_multichip must not spawn a process")

    monkeypatch.setattr(subprocess, "run", no_spawn)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    with pytest.raises(RuntimeError, match="only 8 cpu device"):
        graft.dryrun_multichip(16)


# -- mesh seam (utils/backend.py) -------------------------------------------

from nomad_tpu.utils import backend  # noqa: E402


@pytest.fixture
def mesh_env(monkeypatch):
    """Opt a test into an active process-wide mesh via the env seam;
    restores the degenerate CPU default afterwards."""

    def activate(spec):
        monkeypatch.setenv("NOMAD_TPU_MESH", spec)
        backend.reset_mesh()
        return backend.get_mesh()

    yield activate
    monkeypatch.delenv("NOMAD_TPU_MESH", raising=False)
    backend.reset_mesh()


class TestMeshSeam:
    def test_parse_mesh_spec(self):
        assert backend.parse_mesh_spec("off") == "off"
        assert backend.parse_mesh_spec("0") == "off"
        assert backend.parse_mesh_spec("none") == "off"
        assert backend.parse_mesh_spec("auto") == "auto"
        assert backend.parse_mesh_spec("2,4") == (2, 4)
        assert backend.parse_mesh_spec(" 1 , 8 ") == (1, 8)
        for junk in ("2x4", "2,4,1", "0,4", "2,3"):
            with pytest.raises(ValueError):
                backend.parse_mesh_spec(junk)

    def test_auto_mesh_shape(self):
        assert backend.auto_mesh_shape(1) == (1, 1)
        assert backend.auto_mesh_shape(2) == (1, 2)
        assert backend.auto_mesh_shape(4) == (2, 2)
        assert backend.auto_mesh_shape(8) == (2, 4)
        assert backend.auto_mesh_shape(12) == (2, 4)  # largest pow2 <= n
        assert backend.auto_mesh_shape(16) == (2, 8)  # nodes axis caps at 8

    def test_cpu_default_is_degenerate(self, monkeypatch):
        # the 8-virtual-CPU-device test rig must NOT auto-activate:
        # the single-device jaxpr suite is the reference
        monkeypatch.delenv("NOMAD_TPU_MESH", raising=False)
        backend.reset_mesh()
        cfg = backend.get_mesh()
        assert not cfg.active
        assert cfg.n_node_shards == 1
        backend.reset_mesh()

    def test_env_activates_and_describes(self, mesh_env):
        cfg = mesh_env("2,4")
        assert cfg.active and (cfg.dp, cfg.mp) == (2, 4)
        d = cfg.describe()
        assert d["shape"] == [2, 4]
        assert d["axis_names"] == ["groups", "nodes"]

    def test_shard_put_layouts(self, mesh_env):
        cfg = mesh_env("2,4")
        x = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
        arr = backend.shard_put(x, ("nodes",), cfg)
        assert arr.sharding.spec == P("nodes")
        np.testing.assert_array_equal(np.asarray(arr), x)
        # an axis that does not divide the dim stays replicated
        odd = np.ones((6, 4), dtype=np.float32)
        arr2 = backend.shard_put(odd, ("nodes",), cfg)
        assert arr2.sharding.spec in (P(), P(None), P(None, None))
        # degenerate config is a plain asarray (unchanged jaxpr)
        degen = backend.MeshConfig(None, 1, 1, "test")
        assert not hasattr(
            backend.shard_put(x, ("nodes",), degen).sharding, "mesh"
        ) or backend.shard_put(x, ("nodes",), degen).sharding.is_fully_replicated


# -- hierarchical cross-shard top-k (the per-step reduction) ----------------


class TestHierarchicalTopK:
    @pytest.mark.parametrize("seed", [42, 7])
    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    def test_bit_identical_to_global_topk(self, seed, n_shards):
        """Per-shard local top-k + cross-shard merge must equal the
        global lax.top_k byte-for-byte — values AND indices — including
        across tie groups that straddle shard boundaries."""
        from nomad_tpu.device.score import _topk_nodes

        rng = np.random.default_rng(seed)
        for _ in range(10):
            # heavy ties: few distinct values over a big flat axis
            flat = rng.choice(
                np.array([-np.inf, 0.0, 1.0, 2.0, 3.0], dtype=np.float32),
                size=1024,
            )
            k = int(rng.integers(1, 33))
            ref_v, ref_i = jax.lax.top_k(jax.numpy.asarray(flat), k)
            v, i = _topk_nodes(jax.numpy.asarray(flat), k, n_shards)
            np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v))
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))


# -- hetero joint kernel under the mesh (all three policies) ----------------

from nomad_tpu.scheduler.hetero import (  # noqa: E402
    POLICY_IDS,
    build_hetero_batch,
    build_mixed_asks,
    build_mixed_fleet,
    hetero_place_kernel,
)

MESH_SHAPES = [(2, 4), (1, 8), (4, 2)]


class TestHeteroKernelSharded:
    @pytest.mark.parametrize("policy", sorted(POLICY_IDS))
    @pytest.mark.parametrize("dp,mp", MESH_SHAPES)
    def test_sharded_matches_single_device(self, policy, dp, mp):
        ct = build_mixed_fleet(48, seed=11)
        asks = build_mixed_asks(ct, 8, 4, seed=12)
        b = build_hetero_batch(ct, asks)
        pid = POLICY_IDS[policy]
        ref = hetero_place_kernel(
            b.capacity, b.used, b.asks, b.counts, b.eligible, b.tp,
            b.tpmax, b.cost, policy=pid, steps=b.steps, max_c=b.max_c,
        )
        mesh = _mesh(dp, mp)
        args = dict(
            capacity=b.capacity, used=b.used, asks=b.asks, counts=b.counts,
            eligible=b.eligible, tp=b.tp, tpmax=b.tpmax,
        )
        specs = dict(
            capacity=P("nodes", None), used=P("nodes", None),
            asks=P("groups", None), counts=P("groups"),
            eligible=P("groups", "nodes"), tp=P("groups", "nodes"),
            tpmax=P("groups"),
        )
        sharded = _shard(args, mesh, specs)
        with mesh:
            got = hetero_place_kernel(
                sharded["capacity"], sharded["used"], sharded["asks"],
                sharded["counts"], sharded["eligible"], sharded["tp"],
                sharded["tpmax"], b.cost,
                policy=pid, steps=b.steps, max_c=b.max_c,
            )
            jax.block_until_ready(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# -- preemption kernels under the mesh --------------------------------------

from nomad_tpu.device.preempt import (  # noqa: E402
    choose_preemption_node_kernel,
    find_preemption_kernel,
)


def _preempt_case(seed, n=64, v=8, d=4):
    rng = np.random.default_rng(seed)
    capacity = rng.uniform(100, 200, size=(n, d)).astype(np.float32)
    used = (capacity * rng.uniform(0.6, 0.98, size=(n, d))).astype(
        np.float32
    )
    return dict(
        capacity=capacity,
        used=used,
        ask=np.array([40.0, 30.0, 10.0, 0.0], dtype=np.float32)[:d],
        eligible=rng.random(n) < 0.9,
        victim_res=rng.uniform(5, 40, size=(n, v, d)).astype(np.float32),
        victim_prio=rng.integers(0, 50, size=(n, v)).astype(np.int32),
        victim_mask=rng.random((n, v)) < 0.7,
    )


_PREEMPT_SPECS = dict(
    capacity=P("nodes", None),
    used=P("nodes", None),
    ask=P(),
    eligible=P("nodes"),
    victim_res=P("nodes", None, None),
    victim_prio=P("nodes", None),
    victim_mask=P("nodes", None),
    victim_dev=P("nodes", None),
    dev_need=P("nodes"),
)


class TestPreemptKernelsSharded:
    @pytest.mark.parametrize("dp,mp", MESH_SHAPES)
    def test_find_preemption_sharded_matches(self, dp, mp):
        case = _preempt_case(seed=5)
        ref = find_preemption_kernel(**case)
        mesh = _mesh(dp, mp)
        sharded = _shard(case, mesh, _PREEMPT_SPECS)
        with mesh:
            got = find_preemption_kernel(**sharded)
            jax.block_until_ready(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    @pytest.mark.parametrize("dp,mp", MESH_SHAPES)
    def test_choose_node_sharded_matches(self, dp, mp):
        """The knapsack's final argmax runs over the sharded node axis —
        the cross-shard tie-break must stay lowest-index."""
        case = _preempt_case(seed=9)
        rng = np.random.default_rng(9)
        n, v = case["victim_mask"].shape
        # a third of the victims hold a device instance, half of the
        # nodes lack one for the ask
        case["victim_dev"] = (rng.random((n, v)) < 0.33).astype(np.int32)
        case["dev_need"] = (rng.random(n) < 0.5).astype(np.int32)
        ref = choose_preemption_node_kernel(**case)
        mesh = _mesh(dp, mp)
        sharded = _shard(case, mesh, _PREEMPT_SPECS)
        with mesh:
            got = choose_preemption_node_kernel(**sharded)
            jax.block_until_ready(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# -- production path: registry-dispatched kernel under the mesh -------------


def _mesh_cfg(dp, mp):
    return backend.MeshConfig(_mesh(dp, mp), dp, mp, "test")


def _degenerate_cfg():
    return backend.MeshConfig(None, 1, 1, "test")


def test_shard_put_counts_the_axes_it_replicates(mesh_env):
    """An axis the mesh does not divide is replicated — and counted, so
    'everything on every device' is never a silent layout."""
    cfg = mesh_env("2,4")
    assert backend.shard_drops() == {}
    x = backend.shard_put(
        np.zeros((1, 16), np.float32), ("groups", "nodes"), cfg
    )
    assert x.sharding.spec == jax.sharding.PartitionSpec(None, "nodes")
    backend.shard_put(np.zeros((2, 1), np.float32), ("groups", "nodes"), cfg)
    backend.shard_put(np.zeros((2, 16), np.float32), ("groups", "nodes"), cfg)
    assert backend.shard_drops() == {"groups:1%2": 1, "nodes:1%4": 1}


class TestProductionPathSharded:
    @pytest.mark.parametrize("seed", [42, 7])
    def test_placement_kernel_bit_identical_under_mesh(self, seed):
        """The full PlacementKernel.place path (batch build, shard_put
        seam, hierarchical top-k, overflow repair) through the registry
        must place bit-identically to the single-device reference."""
        from nomad_tpu import mock
        from nomad_tpu.scheduler.algorithms import make_kernel

        ct = mock.build_cluster(1000, seed=seed)
        asks = mock.build_asks(ct, 16, 64, seed=seed + 1)
        ref = make_kernel("binpack", mesh=_degenerate_cfg()).place(ct, asks)
        got = make_kernel("binpack", mesh=_mesh_cfg(2, 4)).place(ct, asks)
        assert len(ref) == len(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g.node_rows, r.node_rows)
            np.testing.assert_array_equal(
                g.scores.view(np.int32), r.scores.view(np.int32)
            )

    @pytest.mark.parametrize("seed", [42, 7])
    def test_spread_kernel_bit_identical_under_mesh(self, seed):
        from nomad_tpu import mock
        from nomad_tpu.scheduler.algorithms import make_kernel

        ct = mock.build_cluster(500, seed=seed)
        asks = mock.build_asks(ct, 8, 32, seed=seed + 1)
        ref = make_kernel("spread", mesh=_degenerate_cfg()).place(ct, asks)
        got = make_kernel("spread", mesh=_mesh_cfg(2, 4)).place(ct, asks)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g.node_rows, r.node_rows)
            np.testing.assert_array_equal(
                g.scores.view(np.int32), r.scores.view(np.int32)
            )

    def test_worker_pass_through_harness_matches_single_device(
        self, mesh_env
    ):
        """The production scheduler path end to end — store → device
        cache → flatten → registry kernel → plan apply — must commit the
        same alloc→node assignment mesh-on as mesh-off."""
        from nomad_tpu import mock
        from nomad_tpu.scheduler import Harness

        def run_once():
            h = Harness()
            for i in range(12):
                node = mock.node()
                node.id = f"node-{i:02d}"
                node.datacenter = "dc1" if i % 2 else "dc2"
                h.store.upsert_node(i + 1, node)
            placements = {}
            for j in range(4):
                job = mock.job()
                job.id = f"mesh-job-{j}"
                job.task_groups[0].count = 6
                h.store.upsert_job(h.next_index(), job)
                ev = mock.eval_for(job)
                h.store.upsert_evals(h.next_index(), [ev])
                h.process(ev)
                for a in h.store.allocs_by_job(job.namespace, job.id):
                    placements[(job.id, a.index())] = a.node_id
            return placements

        ref = run_once()
        mesh_env("2,4")
        got = run_once()
        assert got == ref


# -- explain seam under node sharding ---------------------------------------


class TestExplainUnderMesh:
    def test_explain_gathers_candidates_and_adds_zero_retraces(
        self, mesh_env
    ):
        """With the node axis sharded, explain-on must (a) keep the same
        top pick the kernel placed, (b) add ZERO retraces — the
        provenance path is host-side numpy over the gathered candidate
        columns only."""
        from nomad_tpu import mock
        from nomad_tpu.analysis import retrace
        from nomad_tpu.scheduler.algorithms import make_kernel

        mesh_env("2,4")
        ct = mock.build_cluster(500, seed=3)
        asks = mock.build_asks(ct, 4, 16, seed=4)
        kernel = make_kernel("binpack")
        assert kernel.mesh_cfg().active
        kernel.place(ct, asks)  # warm the shape bucket
        base = dict(retrace.counts())
        results = kernel.place(ct, asks, explain=True)
        assert dict(retrace.counts()) == base, (
            "explain=True under an active mesh must not add a retrace"
        )
        for r in results:
            ex = r.explanation
            assert ex is not None and ex.top_candidates
            placed = [int(x) for x in r.node_rows if x >= 0]
            assert int(ex.top_candidates[0].node_row) == placed[0]


# -- DeviceStateCache: per-shard incremental refresh ------------------------

from nomad_tpu.chaos.plane import (  # noqa: E402
    FaultPlane,
    FaultSpec,
    install,
    uninstall,
)
from nomad_tpu.device.cache import DeviceStateCache  # noqa: E402
from nomad_tpu.state import StateStore  # noqa: E402


def _mesh_store(n=12):
    from nomad_tpu import mock

    store = StateStore()
    for i in range(n):
        node = mock.node()
        node.id = f"node-{i:02d}"
        node.datacenter = "dc1" if i % 2 else "dc2"
        store.upsert_node(i + 1, node)
    return store


class TestCachePerShardRefresh:
    def test_steady_state_node_update_uploads_one_shard(self, mesh_env):
        mesh_env("2,4")
        store = _mesh_store(12)  # padded bucket 16, 4 shards of 4 rows
        cache = DeviceStateCache()
        ct = cache.tensors(store.snapshot())
        assert ct.device_capacity is not None
        assert cache.device_counters()["full_uploads"] == 1
        assert cache.device_counters()["shard_uploads"] == 0

        # steady-state: one node's capacity changes -> incremental
        # refresh + ONE per-shard upload, no reflatten, no full upload
        node = store.snapshot().node_by_id("node-03")
        node.node_resources.cpu = 12_345
        store.upsert_node(100, node)
        ct2 = cache.tensors(store.snapshot())
        assert cache.full_flattens == 1
        assert cache.incremental_refreshes == 1
        c = cache.device_counters()
        assert c["full_uploads"] == 1
        assert c["shard_uploads"] == 1
        row = ct2.node_row["node-03"]
        got = np.asarray(ct2.device_capacity)
        np.testing.assert_array_equal(got[row], ct2.capacity[row])
        assert cache.verify_device_view() == []

    def test_alloc_churn_does_not_touch_device_view(self, mesh_env):
        from nomad_tpu import mock

        mesh_env("2,4")
        store = _mesh_store(12)
        cache = DeviceStateCache()
        cache.tensors(store.snapshot())
        # alloc churn mutates `used` only; the device view holds
        # capacity — the steady-state scheduling loop re-uploads nothing
        store.upsert_allocs(200, [mock.alloc(node_id="node-05")])
        cache.tensors(store.snapshot())
        c = cache.device_counters()
        assert c["full_uploads"] == 1
        assert c["shard_uploads"] == 0
        assert cache.verify_device_view() == []

    def test_chaos_shard_refresh_drop_recovers_via_full_upload(
        self, mesh_env
    ):
        mesh_env("2,4")
        store = _mesh_store(12)
        cache = DeviceStateCache()
        cache.tensors(store.snapshot())
        node = store.snapshot().node_by_id("node-07")
        node.node_resources.cpu = 9_999
        store.upsert_node(101, node)
        plane = FaultPlane(
            schedule=[FaultSpec("mesh.shard_refresh_drop", 0, "drop")]
        )
        install(plane)
        try:
            ct = cache.tensors(store.snapshot())
        finally:
            uninstall()
        # the dropped per-shard upload must NOT leave a stale slice:
        # recovery is a whole-tensor re-upload on the same access
        c = cache.device_counters()
        assert c["full_uploads"] == 2
        assert c["shard_uploads"] == 0
        row = ct.node_row["node-07"]
        np.testing.assert_array_equal(
            np.asarray(ct.device_capacity)[row], ct.capacity[row]
        )
        assert cache.verify_device_view() == []
        assert ("mesh.shard_refresh_drop", 0, "drop") in plane.triggered

    def test_region_major_layout_is_contiguous(self, mesh_env):
        mesh_env("2,4")
        store = _mesh_store(12)
        ct = DeviceStateCache().tensors(store.snapshot())
        ids = ct.region_ids[: ct.num_nodes]
        assert (np.diff(ids) >= 0).all(), "regions must be contiguous"
        assert set(ct.region_vocab.values()) == set(np.unique(ids))
