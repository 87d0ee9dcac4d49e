"""DeviceStateCache: resident tensors refreshed incrementally by state
index instead of full re-flattens per eval (the SnapshotMinIndex /
watch-set analog, nomad/worker.go:536-549, SURVEY.md §7 'latency floor').
"""

import numpy as np

from nomad_tpu import mock
from nomad_tpu.device.cache import DeviceStateCache
from nomad_tpu.device.flatten import flatten_cluster
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Evaluation, new_id


def _store_with_nodes(n=8):
    store = StateStore()
    for i in range(n):
        node = mock.node()
        node.datacenter = "dc1"
        store.upsert_node(i + 1, node)
    return store


def _tensors_equal(a, b):
    assert a.num_nodes == b.num_nodes
    assert sorted(a.node_ids) == sorted(b.node_ids)
    for nid in a.node_ids:
        ra, rb = a.node_row[nid], b.node_row[nid]
        np.testing.assert_allclose(a.capacity[ra], b.capacity[rb], rtol=1e-6)
        np.testing.assert_allclose(a.used[ra], b.used[rb], rtol=1e-6)
        assert a.ready[ra] == b.ready[rb]


def test_cache_hit_same_index():
    store = _store_with_nodes()
    cache = DeviceStateCache()
    ct1 = cache.tensors(store.snapshot())
    ct2 = cache.tensors(store.snapshot())
    assert cache.full_flattens == 1
    assert cache.hits >= 1
    _tensors_equal(ct1, ct2)
    # used is a private copy per call — mutating one eval's view must not
    # leak into the next
    ct1.used[0, 0] += 999.0
    ct3 = cache.tensors(store.snapshot())
    assert ct3.used[0, 0] != ct1.used[0, 0]


def test_incremental_alloc_update_matches_full_flatten():
    store = _store_with_nodes()
    cache = DeviceStateCache()
    cache.tensors(store.snapshot())

    node_id = sorted(store.nodes(), key=lambda n: n.id)[0].id
    a = mock.alloc(node_id=node_id)
    store.upsert_allocs(100, [a])

    snap = store.snapshot()
    ct = cache.tensors(snap)
    assert cache.full_flattens == 1
    assert cache.incremental_refreshes == 1
    _tensors_equal(ct, flatten_cluster(snap))


def test_incremental_node_status_and_new_node():
    store = _store_with_nodes()
    cache = DeviceStateCache()
    cache.tensors(store.snapshot())

    # status flip
    nid = sorted(store.nodes(), key=lambda n: n.id)[2].id
    store.update_node_status(50, nid, "down")
    ct = cache.tensors(store.snapshot())
    assert not ct.ready[ct.node_row[nid]]
    assert cache.full_flattens == 1

    # node joins (same class/dc shape — no rebuild unless bucket overflows)
    newn = mock.node()
    newn.datacenter = "dc1"
    store.upsert_node(60, newn)
    snap = store.snapshot()
    ct = cache.tensors(snap)
    assert newn.id in ct.node_row
    _tensors_equal(ct, flatten_cluster(snap))


def test_node_removal_forces_rebuild_and_matches():
    store = _store_with_nodes()
    cache = DeviceStateCache()
    cache.tensors(store.snapshot())
    nid = sorted(store.nodes(), key=lambda n: n.id)[1].id
    store.delete_node(70, nid)
    snap = store.snapshot()
    ct = cache.tensors(snap)
    assert nid not in ct.node_row
    assert cache.full_flattens == 2
    _tensors_equal(ct, flatten_cluster(snap))


def test_journal_trim_falls_back_to_rebuild():
    store = _store_with_nodes()
    cache = DeviceStateCache()
    cache.tensors(store.snapshot())
    # simulate journal loss
    store.journal._floor = store.latest_index + 1
    a = mock.alloc(node_id=sorted(store.nodes(), key=lambda n: n.id)[0].id)
    store.upsert_allocs(200, [a])
    ct = cache.tensors(store.snapshot())
    assert cache.full_flattens == 2
    _tensors_equal(ct, flatten_cluster(store.snapshot()))


def test_eval_storm_flattens_once():
    """The acceptance bar from the round-1 verdict: scheduling a storm of
    sequential evals re-flattens zero times after the first build."""
    h = Harness()
    for i in range(40):
        node = mock.node()
        node.datacenter = "dc1"
        h.store.upsert_node(i + 1, node)

    for i in range(100):
        job = mock.job()
        job.id = f"storm-{i}"
        job.task_groups[0].count = 2
        h.store.upsert_job(h.next_index(), job)
        ev = Evaluation(
            id=new_id(),
            namespace=job.namespace,
            job_id=job.id,
            type=job.type,
            triggered_by="job-register",
            status="pending",
        )
        h.process(ev)

    placed = [a for a in h.store.allocs() if a.job_id.startswith("storm-")]
    assert len(placed) == 200, f"placed {len(placed)}"
    assert h.device_cache.full_flattens == 1, (
        f"expected exactly 1 full flatten across 100 evals, got "
        f"{h.device_cache.full_flattens}"
    )
    assert h.device_cache.incremental_refreshes >= 99


def _counter(name):
    from nomad_tpu.utils.metrics import global_metrics

    return global_metrics.snapshot()["counters"].get(name, 0)


def test_a_drain_and_a_return_to_eligible_patch_the_ready_row_only():
    """``Node.UpdateDrain`` and ``Node.UpdateEligibility`` write a node's
    status fields: one ``ready`` row follows, the layout and the attribute
    columns stay (``full_flattens`` 1, no column built anew)."""
    from nomad_tpu.structs import DrainStrategy

    store = _store_with_nodes()
    for i, node in enumerate(sorted(store.nodes(), key=lambda n: n.id)):
        node.attributes["platform.rack"] = f"r{i % 4}"
    cache = DeviceStateCache()
    ct = cache.tensors(store.snapshot())
    ids, vocab = ct.attr_column("${attr.platform.rack}")
    built = _counter("nomad.device_cache.attr_columns_rebuilt")
    patched = _counter("nomad.device_cache.node_rows_patched")
    nid = sorted(store.nodes(), key=lambda n: n.id)[3].id
    row = ct.node_row[nid]
    assert ct.ready[row]

    for index, write, ready in (
        (50, lambda i: store.update_node_drain(
            i, nid, DrainStrategy(deadline_s=3600)), False),
        (51, lambda i: store.update_node_drain(
            i, nid, None, eligibility="ineligible"), False),
        (52, lambda i: store.update_node_eligibility(
            i, nid, "eligible"), True),
    ):
        write(index)
        snap = store.snapshot()
        ct = cache.tensors(snap)
        assert bool(ct.ready[row]) is ready
        assert int(ct.ready[: ct.num_nodes].sum()) == ct.num_nodes - (not ready)
        _tensors_equal(ct, flatten_cluster(snap))
        # the column is the one built before the first write, row for row
        ids2, vocab2 = ct.attr_column("${attr.platform.rack}")
        np.testing.assert_array_equal(ids2, ids)
        assert vocab2 == vocab
    assert cache.full_flattens == 1
    assert _counter("nomad.device_cache.attr_columns_rebuilt") == built
    assert _counter("nomad.device_cache.node_rows_patched") == patched + 3


def test_a_node_whose_attribute_changed_gets_new_columns():
    """A cached column follows a fingerprint change of one node: its row
    reads the new value (a value the fleet had not seen extends the
    vocabulary, ids handed out before stay), the generation before keeps
    its own column, and a node that joins gets its row too."""
    import copy

    store = _store_with_nodes(6)  # two rows free in the bucket of 8
    for i, node in enumerate(sorted(store.nodes(), key=lambda n: n.id)):
        node.attributes["platform.rack"] = f"r{i % 4}"
    cache = DeviceStateCache()
    old = cache.tensors(store.snapshot())
    ids, vocab = old.attr_column("${attr.platform.rack}")
    ids, vocab = ids.copy(), dict(vocab)
    node = sorted(store.nodes(), key=lambda n: n.id)[5]
    row = old.node_row[node.id]

    # (the copy keeps its computed class: a fingerprint that moves the
    # class rebuilds the layout, columns and all)
    moved = copy.copy(node)
    moved.attributes = {**node.attributes, "platform.rack": "r9"}
    store.upsert_node(50, moved)
    joined = mock.node()
    joined.datacenter = "dc1"
    joined.attributes["platform.rack"] = "r2"
    store.upsert_node(51, joined)
    snap = store.snapshot()
    ct = cache.tensors(snap)
    assert cache.full_flattens == 1
    ids2, vocab2 = ct.attr_column("${attr.platform.rack}")
    assert vocab2 == {**vocab, "r9": len(vocab)}
    assert ids2[row] == vocab2["r9"] != ids[row]
    assert ids2[ct.node_row[joined.id]] == vocab2["r2"]
    keep = np.ones(ids.size, dtype=bool)
    keep[[row, ct.node_row[joined.id]]] = False
    np.testing.assert_array_equal(ids2[keep], ids[keep])
    # what a full flatten of the same snapshot builds, value for value
    fresh_ids, fresh_vocab = flatten_cluster(snap).attr_column(
        "${attr.platform.rack}")
    names = {v: k for k, v in vocab2.items()}
    fresh_names = {v: k for k, v in fresh_vocab.items()}
    for nid, r in ct.node_row.items():
        fresh_row = flatten_cluster(snap).node_row[nid]
        assert names[int(ids2[r])] == fresh_names[int(fresh_ids[fresh_row])]
    # the generation before this one was not written to
    np.testing.assert_array_equal(
        old.attr_column("${attr.platform.rack}")[0], ids)
    # an attribute that goes away reads absent
    bare = copy.copy(moved)
    bare.attributes = {
        k: v for k, v in moved.attributes.items() if k != "platform.rack"}
    store.upsert_node(52, bare)
    ct = cache.tensors(store.snapshot())
    assert ct.attr_column("${attr.platform.rack}")[0][row] == -1
