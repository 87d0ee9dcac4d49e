"""One host→device hand-off a kernel call (PR 36).

On one device ``PlacementKernel`` hands a call's operands over as one packed
``uint32`` buffer that the kernel's outer program ``<kernel>_packed``
unpacks, beside ``used`` through its seam; capacity is the cache
generation's resident buffer. The eager form this replaced — one
``jnp.asarray`` per operand into the kernel itself — left the package and
lives on here as the reference (``EagerKernel``):

- rows and scores (``uint32`` views, overflow slots included) are those of
  the eager form, for the four kernel families at G = 1 and G = 16, with
  the slim and the dense batch forms, with and without jitter;
- the ``place.upload`` span says what it handed over: ``transfers`` and
  ``bytes``;
- capacity goes up once a generation and again only after a node's
  capacity changed, a layout change or ``invalidate()``, never stale;
- an open breaker's reference path unpacks the same buffer on the CPU and
  places what the eager form placed there.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos.plane import FaultPlane, FaultSpec, install, uninstall
from nomad_tpu.device import score
from nomad_tpu.device.cache import DeviceStateCache
from nomad_tpu.device.score import (
    BLOCK_EVEN_SPREAD,
    BLOCK_TARGET_SPREAD,
    PlacementKernel,
)
from nomad_tpu.obs.trace import global_tracer
from nomad_tpu.resilience import breaker
from nomad_tpu.state import StateStore
from nomad_tpu.utils.metrics import global_metrics

from test_value_scan import blocks_of, make_ask, make_cluster

N = 200
RACKS = 5


class EagerKernel(PlacementKernel):
    """The hand-off as it stood before PR 36: every operand an eager
    ``jnp.asarray`` of its own, the kernel called as it is."""

    def _call(self, kernel, cluster, used0, batch, jitter, **statics):
        operands = {k: jnp.asarray(v) for k, v in batch.items()}
        return kernel(
            jnp.asarray(cluster.capacity),
            jnp.asarray(used0),
            **operands,
            algorithm_spread=jnp.asarray(self.algorithm_spread),
            jitter=None if jitter is None else jnp.asarray(jitter),
            **statics,
        )


class Recording(PlacementKernel):
    """Keeps what each call handed over, beside running it."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.calls = []

    def _call(self, kernel, cluster, used0, batch, jitter, **statics):
        self.calls.append((kernel, dict(batch), jitter, used0))
        return super()._call(kernel, cluster, used0, batch, jitter, **statics)


def _rack_blocks(ct, kind):
    vids = np.arange(N) % RACKS
    desired = None
    if kind == BLOCK_TARGET_SPREAD:
        desired = np.full(RACKS, 40.0, dtype=np.float32)
    return blocks_of(
        ct, [(kind, vids, np.zeros(RACKS, np.float32), desired, None, 1.0)]
    )


# family → (count, blocks' kind): what ``PlacementKernel.place`` routes
# to that kernel
FAMILIES = {
    "closed_form": (40, None),
    "scan": (12, BLOCK_TARGET_SPREAD),
    "chunked": (60, BLOCK_TARGET_SPREAD),
    "opv": (60, BLOCK_EVEN_SPREAD),
}
KERNEL_OF = {
    "closed_form": "place_closed_form_kernel",
    "scan": "place_value_scan_kernel",
    "chunked": "place_spread_chunked_kernel",
    "opv": "place_spread_opv_kernel",
}


def _asks(ct, family, lanes, dense):
    count, kind = FAMILIES[family]
    out = []
    for i in range(lanes):
        a = make_ask(
            ct, count, seed=i, affinities=dense,
            blocks=None if kind is None else _rack_blocks(ct, kind),
        )
        if dense:
            rng = np.random.default_rng(100 + i)
            a.job_counts[rng.choice(N, 20, replace=False)] = 1
            a.penalty_nodes[rng.choice(N, 3, replace=False)] = True
            a.slot_caps = np.where(
                np.arange(ct.padded_n) % 2 == 0, 3.0, 1.0
            ).astype(np.float32)
        out.append(a)
    return out


def _bits(results):
    return [
        (
            r.node_rows.tolist(), r.scores.view(np.uint32).tolist(),
            r.overflow_rows.tolist(),
            r.overflow_scores.view(np.uint32).tolist(),
        )
        for r in results
    ]


def _traced_place(kernel, ct, asks, **kw):
    """``kernel.place`` inside a trace: its results and the tags of each
    ``place.upload`` span."""
    global_tracer.begin("t-upload")
    try:
        with global_tracer.activate("t-upload"):
            results = kernel.place(ct, asks, **kw)
    finally:
        trace = global_tracer.finish("t-upload")
    spans = [s for s in trace["spans"] if s["name"] == "place.upload"]
    return results, [s["tags"] for s in spans]


CASES = [
    (family, lanes, dense)
    for family in FAMILIES
    for lanes in (1, 3)  # one lane is G = 1, three pad to G = 16
    for dense in (False, True)
]


@pytest.mark.parametrize(
    "family,lanes,dense", CASES,
    ids=[f"{f}-G{1 if n == 1 else 16}-{'dense' if d else 'slim'}"
         for f, n, d in CASES],
)
def test_placements_and_tags_are_the_eager_forms(family, lanes, dense):
    ct = make_cluster(N, seed=3)
    algorithm = "binpack" if family == "closed_form" else "spread"
    asks = _asks(ct, family, lanes, dense)
    kw = dict(decorrelate=lanes > 1, decorrelate_salt=7)
    want = EagerKernel(algorithm).place(ct, asks, **kw)
    kernel = Recording(algorithm)
    got, tags = _traced_place(kernel, ct, asks, **kw)
    assert _bits(got) == _bits(want)
    assert any(r.node_rows.min() >= 0 for r in got)

    assert [k.__name__ for k, *_ in kernel.calls] == [KERNEL_OF[family]]
    (_kernel, batch, jitter, used0), (tag,) = kernel.calls[0], tags
    assert (jitter is not None) == (lanes > 1)
    # hand-built tensors carry no resident capacity: it goes up in the
    # span, beside ``used`` and the one packed buffer
    assert tag["transfers"] == 3
    operands = list(batch.values()) + [np.asarray(True)]
    if jitter is not None:
        operands.append(jitter)
    words = sum(-(-np.asarray(v).nbytes // 4) for v in operands)
    assert tag["bytes"] == ct.capacity.nbytes + used0.nbytes + 4 * words
    g = 1 if lanes == 1 else 16
    assert batch["asks"].shape == (g, 4)
    assert batch["job_counts"].shape == (g, ct.padded_n if dense else 1)


def test_pack_is_a_pure_function_of_shapes_and_dtypes_and_round_trips():
    rng = np.random.default_rng(0)
    batch = {
        "f": rng.normal(size=(3, 5)).astype(np.float32),
        "inf": np.full((2, 1), np.inf, dtype=np.float32),
        "i": rng.integers(-9, 9, size=(3, 2, 7)).astype(np.int32),
        "mask": rng.integers(0, 256, size=(3, 13)).astype(np.uint8),
        "flags": np.array([True, False, True]),
        "scalar": np.asarray(True),
    }
    layout, words = score._pack_operands(batch)
    assert words.dtype == np.uint32 and words.ndim == 1
    assert layout == score._pack_operands(
        {k: np.zeros_like(v) for k, v in batch.items()}
    )[0]
    hash(layout)  # a static argument of the outer program
    back = score._unpack_operands(jnp.asarray(words), layout)
    assert list(back) == list(batch)
    for k, v in batch.items():
        assert back[k].dtype == v.dtype and back[k].shape == v.shape
        assert np.asarray(back[k]).tobytes() == v.tobytes()
    with pytest.raises(ValueError):
        score._pack_operands({"wide": np.zeros(3, dtype=np.float64)})


# -- capacity stays on the device between node writes ------------------------


def _store(n=12):
    store = StateStore()
    for i in range(n):
        node = mock.node()
        node.id = f"node-{i:02d}"
        node.datacenter = "dc1"
        store.upsert_node(i + 1, node)
    return store


def _counter(name):
    return global_metrics.snapshot()["counters"].get(name, 0)


def _uploads():
    return _counter("nomad.device_cache.capacity_uploads")


def _device_is_host(ct):
    return np.array_equal(np.asarray(ct.device_capacity), ct.capacity)


def _big_ask(ct):
    """More cpu than any node of ``_store`` has: fits nowhere until a
    node grows."""
    return make_ask(ct, 1, cpu=50_000, mem=256)


def test_capacity_goes_up_once_a_generation():
    store, cache = _store(), DeviceStateCache()
    before = _uploads()
    ct = cache.tensors(store.snapshot())
    assert _uploads() == before + 1 and _device_is_host(ct)
    # alloc churn and a second pass read the buffer of the first
    store.upsert_allocs(100, [mock.alloc(node_id="node-05")])
    ct2 = cache.tensors(store.snapshot())
    assert _uploads() == before + 1
    assert ct2.device_capacity is ct.device_capacity
    # with the resident buffer a call is two hand-offs: used, the pack
    _res, (tag,) = _traced_place(
        PlacementKernel("binpack"), ct2, [make_ask(ct2, 2)]
    )
    assert tag["transfers"] == 2
    assert cache.verify_device_view() == []


def test_a_node_write_that_leaves_capacity_uploads_nothing():
    store, cache = _store(), DeviceStateCache()
    cache.tensors(store.snapshot())
    before = _uploads()
    store.update_node_status(50, "node-03", "down")
    ct = cache.tensors(store.snapshot())
    assert not ct.ready[ct.node_row["node-03"]]
    assert _uploads() == before and _device_is_host(ct)


def test_a_changed_capacity_is_uploaded_and_placed_on():
    store, cache = _store(), DeviceStateCache()
    kernel = PlacementKernel("binpack")
    ct = cache.tensors(store.snapshot())
    assert kernel.place(ct, [_big_ask(ct)])[0].node_rows.tolist() == [-1]
    before = _uploads()
    node = store.snapshot().node_by_id("node-07")
    node.node_resources.cpu = 64_000
    store.upsert_node(101, node)
    ct2 = cache.tensors(store.snapshot())
    assert _uploads() == before + 1 and _device_is_host(ct2)
    assert cache.full_flattens == 1
    row = ct2.node_row["node-07"]
    assert kernel.place(ct2, [_big_ask(ct2)])[0].node_rows.tolist() == [row]
    # the pass that still holds the older tensors reads its own buffer
    assert _device_is_host(ct) and not np.array_equal(
        np.asarray(ct.device_capacity), ct2.capacity
    )
    assert cache.verify_device_view() == []


def test_a_new_node_a_layout_change_and_invalidate_upload_again():
    store, cache = _store(), DeviceStateCache()
    cache.tensors(store.snapshot())
    before = _uploads()
    node = mock.node()
    node.id, node.datacenter = "node-new", "dc1"
    store.upsert_node(102, node)  # appended row
    ct = cache.tensors(store.snapshot())
    assert cache.full_flattens == 1
    assert _uploads() == before + 1 and _device_is_host(ct)
    store.delete_node(103, "node-02")  # a node gone: full reflatten
    ct = cache.tensors(store.snapshot())
    assert cache.full_flattens == 2
    assert _uploads() == before + 2 and _device_is_host(ct)
    cache.invalidate()
    ct = cache.tensors(store.snapshot())
    assert _uploads() == before + 3 and _device_is_host(ct)


def test_a_dropped_shard_refresh_never_leaves_a_stale_capacity():
    store, cache = _store(), DeviceStateCache()
    cache.tensors(store.snapshot())
    before = _uploads()
    node = store.snapshot().node_by_id("node-04")
    node.node_resources.cpu = 9_999
    store.upsert_node(101, node)
    install(FaultPlane(
        schedule=[FaultSpec("mesh.shard_refresh_drop", 0, "drop")]
    ))
    try:
        ct = cache.tensors(store.snapshot())
    finally:
        uninstall()
    # one device has no shard to refresh alone: the whole tensor goes up
    assert _uploads() == before + 1 and _device_is_host(ct)
    assert cache.verify_device_view() == []


# -- the breaker's reference path --------------------------------------------


def _fallback_calls():
    return _counter("nomad.resilience.fallback_calls")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_an_open_breaker_places_what_the_eager_form_placed_there(family):
    """The reference path runs the un-jitted bodies op by op on the CPU,
    which rounds a score's last bits otherwise than the compiled program
    does (1 to 4 ulp, before this hand-off too): its yardstick is the
    eager form on the same path, to the bit, and the device path's rows."""
    ct = make_cluster(N, seed=5)
    algorithm = "binpack" if family == "closed_form" else "spread"
    asks = _asks(ct, family, 1, True)
    device = PlacementKernel(algorithm).place(ct, asks)
    breaker.set_forced_open(True)
    try:
        want = EagerKernel(algorithm).place(ct, asks)
        before = _fallback_calls()
        got = PlacementKernel(algorithm).place(ct, asks)
        after = _fallback_calls()
    finally:
        breaker.set_forced_open(False)
    # the outer program's call left for the reference path, and the
    # kernel inside it ran its body there: no dispatch of its own
    assert after == before + 1
    assert _bits(got) == _bits(want)
    assert [r.node_rows.tolist() for r in got] == [
        r.node_rows.tolist() for r in device
    ]
