"""The preemption ranking reads "in sorted order" without sorting.

``device/preempt._victim_sets`` (the body of ``find_preemption_kernel``
and ``choose_preemption_node_kernel``) held five sorts and five
``take_along_axis`` permutation gathers: the holders of a lacking device
by (priority, distance), the superset filter's farthest-first prefix and
its way back to slot order, the victims-first ``order``. Each is now a
compare over the victim axis (``preempt._precedes``: slot ``i`` comes
before slot ``j``) and a sum over the slots that come before. The gather
forms left the package; they live on here as the reference:

- on integral fixtures (whole MHz / MB, as allocations have them) built
  around the cases the forms must get right, all six results of both
  kernels equal the reference's bit for bit (``uint32`` view of ``net``
  and ``score``);
- ``choose_preemption_node_kernel`` returns what the parent commit
  (80e1433, the gather form) returned on this CPU backend for one
  fixture, recorded in ``preempt_dense_order_parent.json``;
- no gather, sort, scatter, cumulative scan or dynamic slice is left in
  either kernel's program at the benchmark cell's shape.

Every sum over slots adds whole numbers below 2**24, so it is exact in
float32 in any order.
"""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.device import preempt
from nomad_tpu.device.preempt import _superset, resource_distance

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "preempt_dense_order_parent.json",
)
RESULTS = ("best", "feasible", "k", "net", "order", "score")


# -- the gather form (the parent's body), kept as the reference --------------


def gather_victim_sets(
    capacity, used, ask, eligible, victim_res, victim_prio, victim_mask,
    victim_dev, dev_need, extras=None,
):
    """``_victim_sets`` as commit 80e1433 had it: argsort, gather, prefix
    scan, gather back. ``extras`` (a dict) receives ``must``, ``taken``
    and ``kept`` for the fixtures' own checks."""
    big = jnp.float32(1e9)
    v = victim_mask.shape[1]
    slots = jnp.arange(v)[None, :]
    free = capacity - used
    res = jnp.where(victim_mask[:, :, None], victim_res, 0.0)
    prio = jnp.where(victim_mask, victim_prio, 0)
    dist_ask = resource_distance(ask[None, None, :], victim_res)

    key = victim_prio.astype(jnp.float32) * 1e4 + jnp.minimum(dist_ask, 9e3)
    holder = victim_mask & (victim_dev > 0)
    by_prio = jnp.argsort(jnp.where(holder, key, big), axis=1)
    held = jnp.take_along_axis(
        jnp.where(holder, victim_dev, 0), by_prio, axis=1
    )
    before = jnp.cumsum(held, axis=1) - held
    must_sorted = (held > 0) & (before < dev_need[:, None])
    rank = jnp.argsort(by_prio, axis=1)
    must = jnp.take_along_axis(must_sorted, rank, axis=1)
    eligible = eligible & (
        jnp.sum(jnp.where(must_sorted, held, 0), axis=1) >= dev_need
    )

    seeded = jnp.sum(jnp.where(must[:, :, None], res, 0.0), axis=1)
    available = free + seeded
    carry = (
        must,
        jnp.where(must, 0, v + 1).astype(jnp.int32),
        available,
        ask[None, :] - seeded,
        _superset(available, ask[None, :]),
    )

    def take_nearest(i, carry):
        taken, step_of, available, needed, met = carry
        on_offer = victim_mask & ~taken
        lowest = jnp.min(
            jnp.where(on_offer, victim_prio, jnp.iinfo(jnp.int32).max),
            axis=1,
        )
        group = on_offer & (victim_prio == lowest[:, None])
        dist = resource_distance(needed[:, None, :], victim_res)
        pick = jnp.argmin(jnp.where(group, dist, big), axis=1)
        go = ~met & jnp.any(on_offer, axis=1)
        one = (slots == pick[:, None]) & go[:, None]
        gone = jnp.sum(jnp.where(one[:, :, None], res, 0.0), axis=1)
        available = available + gone
        return (
            taken | one,
            jnp.where(one, i + 1, step_of),
            available,
            needed - gone,
            _superset(available, ask[None, :]),
        )

    taken, step_of, _available, _needed, met = jax.lax.fori_loop(
        0, v, take_nearest, carry
    )
    order = jnp.lexsort((step_of, jnp.where(taken, -dist_ask, big)), axis=1)
    sorted_taken = jnp.take_along_axis(taken, order, axis=1)
    sorted_res = jnp.take_along_axis(res, order[:, :, None], axis=1)
    freed_to = jnp.cumsum(
        jnp.where(sorted_taken[:, :, None], sorted_res, 0.0), axis=1
    )
    covers = _superset(
        free[:, None, :] + freed_to, ask[None, None, :]
    ) & sorted_taken
    n_kept = jnp.where(
        jnp.any(covers, axis=1), jnp.argmax(covers, axis=1) + 1, 0
    )
    kept_sorted = sorted_taken & (slots < n_kept[:, None])
    kept = jnp.take_along_axis(
        kept_sorted, jnp.argsort(order, axis=1), axis=1
    )
    victims = kept | must
    k = jnp.sum(victims, axis=1).astype(jnp.int32)
    any_fit = met & eligible & (k > 0)
    victims = victims & any_fit[:, None]
    k = jnp.where(any_fit, k, 0)
    net = jnp.sum(jnp.where(victims, prio, 0), axis=1).astype(jnp.float32)
    freed = jnp.sum(jnp.where(victims[:, :, None], res, 0.0), axis=1)
    order = jnp.argsort(~victims, axis=1, stable=True)
    if extras is not None:
        extras.update(must=must, taken=taken, kept=kept)
    return any_fit, k, net, order.astype(jnp.int32), freed


@pytest.fixture
def gather_kernels(monkeypatch):
    """Both kernels' own bodies traced over the gather form."""
    monkeypatch.setattr(preempt, "_victim_sets", gather_victim_sets)
    return (
        jax.jit(preempt.choose_preemption_node_kernel.__wrapped__),
        jax.jit(preempt.find_preemption_kernel.__wrapped__),
    )


def bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


# -- fixtures ------------------------------------------------------------------

ASK = np.array([2000.0, 4096.0, 300.0, 0.0], dtype=np.float32)
# (cpu MHz, memory MB, disk MB, priority, device instances) per victim
CRAFTED = {
    # one priority, equal sizes, equal distances: the tie goes to the slot
    "equal_keys": [(1000, 2048, 300, 20, 1)] * 4,
    # three priority groups of mixed sizes: the greedy's step depends on
    # what the steps before took
    "mixed_groups": [
        (500, 1024, 0, 30, 0), (1000, 1024, 300, 10, 1), (250, 512, 0, 10, 0),
        (2000, 4096, 0, 30, 1), (500, 512, 0, 20, 0), (250, 2048, 300, 20, 1),
    ],
    # a small cheap victim and a huge dear one: farthest first, the huge
    # one covers alone and the superset filter drops the small one
    "superset_drops": [(500, 1024, 0, 10, 0), (6000, 12288, 600, 30, 0)],
    # two holders of one instance each: a need of three cannot be covered
    "holders_short": [(1000, 2048, 300, 20, 1), (1000, 2048, 300, 20, 1)],
    # room without a victim: not an option of the preemption ranking
    "fits_already": [(500, 1024, 0, 20, 0), (500, 1024, 0, 20, 1)],
    # equal keys among the holders of several instances each
    "holders_tie": [
        (500, 1024, 0, 20, 2), (500, 1024, 0, 20, 2), (500, 1024, 0, 20, 2),
        (2000, 4096, 300, 20, 0),
    ],
}
CRAFTED_NEED = {"holders_short": 3, "holders_tie": 3, "equal_keys": 2}


def make_case(v, with_device, seed=0, n=192):
    """Nine operands of ``choose_preemption_node_kernel``: the crafted rows
    (cut to ``v`` victims) first, random rows of two to four priority
    groups after them, every eighth row padding."""
    r = np.random.default_rng(1000 * v + 10 * seed + int(with_device))
    cap = np.zeros((n, 4), np.float32)
    cap[:, 0] = r.choice([4000, 8000], n)
    cap[:, 1] = r.choice([8192, 16384], n)
    cap[:, 2], cap[:, 3] = 100000, 1000
    count = r.integers(0, v + 1, n)
    count[::8] = 0
    mask = np.arange(v)[None, :] < count[:, None]
    res = np.zeros((n, v, 4), np.float32)
    res[:, :, 0] = r.choice([250, 500, 500, 1000, 2000], (n, v))
    res[:, :, 1] = r.choice([256, 512, 1024, 1024, 4096], (n, v))
    res[:, :, 2] = r.choice([0, 300, 300], (n, v))
    prio = r.choice([10, 20, 20, 30, 50], (n, v)).astype(np.int32)
    dev = (r.integers(1, 3, (n, v)) * (r.random((n, v)) < 0.4)).astype(np.int32)
    need = r.integers(0, 4, n).astype(np.int32)
    rows = {}
    for row, (name, victims) in enumerate(CRAFTED.items(), start=1):
        victims = victims[:v]
        rows[name] = row
        mask[row] = np.arange(v) < len(victims)
        for j, (cpu, mem, disk, p, d) in enumerate(victims):
            res[row, j] = (cpu, mem, disk, 0)
            prio[row, j], dev[row, j] = p, d
        need[row] = CRAFTED_NEED.get(name, 0)
    res *= mask[:, :, None]
    prio *= mask
    dev *= mask
    used = np.minimum(
        res.sum(1) + np.float32(100) * r.integers(0, 3, (n, 1)), cap
    ).astype(np.float32)
    # full to the brim but for the row that has room already
    for name, row in rows.items():
        cap[row] = (8000, 16384, 100000, 1000)
        used[row] = cap[row]
    used[rows["fits_already"]] = cap[rows["fits_already"]] - ASK
    eligible = r.random(n) < 0.9
    eligible[list(rows.values())] = True
    eligible[::8] = True  # padding is refused for its victims, not its mask
    if not with_device:
        dev[:], need[:] = 0, 0
    return (cap, used, ASK, eligible, res, prio, mask, dev, need), rows


@pytest.mark.parametrize("with_device", [True, False], ids=["gpu", "plain"])
@pytest.mark.parametrize("v", [1, 4, 16, 32])
def test_kernels_equal_the_gather_form(v, with_device, gather_kernels):
    args, rows = make_case(v, with_device)
    want = gather_kernels[0](*args)
    got = preempt.choose_preemption_node_kernel(*args)
    for name, w, g in zip(RESULTS, want, got):
        np.testing.assert_array_equal(bits(g), bits(w), err_msg=name)
    want4 = gather_kernels[1](*args[:7])
    got4 = preempt.find_preemption_kernel(*args[:7])
    for name, w, g in zip(RESULTS[1:5], want4, got4):
        np.testing.assert_array_equal(bits(g), bits(w), err_msg=name)

    # the fixture holds what it says it holds
    def with_extras(*operands):
        extras = {}
        return gather_victim_sets(*operands, extras=extras), extras

    (feasible, k, _net, order, _freed), extras = jax.jit(with_extras)(*args)
    feasible, k, order = map(np.asarray, (feasible, k, order))
    must, taken, kept = (np.asarray(extras[x]) for x in ("must", "taken", "kept"))
    assert not feasible[::8].any() and not k[::8].any()
    assert not feasible[rows["fits_already"]]
    assert feasible.sum() >= (8 if v > 1 else 1)
    if with_device:
        assert not feasible[rows["holders_short"]]
        assert must.any()
    if v >= 4:
        row = rows["superset_drops"]
        assert taken[row, :2].all() and kept[row].tolist()[:2] == [False, True]
        assert k[row] == 1 and order[row, 0] == 1
        assert (taken & ~kept & ~must).sum() > 1  # random rows drop too
        row = rows["equal_keys"]
        assert feasible[row]
        # the need of two goes to slots 0 and 1; without one, two of the
        # equal victims cover the ask and the lower slots are taken first
        assert order[row, :2].tolist() == [0, 1] and k[row] == 2
        row = rows["mixed_groups"]
        assert feasible[row] and len(set(args[5][row, :v].tolist())) >= 2
        if with_device:
            row = rows["holders_tie"]
            assert must[row].tolist()[:4] == [True, True, False, False]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("v", [2, 16, 32])
def test_precedes_is_the_stable_sort(v, seed):
    """Counting the slots that come before gives each slot its place in a
    stable ``lexsort``: keys with many ties, -0.0 beside 0.0."""
    r = np.random.default_rng(50 * v + seed)
    n = 64
    primary = r.choice([-2.5, -0.0, 0.0, 1.0, 1e9], (n, v)).astype(np.float32)
    secondary = r.integers(0, 3, (n, v)).astype(np.int32)
    place = np.asarray(
        jnp.sum(preempt._precedes(primary, secondary), axis=1)
    )
    order = np.asarray(jnp.lexsort((secondary, primary), axis=1))
    np.testing.assert_array_equal(place, np.argsort(order, axis=1))
    np.testing.assert_array_equal(
        np.take_along_axis(place, order, axis=1),
        np.broadcast_to(np.arange(v), (n, v)),
    )


def test_fractional_resources_keep_the_sets():
    """Sums of fractions may round differently in another order: the sets
    and the order stay, ``net`` and ``score`` agree to float32 rounding."""
    (cap, used, ask, eligible, res, prio, mask, dev, need), _ = make_case(
        16, True, seed=5
    )
    r = np.random.default_rng(7)
    res = (res * r.uniform(0.5, 1.5, res.shape)).astype(np.float32)
    used = np.minimum(res.sum(1), cap).astype(np.float32)
    args = (cap, used, ask, eligible, res, prio, mask, dev, need)
    want = jax.jit(gather_victim_sets)(*args)
    got = jax.jit(preempt._victim_sets)(*args)
    for w, g in zip(want[:2] + want[3:4], got[:2] + got[3:4]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(got[2], want[2], rtol=1e-6)
    np.testing.assert_allclose(got[4], want[4], rtol=1e-6)
    assert np.asarray(want[0]).sum() > 20


# -- the parent's results, recorded -------------------------------------------


def recorded_case():
    return make_case(16, True, seed=3, n=256)[0]


def record(results):
    return {
        name: bits(x).tolist() for name, x in zip(RESULTS, results)
    }


def test_kernel_returns_what_the_parent_returned():
    with open(RECORDED) as f:
        recorded = json.load(f)
    assert recorded["commit"].startswith("80e1433")
    got = record(preempt.choose_preemption_node_kernel(*recorded_case()))
    for name in RESULTS:
        assert got[name] == recorded["results"][name], name
    assert sum(recorded["results"]["feasible"]) > 50


# -- the programs ---------------------------------------------------------------


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


CELL_SHAPE = (16384, 16, 4)  # gpu-preempt-10k: padded nodes, bucket, dims
MOVES_DATA = (
    "gather", "sort", "scatter", "scatter-add", "dynamic_slice",
    "dynamic_update_slice", "cumsum", "cumlogsumexp", "cummax", "cummin",
    "cumprod", "top_k",
)


@pytest.mark.parametrize(
    "kernel", ["choose_preemption_node_kernel", "find_preemption_kernel"]
)
def test_no_gather_and_no_sort_in_the_program(kernel):
    n, v, d = CELL_SHAPE
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    shapes = (
        S((n, d), f32), S((n, d), f32), S((d,), f32), S((n,), bool),
        S((n, v, d), f32), S((n, v), i32), S((n, v), bool),
        S((n, v), i32), S((n,), i32),
    )
    jitted = getattr(preempt, kernel).jitted
    shapes = shapes[: len(inspect.signature(jitted).parameters)]
    used = set(_primitives(jax.make_jaxpr(jitted)(*shapes).jaxpr))
    assert "while" in used or "scan" in used, "the greedy's loop"
    assert not used & set(MOVES_DATA), sorted(used & set(MOVES_DATA))
    lowered = jitted.lower(*shapes).as_text()
    for op in ("gather", "sort", "scatter", "dynamic_slice"):
        assert f"stablehlo.{op}" not in lowered, op


def test_signatures_and_results_are_the_parents():
    """``benchmark/metrics/preempt_kernel_*.json`` find the program by its
    name, ``rank_preemption_nodes`` sends nine operands."""
    choose = preempt.choose_preemption_node_kernel
    find = preempt.find_preemption_kernel
    assert choose.__name__ == "choose_preemption_node_kernel"
    assert find.__name__ == "find_preemption_kernel"
    assert list(inspect.signature(choose).parameters) == [
        "capacity", "used", "ask", "eligible", "victim_res", "victim_prio",
        "victim_mask", "victim_dev", "dev_need",
    ]
    assert list(inspect.signature(find).parameters) == [
        "capacity", "used", "ask", "eligible", "victim_res", "victim_prio",
        "victim_mask",
    ]
    args = recorded_case()
    assert len(choose(*args)) == 6 and len(find(*args[:7])) == 4


if __name__ == "__main__":
    # python tests/test_preempt_dense_order.py <commit>: record what the
    # tree on sys.path returns (run from a checkout of the parent)
    import sys

    out = {
        "commit": sys.argv[1],
        "fixture": "make_case(16, True, seed=3, n=256)",
        "results": record(
            preempt.choose_preemption_node_kernel(*recorded_case())
        ),
    }
    with open(RECORDED, "w") as f:
        json.dump(out, f, separators=(",", ":"))
        f.write("\n")
