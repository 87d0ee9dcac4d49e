"""The batched placement kernel — the TPU replacement for the reference's
iterator-chain inner loop.

What the reference does per placement (scheduler/stack.go:343-438 chain,
scheduler/rank.go:193-527 BinPackIterator.Next): walk up to ``limit`` nodes
through ~10 iterator stages, computing fit and score sequentially in Go.
O(allocs × limit × stages), single-threaded per eval.

What this module does instead: ONE fully-parallel scoring pass per group
batch. For a group placing ``count`` identical asks, every candidate
"place the (j+1)-th instance of this group on node n" has a closed-form
score — usage is used0 + (j+1)·ask, collisions are jc0 + j — so the whole
candidate space is a dense [N, J] plane computed in one shot
(``_score_planes``). Two selection paths consume the planes:

- **Closed-form top-k** (groups with no cross-node coupling): per-node
  score columns are made monotone by a running-min clamp, which turns
  greedy placement into a single ``lax.top_k`` over the flattened plane.
  One parallel pass replaces ``count`` sequential argmax steps.

- **Exact scan** (groups whose spread blocks / distinct_property caps
  couple nodes through global per-value counts): a ``lax.scan`` over
  placement steps that does only O(N·J) *select* work per step — the
  heads of each node's precomputed column plus a [B, V] per-value boost
  table — instead of rescoring every node against every resource dim.
  Exact stepwise-greedy semantics at a fraction of the serial cost. The
  scan and the one-per-value chunked kernel read heads and tables by
  dense selects over node-minor arrays, not by gathers: a TPU walks a
  gather element by element (123–171 us for each [16384] vector on a
  v5e, against 3 us for the select over the whole [24, 16384] plane).

Batch dimension = concurrent evals/groups, replacing Nomad's worker-per-
core optimistic concurrency (nomad/worker.go:85): every group in a batch
scores against the same snapshot, and conflicts are resolved host-side by
``repair_batch_conflicts`` (using each lane's overflow candidates) before
the plan applier's authoritative re-check.

Scoring component semantics (each cites its reference):
- binpack/spread fit: nomad/structs/funcs.go:236-274, normalized /18
  (rank.go:513-516).
- job anti-affinity: −(collisions+1)/desired_count for nodes already
  holding collisions > 0 allocs of the job (rank.go:536-604).
- reschedule penalty: −1 on the node a failed alloc is being replaced
  from (rank.go:606-648).
- node affinity: weight-normalized Σ w·match / Σ|w| (rank.go:650-737),
  precomputed per node host-side (string matching ≪ scoring cost).
- spread (scheduler/spread.go:110-228): one component summing per-block
  boosts. Target mode: (desired − used−1)/desired × weight/Σweights, −1
  for untargeted values; even mode: the min/max-delta boost
  (spread.go:178-228). The component joins the normalization mean only
  when the total boost is nonzero (spread.go:168-171).
- distinct_property (feasible.go:604-707): not a score — a dynamic
  per-value cap carried through the scan's count state.
- normalization: mean over *contributing* components
  (rank.go:740-767 ScoreNormalizationIterator).
"""

from __future__ import annotations

import functools
import inspect
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import global_tracer as _tracer
from ..structs.resources import BINPACK_MAX_SCORE
from ..utils.backend import (
    get_mesh,
    host_put,
    shard_put,
    traced_jit,
    transfer_totals,
)
from ..utils.metrics import global_metrics as _metrics

# Retrace budgets (nomad_tpu.analysis.retrace): the per-kernel trace
# count a representative bench batch may reach. Every dynamic dimension
# is bucketed (nodes/victims/steps to powers of two, k to the overflow
# grid), so distinct static-arg combos — not calls — bound compiles; a
# kernel that blows its budget has lost a shape bucket or a static arg.
RETRACE_BUDGET = 16

_LN10 = 2.302585092994046

# value-block kinds (ValueBlocks.kinds; see flatten.py)
BLOCK_TARGET_SPREAD = 0
BLOCK_EVEN_SPREAD = 1
BLOCK_DISTINCT_CAP = 2
BLOCK_INACTIVE = -1
# ``desired`` of an even-spread block at a value the job's combined-use map
# holds at a count of 0 (every allocation there stopped by the plan: the
# rack of a lost node); -1 at every other value
EVEN_HELD_AT_ZERO = 0.0

# extra greedy candidates emitted beyond ``count`` per lane, consumed by
# repair_batch_conflicts when optimistic batch lanes collide on a node
OVERFLOW_CANDIDATES = 16

# exact stepwise scan only for small groups; larger spread groups place in
# chunks (boost tables frozen for CHUNK placements — spread counts move
# slowly, and the host repair walk re-verifies every placement anyway)
EXACT_SCAN_MAX_COUNT = 32
CHUNK = 16


def _pow10(x):
    # KNOWN 1-ulp portability leak: XLA's exp expansion is not
    # bit-stable across shardings (fmuladd/vector-width decisions shift
    # with the per-shard loop bounds), so scores built under an active
    # mesh can differ from degenerate ones in the last bit. Solver
    # kernels stay byte-portable on FIXED inputs (tests pin that); the
    # scoring stack's cross-mesh stability is input-dependent.
    return jnp.exp(_LN10 * x)


def _topk_nodes(flat, k: int, n_shards: int = 1):
    """Top-k over the flattened node-major [N*J] plane, hierarchically
    when the node axis is sharded: per-shard local top-k, then one
    cross-shard merge over the [S·k'] candidates. BIT-IDENTICAL to the
    global ``lax.top_k`` by construction — ``lax.top_k`` orders by
    (value desc, index asc), each shard forwards a prefix of its own such
    order (min(k, seg) entries always covers the global winners, ties
    included), and candidates are concatenated shard-major so the merge's
    lowest-candidate-position tie-break IS the lowest-global-index
    tie-break. ``n_shards`` is static; 1 (or a non-dividing length)
    Python-gates to the plain global top_k, leaving the single-device
    jaxpr untouched.

    That construction rests on ``lax.top_k`` putting the lower index
    first among equal values. XLA:CPU does; XLA:TPU does so only for a
    1-D operand — its batched top_k (this reshape, and the ``vmap`` over
    lanes around either path) orders equal values differently, so on a
    TPU mesh-on and mesh-off agree on WHICH nodes a lane gets and on
    every score but not on the order of equal-score picks (chip_smoke.py
    on four chips; ROADMAP A7)."""
    if n_shards <= 1 or flat.shape[0] % n_shards != 0:
        return jax.lax.top_k(flat, k)
    seg = flat.shape[0] // n_shards
    k_local = min(k, seg)
    lv, li = jax.lax.top_k(flat.reshape(n_shards, seg), k_local)
    gi = li + (jnp.arange(n_shards, dtype=li.dtype) * seg)[:, None]
    mv, mpos = jax.lax.top_k(lv.reshape(-1), k)
    return mv, gi.reshape(-1)[mpos]


def _unpack_mask(packed, n: int):
    """Device-side unpack of a host np.packbits mask: u8[..., n/8] →
    bool[..., n]. Per-lane masks are the largest per-pass upload for big
    clusters (a dense [128, 16k] bool batch is 2 MB), so bools ride
    bit-packed 8×."""
    bits = (
        packed[..., :, None]
        >> jnp.arange(7, -1, -1, dtype=packed.dtype)[None, :]
    ) & 1
    return bits.reshape(*packed.shape[:-1], -1)[..., :n].astype(bool)


def _unpack_lane_inputs(capacity, eligible, job_counts, penalty_nodes):
    """Normalize slim per-lane encodings at kernel entry (static on
    dtype/shape at trace time): packed masks unpack to [G, N]; degenerate
    [G, 1] arrays stay and broadcast through the score math."""
    n = capacity.shape[0]
    if eligible.dtype == jnp.uint8:
        eligible = _unpack_mask(eligible, n)
    if penalty_nodes.dtype == jnp.uint8:
        penalty_nodes = _unpack_mask(penalty_nodes, n)
    return eligible, job_counts.astype(jnp.int32), penalty_nodes


def component_scores(
    capacity,  # f32[N, D]
    used,  # f32[N, D] current proposed usage
    ask,  # f32[D]
    eligible,  # bool[N]
    job_counts,  # i32[N]
    desired_total,  # f32[] anti-affinity denominator
    penalty_nodes,  # bool[N]
    affinity_scores,  # f32[N]
    has_affinities,  # bool[]
    spread_boost,  # f32[N] (precomputed for this step)
    has_spreads,  # bool[]
    distinct_hosts,  # bool[]
    algorithm_spread,  # bool[] scheduler algorithm: binpack vs spread fit
    throughputs=None,  # f32[N] normalized [0, 1] class-throughput share
):
    """Per-node normalized score for placing one instance of ``ask``.
    Returns (final_score f32[N] with -inf infeasible, fits bool[N]).
    Used by the dense [G, N] score-matrix path (annotation, system
    scheduler); the placement paths use the [N, J] planes instead.

    ``throughputs`` is the heterogeneity axis: the job's per-device-class
    coefficient gathered per node and normalized by the job's best class
    (scheduler/hetero.py). When given it joins the component average like
    affinity does, and zero-throughput nodes (the job cannot progress on
    that class) become infeasible. The gate is Python-level ``None`` —
    class-less callers trace the exact same jaxpr as before the axis
    existed, which is what keeps binpack/spread bit-identical."""
    proposed = used + ask  # [N, D]
    fits = jnp.all(proposed <= capacity, axis=-1) & eligible
    fits &= jnp.where(distinct_hosts, job_counts == 0, True)
    if throughputs is not None:
        fits &= throughputs > 0.0

    free_frac = jnp.where(
        capacity > 0, (capacity - proposed) / jnp.maximum(capacity, 1e-9), 1.0
    )
    pow_sum = _pow10(free_frac[:, 0]) + _pow10(free_frac[:, 1])  # cpu, mem
    binpack = jnp.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
    spread_fit = jnp.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
    fit_score = jnp.where(algorithm_spread, spread_fit, binpack) / BINPACK_MAX_SCORE

    collisions = job_counts.astype(jnp.float32)
    anti = jnp.where(
        job_counts > 0, -(collisions + 1.0) / jnp.maximum(desired_total, 1.0), 0.0
    )
    resched = jnp.where(penalty_nodes, -1.0, 0.0)
    aff = jnp.where(has_affinities, affinity_scores, 0.0)
    spread_on = has_spreads & (spread_boost != 0.0)
    spread_c = jnp.where(spread_on, spread_boost, 0.0)

    n_comp = (
        1.0
        + (job_counts > 0)
        + penalty_nodes
        + jnp.where(has_affinities, 1.0, 0.0)
        + jnp.where(spread_on, 1.0, 0.0)
    )
    total = fit_score + anti + resched + aff + spread_c
    if throughputs is not None:
        total = total + throughputs
        n_comp = n_comp + 1.0
    final = total / n_comp
    return jnp.where(fits, final, -jnp.inf), fits


def _score_planes(
    capacity,  # f32[N, D]
    used0,  # f32[N, D]
    ask,  # f32[D]
    elig,  # bool[N]
    jc0,  # i32[N]
    dt,  # f32[] anti-affinity denominator
    pen,  # bool[N]
    aff,  # f32[N]
    has_aff,  # bool[]
    dh,  # bool[] distinct_hosts
    caps,  # f32[N] per-node device-slot caps
    algorithm_spread,  # bool[]
    max_j: int,
    jitter=None,  # f32[N] tie-break noise (decorrelated batch passes)
):
    """The shared [N, J] candidate planes: numerator (sum of non-spread
    components), denominator (contributing-component count, spread
    excluded — the scan adds it dynamically), and feasibility. Work in
    [N, J] planes only — a [N, J, D] temp is N·J·D·4 bytes and OOMs at
    40k-node scale; the D axis is tiny and static, so unroll it."""
    js = jnp.arange(max_j, dtype=jnp.float32)  # [J]
    mult = js[None, :] + 1.0  # [1, J]
    # Closed-form per-node feasible-column bound instead of D separate
    # [N, J] comparison planes (the r3 regression suspect): used0 +
    # (j+1)·ask ≤ cap for all dims ⇔ j < min_d floor((cap−used0)/ask).
    # The 1e-6 nudge absorbs float division round-down on exact fits.
    free0 = capacity - used0  # [N, D]
    per_dim = jnp.where(
        ask[None, :] > 0,
        jnp.floor(free0 / jnp.maximum(ask[None, :], 1e-9) + 1e-6),
        jnp.inf,
    )
    jmax = jnp.min(per_dim, axis=1)  # [N] feasible instances of this ask
    jmax = jnp.where(elig, jmax, 0.0)
    jmax = jnp.minimum(jmax, caps)  # device-slot caps
    # distinct_hosts ⇒ only j=0 and only where no existing collision
    jmax = jnp.where(
        dh,
        jnp.where(jc0 == 0, jnp.minimum(jmax, 1.0), 0.0),
        jmax,
    )
    fits = js[None, :] < jmax[:, None]  # [N, J]

    pow_sum = jnp.zeros_like(fits, dtype=jnp.float32)
    for d in (0, 1):  # cpu, mem drive the fit score
        cap_d = capacity[:, d : d + 1]
        prop_d = used0[:, d : d + 1] + mult * ask[d]
        free_d = jnp.where(
            cap_d > 0, (cap_d - prop_d) / jnp.maximum(cap_d, 1e-9), 1.0
        )
        pow_sum = pow_sum + _pow10(free_d)
    binpack = jnp.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
    spread_fit = jnp.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
    fit_score = (
        jnp.where(algorithm_spread, spread_fit, binpack) / BINPACK_MAX_SCORE
    )

    coll = jc0[:, None].astype(jnp.float32) + js[None, :]  # after j placed
    has_coll = coll > 0
    anti = jnp.where(has_coll, -(coll + 1.0) / jnp.maximum(dt, 1.0), 0.0)
    resched = jnp.where(pen[:, None], -1.0, 0.0)
    aff_c = jnp.where(has_aff, aff[:, None], 0.0)
    num = fit_score + anti + resched + aff_c  # [N, J]
    if jitter is not None:
        # per-call deterministic tie-break noise (~1e-5 ≪ any meaningful
        # score difference): the vector analog of the reference's
        # per-worker node shuffle (stack.go:74-90) — without it every
        # concurrent batch fills an empty homogeneous cluster in the
        # same node order and the applier bounces the later plans
        num = num + jitter[:, None]
    den = 1.0 + has_coll + pen[:, None] + jnp.where(has_aff, 1.0, 0.0)
    # slim [1]-shaped lane inputs leave den rank-deficient; the scan
    # paths read it per node (transposed to [J, N] and by select), so
    # materialize the broadcast
    num = jnp.broadcast_to(num, fits.shape)
    den = jnp.broadcast_to(den, fits.shape)
    return num, den, fits


# -- closed-form greedy (the TPU-shaped fast path) ---------------------------
#
# For one group placing ``count`` IDENTICAL asks with no per-value
# coupling, node scores are independent and the per-node score sequence
# s[n, j] is monotone non-increasing in j after a running-min clamp
# (binpack worsens with usage, anti-affinity grows; the single
# non-monotone corner — a rising best-fit head — is flattened by the
# clamp, under which top-k fills nodes in descending initial-score order,
# exactly what stepwise greedy does with rising heads). Greedy placement
# then equals a plain top-k over the flattened [N, J] matrix.
#
# This is the "batched dense score matrix" BASELINE.json names as the
# north-star replacement for the reference's per-placement iterator walk
# (scheduler/rank.go:193-527): O(N·J) parallel work, O(log) depth.


@functools.partial(traced_jit, retrace_budget=RETRACE_BUDGET,
                   static_argnames=("max_j", "k", "n_shards"))
def place_closed_form_kernel(
    capacity,  # f32[N, D] shared
    used0,  # f32[N, D] shared snapshot usage
    asks,  # f32[G, D]
    eligible,  # bool[G, N]
    job_counts,  # i32[G, N]
    desired_totals,  # f32[G]
    penalty_nodes,  # bool[G, N]
    affinity_scores,  # f32[G, N]
    has_affinities,  # bool[G]
    distinct_hosts,  # bool[G]
    slot_caps,  # f32[G, N]
    algorithm_spread,  # bool[]
    counts,  # i32[G]
    max_j: int,  # static: max instances of one group per node
    k: int,  # static: top-k width (≥ max count in batch + overflow)
    jitter=None,  # f32[N] tie-break noise, shared across lanes
    n_shards: int = 1,  # static: node-axis mesh shards (hierarchical top-k)
):
    """Returns (choices i32[G, k], scores f32[G, k]) in greedy order.
    Entries past a lane's feasible candidates are −1/−inf; entries in
    [count, k) are valid *overflow* candidates for conflict repair."""

    eligible, job_counts, penalty_nodes = _unpack_lane_inputs(
        capacity, eligible, job_counts, penalty_nodes
    )

    # the named scopes change no equation: they put a stage's name into
    # the op metadata, so a profile says which stage ``fusion.130`` is
    def one_group(ask, elig, jc0, dt, pen, aff, has_aff, dh, caps, count):
        with jax.named_scope("fit_score"):
            num, den, fits = _score_planes(
                capacity, used0, ask, elig, jc0, dt, pen, aff, has_aff, dh,
                caps, algorithm_spread, max_j, jitter=jitter,
            )
            s_raw = jnp.where(fits, num / den, -jnp.inf)
            # Selection runs on the running-min clamp: it restores the
            # prefix rule "(n,j) requires (n,j-1)" that plain top-k needs.
            s_sel = jax.lax.associative_scan(jnp.minimum, s_raw, axis=1)

        with jax.named_scope("top_k"):
            flat_sel = s_sel.reshape(-1)  # [N*J]
            flat_raw = s_raw.reshape(-1)
            k_eff = min(k, flat_sel.shape[0])  # tiny clusters: < k slots
            # node-major flattening keeps each shard's rows contiguous in
            # flat index space, so the hierarchical reduction applies as-is
            top_sel, top_idx = _topk_nodes(flat_sel, k_eff, n_shards)
            if k_eff < k:
                pad = k - k_eff
                top_sel = jnp.concatenate(
                    [top_sel, jnp.full(pad, -jnp.inf, top_sel.dtype)]
                )
                top_idx = jnp.concatenate(
                    [top_idx, jnp.zeros(pad, top_idx.dtype)]
                )
        with jax.named_scope("pack_rows_scores"):
            # report the TRUE (unclamped) score of each chosen (n, j) — the
            # AllocMetric the oracle would have recorded for that placement
            top_raw = flat_raw[top_idx]
            node_rows = (top_idx // max_j).astype(jnp.int32)
            ok = top_sel > -jnp.inf  # caller slices [:count] vs overflow
            return (
                jnp.where(ok, node_rows, -1),
                jnp.where(ok, top_raw, -jnp.inf),
            )

    choices, scores = jax.vmap(one_group)(
        asks, eligible, job_counts, desired_totals, penalty_nodes,
        affinity_scores, has_affinities, distinct_hosts, slot_caps, counts,
    )
    # one fused [G, 2k] i32 result: one device→host fetch per pass
    # instead of two, scores riding bitcast alongside rows
    with jax.named_scope("pack_rows_scores"):
        return jnp.concatenate(
            [choices, jax.lax.bitcast_convert_type(scores, jnp.int32)],
            axis=1,
        )


# -- exact scan (spread / distinct_property groups) --------------------------


def _block_tables(c, desired, caps, weights, kinds):
    """Per-(block, value) boost + allowance tables from the current count
    state ``c`` [B, V].

    Target mode (spread.go:110-174): boost[v] = (desired − (c+1))/desired
    × weight, where weight is already weight/Σweights; desired < 0 marks a
    value with no explicit or implicit target → flat −1 (unweighted,
    spread.go:145-152).

    Even mode (spread.go:178-228 evenSpreadScoreBoost): boosts derive
    from the min/max over the values of the job's combined-use map: those
    with a positive count, and those the map holds at 0
    (``EVEN_HELD_AT_ZERO`` in ``desired``: every allocation there stopped
    by the plan). A value the job never used is not in the map. A min of 0
    takes the source's own branches: -1 off the min, +1 at it. (Go's loop
    lets a later value overwrite a zero min, so the source's result there
    follows the map's iteration order; the branches written for a zero min
    are the reading taken.) With no positive count the boost is 0, as for
    an empty map: a plan that stops every allocation of the job starts its
    spread afresh.

    Distinct caps (feasible.go:604): allow[v] = c[v] < cap[v].
    """
    # target
    t_boost = jnp.where(
        desired > 0,
        (desired - (c + 1.0)) / jnp.maximum(desired, 1e-9) * weights[:, None],
        -1.0,
    )
    # even
    held = (c > 0) | (desired == EVEN_HELD_AT_ZERO)
    any_pos = jnp.any(c > 0, axis=1, keepdims=True)  # [B, 1]
    minc = jnp.min(jnp.where(held, c, jnp.inf), axis=1, keepdims=True)
    maxc = jnp.max(jnp.where(held, c, -jnp.inf), axis=1, keepdims=True)
    safe_min = jnp.maximum(minc, 1e-9)
    e_boost = jnp.where(
        c == minc,
        jnp.where(
            minc == maxc, -1.0,
            jnp.where(minc > 0, (maxc - minc) / safe_min, 1.0),
        ),
        jnp.where(minc > 0, (minc - c) / safe_min, -1.0),
    )
    e_boost = jnp.where(any_pos, e_boost, 0.0)

    boost = jnp.where(
        (kinds == BLOCK_TARGET_SPREAD)[:, None],
        t_boost,
        jnp.where((kinds == BLOCK_EVEN_SPREAD)[:, None], e_boost, 0.0),
    )
    allow = jnp.where((kinds == BLOCK_DISTINCT_CAP)[:, None], c < caps, True)
    return boost, allow


@functools.partial(traced_jit, retrace_budget=RETRACE_BUDGET,
                   static_argnames=("max_j", "max_steps"))
def place_value_scan_kernel(
    capacity,  # f32[N, D] shared
    used0,  # f32[N, D] shared snapshot usage
    asks,  # f32[G, D]
    eligible,  # bool[G, N]
    job_counts,  # i32[G, N]
    desired_totals,  # f32[G]
    penalty_nodes,  # bool[G, N]
    affinity_scores,  # f32[G, N]
    has_affinities,  # bool[G]
    distinct_hosts,  # bool[G]
    slot_caps,  # f32[G, N]
    block_value_ids,  # i32[G, B, N] (−1 = node has no value)
    block_counts0,  # f32[G, B, V]
    block_desired,  # f32[G, B, V]
    block_caps,  # f32[G, B, V]
    block_weights,  # f32[G, B]
    block_kinds,  # i32[G, B]
    algorithm_spread,  # bool[]
    counts,  # i32[G] placements to emit (incl. overflow slots)
    max_j: int,
    max_steps: int,
    jitter=None,  # f32[N] tie-break noise
):
    """Greedy sequential placement with per-value count coupling.

    All heavy scoring is hoisted into the parallel [N, J] plane
    precompute; each scan step reads every node's column head, adds the
    per-value boost/allowance tables, and argmaxes — the device-resident
    analog of re-running SpreadIterator + DistinctPropertyIterator per
    placement (scheduler/spread.go:110, feasible.go:645), at one pass
    over the [J, N] planes per step instead of O(N·D·stages) rescoring.

    Inside the loop the node axis is the minor axis of everything a step
    reads, and nothing is indexed dynamically (as in
    ``place_spread_opv_kernel``): the column heads are ``_column_heads``'
    one-hot select over the [J, N] planes, the boost and allowance of a
    node's values ``_value_reads``' masked sum / any over the [B, V, N]
    membership of ``block_value_ids``, the picked node's values a masked
    sum over a one-hot of the row, and its score the maximum (argmax
    returns the first maximum; its value is the maximum, −inf included).
    Picks and scores are those of the five gathers and two dynamic slices
    these replaced, to the bit (tests/test_scan_dense_reads.py keeps that
    form as the reference), at 17 us a step on a v5e where they took 772:
    a gather writes a [16384] vector element by element (PERF.md section
    6, PR 30 and PR 34). A sum of one selected value and zeros is exact but
    for −0.0, which would read +0.0. That cannot reach a score: the boost
    is used only where it is not zero, and a head numerator or
    denominator is only ever added to +0.0 or to such a boost, which
    gives the same sum from either zero (no plane holds −0.0 anyway:
    ``_score_planes`` sums each entry starting from a fit score ≥ +0.0).
    """

    eligible, job_counts, penalty_nodes = _unpack_lane_inputs(
        capacity, eligible, job_counts, penalty_nodes
    )

    def one_group(
        ask, elig, jc0, dt, pen, aff, has_aff, dh, caps,
        vids, c0, desired, vcaps, weights, kinds, count,
    ):
        with jax.named_scope("fit_score"):
            num, den, fits = _score_planes(
                capacity, used0, ask, elig, jc0, dt, pen, aff, has_aff, dh,
                caps, algorithm_spread, max_j, jitter=jitter,
            )
        n = num.shape[0]
        is_spread = (kinds == BLOCK_TARGET_SPREAD) | (kinds == BLOCK_EVEN_SPREAD)
        has_spread_any = jnp.any(is_spread)
        # what the loop selects from, node axis minor, built once: the
        # planes as [J, N], the [B, V, N] membership of ``vids`` and the
        # row ids that the pick's one-hot compares against
        num_jn, den_jn, fits_jn = num.T, den.T, fits.T
        member = vids[:, None, :] == jnp.arange(c0.shape[1])[None, :, None]
        node_ids = jnp.arange(n)

        def step(state, i):
            jn, c = state  # jn i32[N] next column per node; c f32[B, V]
            head_num, head_den, head_fit = _column_heads(
                num_jn, den_jn, fits_jn, jn
            )

            tbl, allow = _block_tables(c, desired, vcaps, weights, kinds)
            per_block, allow_pb = _value_reads(member, tbl, allow)  # [B, N]
            contrib = jnp.where(vids >= 0, per_block, -1.0)
            boost = jnp.sum(
                jnp.where(is_spread[:, None], contrib, 0.0), axis=0
            )  # [N]
            allowed = jnp.all(
                jnp.where(
                    (kinds == BLOCK_DISTINCT_CAP)[:, None] & (vids >= 0),
                    allow_pb,
                    True,
                ),
                axis=0,
            )  # [N]

            spread_on = has_spread_any & (boost != 0.0)
            den_t = head_den + jnp.where(spread_on, 1.0, 0.0)
            score = (head_num + jnp.where(spread_on, boost, 0.0)) / den_t
            score = jnp.where(head_fit & allowed, score, -jnp.inf)

            best = jnp.argmax(score)
            best_score = jnp.max(score)
            ok = (best_score > -jnp.inf) & (i < count)
            best_hot = node_ids == best  # [N]
            jn = jn + (best_hot & ok).astype(jn.dtype)
            # [B] value per block at the chosen node (−1 = none: the
            # one-hot sum returns it unchanged)
            bumped = jnp.sum(jnp.where(best_hot[None, :], vids, 0), axis=1)
            c = c + jnp.where(
                (ok & (bumped >= 0))[:, None],
                jax.nn.one_hot(
                    jnp.maximum(bumped, 0), c.shape[1], dtype=c.dtype
                ),
                0.0,
            )
            return (jn, c), (
                jnp.where(ok, best, -1).astype(jnp.int32),
                jnp.where(ok, best_score, -jnp.inf).astype(jnp.float32),
            )

        state0 = (jnp.zeros(n, dtype=jnp.int32), c0)
        with jax.named_scope("spread_loop"):
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


@functools.partial(traced_jit, retrace_budget=RETRACE_BUDGET,
                   static_argnames=("max_j", "chunk", "n_chunks", "n_shards"))
def place_spread_chunked_kernel(
    capacity,  # f32[N, D] shared
    used0,  # f32[N, D] shared snapshot usage
    asks,  # f32[G, D]
    eligible,  # bool[G, N]
    job_counts,  # i32[G, N]
    desired_totals,  # f32[G]
    penalty_nodes,  # bool[G, N]
    affinity_scores,  # f32[G, N]
    has_affinities,  # bool[G]
    distinct_hosts,  # bool[G]
    slot_caps,  # f32[G, N]
    block_value_ids,  # i32[G, B, N] (−1 = node has no value)
    block_counts0,  # f32[G, B, V]
    block_desired,  # f32[G, B, V]
    block_caps,  # f32[G, B, V]
    block_weights,  # f32[G, B]
    block_kinds,  # i32[G, B]
    algorithm_spread,  # bool[]
    counts,  # i32[G] placements to emit (incl. overflow slots)
    max_j: int,
    chunk: int,
    n_chunks: int,
    jitter=None,  # f32[N] tie-break noise
    n_shards: int = 1,  # static: node-axis mesh shards (hierarchical top-k)
):
    """Chunked greedy placement for large spread-coupled groups.

    The exact scan (place_value_scan_kernel) pays one sequential
    ``lax.scan`` step per placement — 250-instance groups compile to
    512-deep scans whose per-step work is one select over the planes and
    an argmax, the exact wrong shape for a TPU.
    This kernel instead freezes the per-value boost/allowance tables for
    ``chunk`` placements at a time and selects each chunk with the same
    running-min-clamp + top-k used by the closed-form path, so a
    250-instance group runs ~16 wide parallel steps instead of 512
    narrow ones. Spread counts move by at most ``chunk`` between table
    refreshes; the resulting boost staleness is bounded and verified
    against the stepwise oracle in tests (test_value_scan.py). Caps
    (distinct_property) can overshoot within a chunk, so groups with cap
    blocks stay on the exact scan — see PlacementKernel.place routing.

    Reference seam: scheduler/spread.go:110-228 recomputes boosts per
    placement; the reference tolerates far coarser approximation in the
    other direction by score-sampling only ≥100 nodes (stack.go:165-174).
    """

    eligible, job_counts, penalty_nodes = _unpack_lane_inputs(
        capacity, eligible, job_counts, penalty_nodes
    )

    def one_group(
        ask, elig, jc0, dt, pen, aff, has_aff, dh, caps,
        vids, c0, desired, vcaps, weights, kinds, count,
    ):
        with jax.named_scope("fit_score"):
            num, den, fits = _score_planes(
                capacity, used0, ask, elig, jc0, dt, pen, aff, has_aff, dh,
                caps, algorithm_spread, max_j, jitter=jitter,
            )
        n = num.shape[0]
        nb = vids.shape[0]
        is_spread = (kinds == BLOCK_TARGET_SPREAD) | (kinds == BLOCK_EVEN_SPREAD)
        has_spread_any = jnp.any(is_spread)
        # the [B, V, N] membership of ``vids`` that the table reads select
        # through, built once
        member = vids[:, None, :] == jnp.arange(c0.shape[1])[None, :, None]
        js_row = jnp.arange(max_j, dtype=jnp.int32)[None, :]  # [1, J]

        def step(state, _):
            jn, c, n_placed = state  # i32[N], f32[B, V], i32[]
            tbl, allow = _block_tables(c, desired, vcaps, weights, kinds)
            per_block, allow_pb = _value_reads(member, tbl, allow)  # [B, N]
            contrib = jnp.where(vids >= 0, per_block, -1.0)
            boost = jnp.sum(
                jnp.where(is_spread[:, None], contrib, 0.0), axis=0
            )  # [N]
            allowed = jnp.all(
                jnp.where(
                    (kinds == BLOCK_DISTINCT_CAP)[:, None] & (vids >= 0),
                    allow_pb,
                    True,
                ),
                axis=0,
            )  # [N]

            spread_on = has_spread_any & (boost != 0.0)  # [N]
            den_t = den + jnp.where(spread_on, 1.0, 0.0)[:, None]
            s_raw = (num + jnp.where(spread_on, boost, 0.0)[:, None]) / den_t
            feas = fits & allowed[:, None] & (js_row >= jn[:, None])
            # consumed columns (j < jn) must not poison the running-min
            s_for_min = jnp.where(
                js_row < jn[:, None],
                jnp.inf,
                jnp.where(feas, s_raw, -jnp.inf),
            )
            s_sel = jax.lax.associative_scan(jnp.minimum, s_for_min, axis=1)
            s_sel = jnp.where(feas, s_sel, -jnp.inf)

            vals, idx = _topk_nodes(s_sel.reshape(-1), chunk, n_shards)
            take = (jnp.arange(chunk) + n_placed < count) & (vals > -jnp.inf)
            rows = (idx // max_j).astype(jnp.int32)
            true_scores = s_raw.reshape(-1)[idx]

            # dense masked updates — TPU scatters serialize
            jn = jn + jnp.sum(
                (jnp.arange(n)[None, :] == rows[:, None])
                & take[:, None],
                axis=0,
            ).astype(jnp.int32)
            picked_vals = vids[:, rows]  # [B, chunk]
            upd = take[None, :] & (picked_vals >= 0)
            c = c + jnp.sum(
                jnp.where(
                    upd[:, :, None],
                    picked_vals[:, :, None]
                    == jnp.arange(c.shape[1])[None, None, :],
                    False,
                ).astype(c.dtype),
                axis=1,
            )
            n_placed = n_placed + jnp.sum(take.astype(jnp.int32))
            return (jn, c, n_placed), (
                jnp.where(take, rows, -1),
                jnp.where(take, true_scores, -jnp.inf).astype(jnp.float32),
            )

        state0 = (
            jnp.zeros(n, dtype=jnp.int32),
            c0,
            jnp.zeros((), dtype=jnp.int32),
        )
        with jax.named_scope("spread_loop"):
            _, (choices, scores) = jax.lax.scan(
                step, state0, None, length=n_chunks
            )
        with jax.named_scope("pack_rows_scores"):
            return choices.reshape(-1), scores.reshape(-1)

    return jax.vmap(one_group)(
        asks, eligible, job_counts, desired_totals, penalty_nodes,
        affinity_scores, has_affinities, distinct_hosts, slot_caps,
        block_value_ids, block_counts0, block_desired, block_caps,
        block_weights, block_kinds, counts,
    )


def _column_heads(num_t, den_t, fits_t, jn):
    """Each node's column head out of the node-minor ``[J, N]`` planes:
    a one-hot select over J reduced along the sublane axis. A sum of one
    selected value and zeros is exact, so these are bit for bit the
    values ``take_along_axis(plane, min(jn, J-1))`` reads from ``[N, J]``
    (tests/test_opv_dense_reads.py keeps that gather as the reference) —
    without a gather, which the TPU serializes element by element."""
    max_j = num_t.shape[0]
    head_j = jnp.minimum(jn, max_j - 1)
    sel = jnp.arange(max_j, dtype=jn.dtype)[:, None] == head_j[None, :]
    head_num = jnp.sum(jnp.where(sel, num_t, 0.0), axis=0)
    head_den = jnp.sum(jnp.where(sel, den_t, 0.0), axis=0)
    head_fit = jnp.any(sel & fits_t, axis=0) & (jn < max_j)
    return head_num, head_den, head_fit


def _value_reads(member, tbl, allow):
    """Per-node reads ``[B, N]`` of the ``[B, V]`` boost and allowance
    tables through the membership compare ``member[b, v, n] = (vids[b, n]
    == v)``: a masked sum / any over V. A node without a value matches no
    v and reads 0.0 / False; the callers overwrite both under ``vids >=
    0`` as they did the gather's."""
    per_block = jnp.sum(jnp.where(member, tbl[:, :, None], 0.0), axis=1)
    allow_pb = jnp.any(member & allow[:, :, None], axis=1)
    return per_block, allow_pb


@functools.partial(traced_jit, retrace_budget=RETRACE_BUDGET,
                   static_argnames=("max_j", "k_seg", "n_chunks"))
def place_spread_opv_kernel(
    capacity,  # f32[N, D] shared
    used0,  # f32[N, D] shared snapshot usage
    asks,  # f32[G, D]
    eligible,  # bool[G, N]
    job_counts,  # i32[G, N]
    desired_totals,  # f32[G]
    penalty_nodes,  # bool[G, N]
    affinity_scores,  # f32[G, N]
    has_affinities,  # bool[G]
    distinct_hosts,  # bool[G]
    slot_caps,  # f32[G, N]
    block_value_ids,  # i32[G, B, N]
    block_counts0,  # f32[G, B, V]
    block_desired,  # f32[G, B, V]
    block_caps,  # f32[G, B, V]
    block_weights,  # f32[G, B]
    block_kinds,  # i32[G, B]
    enforce_idx,  # i32[G] block whose values are one-per-chunk
    algorithm_spread,  # bool[]
    counts,  # i32[G] placements to emit (incl. overflow slots)
    max_j: int,
    k_seg: int,  # picks per step = min(CHUNK, V+1)
    n_chunks: int,
    jitter=None,  # f32[N] tie-break noise
):
    """One-per-value chunked placement for even-mode spread groups.

    Even-spread boosts (spread.go:178-228) jump discontinuously as a
    value stops being the min — freezing the boost table for a plain
    CHUNK-sized step dumps the whole chunk onto the currently-min values
    and oscillates. But stepwise greedy under even-spread naturally
    *rotates* values (placing on the min value usually removes it from
    the min set), so restricting each step to at most ONE placement per
    value of the dominant even block recovers stepwise-like behavior
    while still placing up to min(CHUNK, V) instances per sequential
    step: per-value segment-max of the head scores, then top-k over the
    [V+1] segment maxima (the +1 segment holds value-less nodes).
    Depth count/min(CHUNK, V) instead of count — for the BASELINE
    config-3 shape (250 instances × 25 racks) that is 18 steps vs 512.

    Inside the loop the node axis is the minor axis of everything a step
    reads, and nothing is indexed dynamically: the column heads are a
    one-hot select over the [J, N] planes (``_column_heads``), the boost
    and allowance of a node's values a masked sum / any over the
    [B, V, N] membership of ``block_value_ids`` (``_value_reads``), and
    what a step needs of the nodes it picked (their values, the best
    score) a masked reduce over a one-hot of the row. Every one is exact,
    so picks and scores are those of the gathers these replaced
    (tests/test_opv_dense_reads.py), at 0.04 ms a step on a v5e where the
    seven gathers took 0.94 (PERF.md section 6, PR 30).
    """

    eligible, job_counts, penalty_nodes = _unpack_lane_inputs(
        capacity, eligible, job_counts, penalty_nodes
    )

    def one_group(
        ask, elig, jc0, dt, pen, aff, has_aff, dh, caps,
        vids, c0, desired, vcaps, weights, kinds, eidx, count,
    ):
        with jax.named_scope("fit_score"):
            num, den, fits = _score_planes(
                capacity, used0, ask, elig, jc0, dt, pen, aff, has_aff, dh,
                caps, algorithm_spread, max_j, jitter=jitter,
            )
        n = num.shape[0]
        nb = vids.shape[0]
        nv = c0.shape[1]
        is_spread = (kinds == BLOCK_TARGET_SPREAD) | (kinds == BLOCK_EVEN_SPREAD)
        has_spread_any = jnp.any(is_spread)
        # what the loop selects from, node axis minor, built once: the
        # planes as [J, N], the [B, V, N] membership of ``vids`` and the
        # row ids that the picks' one-hots compare against
        num_jn, den_jn, fits_jn = num.T, den.T, fits.T
        member = vids[:, None, :] == jnp.arange(nv)[None, :, None]
        node_ids = jnp.arange(n)
        evids = jnp.take(vids, eidx, axis=0)  # [N] enforce-block values
        seg = jnp.where(evids >= 0, evids, nv)  # [N]; nv = no-value segment
        # which enforce-block values actually exist on an eligible node:
        # V is padded to a power of two, and a phantom value with count 0
        # must not read as "empty" to the rotation guard (it would lock
        # the rotation onto unreachable segments and starve the chunk)
        present_v = jnp.any(
            (evids[None, :] == jnp.arange(nv)[:, None]) & elig[None, :],
            axis=1,
        )  # [V]

        def node_scores(head_num, head_den, head_ok, c):
            tbl, allow = _block_tables(c, desired, vcaps, weights, kinds)
            per_block, allow_pb = _value_reads(member, tbl, allow)
            contrib = jnp.where(vids >= 0, per_block, -1.0)
            boost = jnp.sum(
                jnp.where(is_spread[:, None], contrib, 0.0), axis=0
            )
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
            score = (head_num + jnp.where(spread_on, boost, 0.0)) / den_t
            return jnp.where(head_ok & allowed, score, -jnp.inf)

        def step(state, _):
            jn, c, n_placed = state
            head_num, head_den, head_fit = _column_heads(
                num_jn, den_jn, fits_jn, jn
            )

            # Two-phase chunk: spread counts sit at symmetric states (all
            # values even ⇒ every even-boost −1) at chunk boundaries, and
            # under a negative frozen total the component-count divisor
            # inverts within-value ordering — the whole chunk would
            # re-pick already-filled nodes. One placement breaks the
            # symmetry exactly as stepwise greedy experiences it, so:
            # pick 1 with the frozen table, bump its value, re-derive the
            # table, then pick the remaining k−1 one-per-value.
            score0 = node_scores(head_num, head_den, head_fit, c)
            first = jnp.argmax(score0).astype(jnp.int32)
            first_hot = node_ids == first  # [N]
            best0 = jnp.max(score0)
            ok0 = (best0 > -jnp.inf) & (n_placed < count)
            v_first = jnp.sum(jnp.where(first_hot, seg, 0))
            first_vals = jnp.sum(
                jnp.where(first_hot[None, :], vids, 0), axis=1
            )  # [B]
            c1 = c + jnp.where(
                (ok0 & (first_vals >= 0))[:, None],
                jax.nn.one_hot(
                    jnp.maximum(first_vals, 0), nv, dtype=c.dtype
                ),
                0.0,
            )

            score1 = node_scores(head_num, head_den, head_fit, c1)
            score1 = jnp.where(seg == v_first, -jnp.inf, score1)
            # Rotation guard: stepwise greedy only places on values at
            # the (positive) minimum count — or still empty — of the
            # dominant even block; each placement removes that value from
            # the min set. A chunk that keeps taking beyond the min set
            # pays the symmetric-state −1 boost for its tail picks and
            # diverges from greedy. Restrict the one-per-value picks to
            # the rotating set; the chunk under-fills and later chunks (or
            # the host repair re-score) finish the remainder exactly.
            ecounts = c1[eidx]  # [V] enforce-block counts after the bump
            pos1 = ecounts > 0
            minc1 = jnp.min(jnp.where(pos1, ecounts, jnp.inf))
            maxc1 = jnp.max(jnp.where(pos1, ecounts, -jnp.inf))
            empty_v = (~pos1) & present_v  # reachable and still unused
            no_empty = ~jnp.any(empty_v)
            # greedy's rotation set under even spread: empty values while
            # any exist (+1 boost beats every filled value's); otherwise
            # the at-min values — but only once the bump broke symmetry
            # (minc==maxc ⇒ every value scores the −1 symmetric boost;
            # greedy pays that once per ROUND, not once per pick — the
            # chunk's single first-pick is that once, and the next
            # chunk's re-derived table continues from the broken state)
            rotate_ok = jnp.where(
                no_empty,
                pos1 & (ecounts <= minc1) & (maxc1 > minc1),
                empty_v,
            )
            is_even_enforce = (
                jnp.take(kinds, eidx) == BLOCK_EVEN_SPREAD
            )
            seg_allowed = jnp.concatenate(
                [
                    jnp.where(is_even_enforce, rotate_ok, True),
                    jnp.ones(1, dtype=bool),  # value-less segment
                ]
            )
            # dense masked segment-max — TPU scatters serialize, masked
            # compare+reduce rides the VPU ([V+1, N] is small)
            seg_plane = seg[None, :] == jnp.arange(nv + 1)[:, None]
            seg_max = jnp.max(
                jnp.where(seg_plane, score1[None, :], -jnp.inf), axis=1
            )
            seg_max = jnp.where(seg_allowed, seg_max, -jnp.inf)
            with jax.named_scope("top_k"):
                vals, vsel = jax.lax.top_k(seg_max, k_seg - 1)
            take_r = (
                jnp.arange(k_seg - 1) + n_placed + ok0.astype(jnp.int32)
                < count
            ) & (vals > -jnp.inf) & ok0
            in_seg = seg[None, :] == vsel[:, None]  # [k_seg-1, N]
            rows_r = jnp.argmax(
                jnp.where(in_seg, score1[None, :], -jnp.inf), axis=1
            ).astype(jnp.int32)

            rows = jnp.concatenate([first[None], rows_r])
            take = jnp.concatenate([ok0[None], take_r])
            vals_all = jnp.concatenate([best0[None], vals])

            rows_hot = node_ids[None, :] == rows[:, None]  # [k_seg, N]
            jn = jn + jnp.sum(
                rows_hot & take[:, None], axis=0
            ).astype(jnp.int32)
            picked_vals = jnp.sum(
                jnp.where(rows_hot[1:], vids[:, None, :], 0), axis=2
            )  # [B, k_seg-1]
            upd = take_r[None, :] & (picked_vals >= 0)
            c = c1 + jnp.sum(
                jnp.where(
                    upd[:, :, None],
                    picked_vals[:, :, None]
                    == jnp.arange(c.shape[1])[None, None, :],
                    False,
                ).astype(c.dtype),
                axis=1,
            )
            # ok0 False ⇒ c1 == c and nothing was taken
            n_placed = n_placed + jnp.sum(take.astype(jnp.int32))
            return (jn, c, n_placed), (
                jnp.where(take, rows, -1),
                jnp.where(take, vals_all, -jnp.inf).astype(jnp.float32),
            )

        state0 = (
            jnp.zeros(n, dtype=jnp.int32),
            c0,
            jnp.zeros((), dtype=jnp.int32),
        )
        with jax.named_scope("spread_loop"):
            _, (choices, scores) = jax.lax.scan(
                step, state0, None, length=n_chunks
            )
        with jax.named_scope("pack_rows_scores"):
            return choices.reshape(-1), scores.reshape(-1)

    return jax.vmap(one_group)(
        asks, eligible, job_counts, desired_totals, penalty_nodes,
        affinity_scores, has_affinities, distinct_hosts, slot_caps,
        block_value_ids, block_counts0, block_desired, block_caps,
        block_weights, block_kinds, enforce_idx, counts,
    )


@functools.partial(traced_jit, retrace_budget=RETRACE_BUDGET)
def score_matrix_kernel(
    capacity,
    used,
    asks,  # f32[G, D]
    eligible,  # bool[G, N]
    job_counts,  # i32[G, N]
    desired_totals,
    penalty_nodes,
    affinity_scores,
    has_affinities,
    distinct_hosts,
    algorithm_spread,
    throughputs=None,  # f32[G, N] normalized class-throughput shares
):
    """The dense evals×nodes score matrix (no sequential state) — used for
    dry-run annotation, the system scheduler, and benchmarks. The optional
    class axis (``throughputs``) is Python-gated on None, so class-less
    callers compile and run the pre-heterogeneity program unchanged."""
    zero_boost = jnp.zeros(capacity.shape[0], dtype=jnp.float32)

    if throughputs is None:

        def one(a, e, jc, dt, pn, af, ha, dh):
            final, fits = component_scores(
                capacity, used, a, e, jc, dt, pn, af, ha,
                zero_boost, jnp.asarray(False), dh, algorithm_spread,
            )
            return final, fits

        return jax.vmap(one)(
            asks,
            eligible,
            job_counts,
            desired_totals,
            penalty_nodes,
            affinity_scores,
            has_affinities,
            distinct_hosts,
        )

    def one_tp(a, e, jc, dt, pn, af, ha, dh, tp):
        final, fits = component_scores(
            capacity, used, a, e, jc, dt, pn, af, ha,
            zero_boost, jnp.asarray(False), dh, algorithm_spread,
            throughputs=tp,
        )
        return final, fits

    return jax.vmap(one_tp)(
        asks,
        eligible,
        job_counts,
        desired_totals,
        penalty_nodes,
        affinity_scores,
        has_affinities,
        distinct_hosts,
        throughputs,
    )


def _steps_bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _dummy_ask(pn: int):
    """Zero-count padding lane for the group axis: eligible nowhere, so
    the kernel places nothing and its lane is dropped on unpack. Keeps
    the compiled G dimension bucketed (recompiles are the real cost of a
    varying batch size, not the padded FLOPs)."""
    from .flatten import GroupAsk

    return GroupAsk(
        job_id="",
        tg_name="",
        count=0,
        desired_total=1,
        ask=np.zeros(4, dtype=np.float32),
        eligible=np.zeros(pn, dtype=bool),
        job_counts=np.zeros(pn, dtype=np.int32),
        penalty_nodes=np.zeros(pn, dtype=bool),
        affinity_scores=np.zeros(pn, dtype=np.float32),
        has_affinities=False,
        distinct_hosts=False,
    )


def _pad_group_axis(asks: list, pn: int) -> list:
    """Pad the ask list so the compiled G dimension takes only two small
    values: 1 (single-eval path) or a power-of-two ≥ 16 (batched path).
    Collapsing 2..16 asks onto one 16-lane executable costs padded vmap
    lanes but avoids a recompile per distinct batch size."""
    n = len(asks)
    g = 1 if n == 1 else max(16, _steps_bucket(n))
    if g == n:
        return asks
    dummy = _dummy_ask(pn)
    return asks + [dummy] * (g - n)


def _shared_batch(asks: list, pn: int) -> dict:
    """Host-side assembly of the kernel inputs common to all placement
    paths (the value-block fields, the algorithm flag and the tie-break
    jitter are added by ``PlacementKernel``).

    Slim forms: eligibility/penalty masks ride bit-packed (u8, 8×), and
    per-lane arrays that are degenerate across the whole batch (no job
    allocs yet, no penalties, no affinities, no device asks — the common
    case for fresh registrations) collapse to [G, 1] broadcasts instead
    of [G, N] arrays. The forms key the compiled variants and keep the
    host's assembly and the one packed buffer small; what a kernel call
    pays for its operands is the number of hand-offs, not their bytes
    (PERF.md section 6, PR 36), and ``_pack_operands`` makes that one."""
    g = len(asks)
    jc = np.stack([a.job_counts for a in asks])
    if not jc.any():
        jc = np.zeros((g, 1), dtype=np.int32)
    pen = np.stack([a.penalty_nodes for a in asks])
    pen = (
        np.packbits(pen, axis=1)
        if pen.any()
        else np.zeros((g, 1), dtype=bool)
    )
    if any(a.has_affinities for a in asks):
        aff = np.stack([a.affinity_scores for a in asks])
    else:
        aff = np.zeros((g, 1), dtype=np.float32)
    if any(a.slot_caps is not None for a in asks):
        caps = np.stack(
            [
                a.slot_caps
                if a.slot_caps is not None
                else np.full(pn, np.inf, dtype=np.float32)
                for a in asks
            ]
        )
    else:
        caps = np.full((g, 1), np.inf, dtype=np.float32)
    return dict(
        asks=np.stack([a.ask for a in asks]),
        eligible=np.packbits(
            np.stack([a.eligible for a in asks]), axis=1
        ),
        job_counts=jc,
        desired_totals=np.array(
            [a.desired_total for a in asks], dtype=np.float32
        ),
        penalty_nodes=pen,
        affinity_scores=aff,
        has_affinities=np.array([a.has_affinities for a in asks]),
        distinct_hosts=np.array([a.distinct_hosts for a in asks]),
        slot_caps=caps,
        counts=np.array([a.count for a in asks], dtype=np.int32),
    )


# PartitionSpec axes per batch tensor (mesh sharding seam): groups ride
# data-parallel, dense per-node columns shard on the node axis. Packed u8
# masks and [G, 1] degenerate broadcasts keep their trailing axes
# replicated (shard_put skips any axis the mesh size doesn't divide).
_BATCH_SPECS = {
    "asks": ("groups",),
    "eligible": ("groups",),
    "job_counts": ("groups", "nodes"),
    "desired_totals": ("groups",),
    "penalty_nodes": ("groups",),
    "affinity_scores": ("groups", "nodes"),
    "has_affinities": ("groups",),
    "distinct_hosts": ("groups",),
    "slot_caps": ("groups", "nodes"),
    "counts": ("groups",),
    "block_value_ids": ("groups", None, "nodes"),
    "block_counts0": ("groups",),
    "block_desired": ("groups",),
    "block_caps": ("groups",),
    "block_weights": ("groups",),
    "block_kinds": ("groups",),
    "throughputs": ("groups", "nodes"),
    "jitter": ("nodes",),
}


def used_device(cluster, used0, cfg=None):
    """The one seam every kernel's per-pass ``used`` upload routes
    through. With incremental rescoring on (the tensors carry a
    ``score_cache``), the DeviceStateCache serves a device-resident
    buffer bitwise equal to ``used0`` — only dirty slices travelled;
    otherwise (or when the cache declines) the from-scratch
    ``shard_put``, byte for byte the pre-incremental upload. The
    returned array has the same aval either way, so the traced program
    is one and the same — the jaxpr-identity pin of the incremental
    path (analysis/jaxlint/diff.py)."""
    if cfg is None:
        cfg = get_mesh()
    cache = getattr(cluster, "score_cache", None)
    if cache is not None:
        dev = cache.score_view(cluster, used0, cfg)
        if dev is not None:
            return dev
    return shard_put(used0, ("nodes",), cfg)


def _device_batch(batch: dict, cfg) -> dict:
    """A host batch dict onto an active mesh, array by array, each with
    its PartitionSpec: what the specs are for, and what one packed
    buffer cannot carry. The per-call scalars that have no spec
    (``algorithm_spread``, ``enforce_idx``) go up unplaced."""
    return {
        k: shard_put(v, _BATCH_SPECS[k], cfg)
        if k in _BATCH_SPECS
        else jnp.asarray(v)
        for k, v in batch.items()
    }


def _pack_operands(batch: dict):
    """``(layout, words)``: the batch's arrays laid end to end in one
    fresh ``uint32`` buffer, each from a word boundary, in the dict's
    order. ``layout`` — ``(name, dtype, shape)`` per array — is a pure
    function of the shapes and dtypes, which key the compiled variant
    already; ``_unpack_operands`` is its inverse inside the compiled
    program. One buffer is one host→device hand-off where the dict was
    one per array, at 0.3 ms each whatever it carried."""
    arrays = [np.asarray(v) for v in batch.values()]
    layout = tuple(
        (k, a.dtype.name, a.shape) for k, a in zip(batch, arrays)
    )
    if any(a.dtype.itemsize not in (1, 4) for a in arrays):
        raise ValueError(f"operands of 1 or 4 bytes an element only: {layout}")
    starts = np.cumsum([0] + [-(-a.nbytes // 4) * 4 for a in arrays])
    words = np.zeros(int(starts[-1]) // 4, dtype=np.uint32)
    raw = words.view(np.uint8)
    for a, at in zip(arrays, starts):
        # reshape(-1) copies where ``a`` is not contiguous
        raw[at : at + a.nbytes] = a.reshape(-1).view(np.uint8)
    return layout, words


def _unpack_operands(words, layout) -> dict:
    """The arrays ``_pack_operands`` laid into ``words``, by static
    slices and bitcasts: same dtypes, shapes and bytes."""
    out = {}
    at = 0
    for name, dtype, shape in layout:
        dt = np.dtype(dtype)
        size = int(np.prod(shape, dtype=np.int64))
        n = -(-size * dt.itemsize // 4)
        seg = words[at : at + n]
        at += n
        if dt.itemsize == 4:
            arr = jax.lax.bitcast_convert_type(seg, dt)
        else:
            arr = jax.lax.bitcast_convert_type(seg, jnp.uint8)
            arr = arr.reshape(-1)[:size]
            if dt == np.bool_:
                arr = arr != 0
        out[name] = arr.reshape(shape)
    return out


@functools.cache
def _packed_entry(kernel, static: tuple):
    """The thin outer program of ``kernel`` for a packed call:
    ``(capacity, used0, words, layout, <the statics>)``. It unpacks and
    calls the kernel as it is (a nested ``traced_jit`` call inlines), so
    the kernel keeps its signature, and the device program is named
    ``<kernel>_packed``: whoever looks for the kernel's name finds it.
    One entry per kernel object, made at its first packed call."""

    def entry(capacity, used0, words, layout, **statics):
        return kernel(
            capacity, used0, **_unpack_operands(words, layout), **statics
        )

    entry.__name__ = entry.__qualname__ = f"{kernel.__name__}_packed"
    entry.__module__ = kernel.__module__
    entry.__signature__ = inspect.Signature(
        [
            inspect.Parameter(n, inspect.Parameter.POSITIONAL_OR_KEYWORD)
            for n in ("capacity", "used0", "words", "layout", *static)
        ]
    )
    return traced_jit(
        entry,
        retrace_budget=RETRACE_BUDGET,
        static_argnames=("layout", *static),
    )


@dataclass
class PlacementResult:
    """Host-side result for one group: chosen node rows (−1 = failed) and
    their normalized scores, in placement order; plus overflow candidates
    (the next entries greedy would have taken) for conflict repair."""

    node_rows: np.ndarray
    scores: np.ndarray
    overflow_rows: np.ndarray = field(
        default_factory=lambda: np.empty(0, dtype=np.int32)
    )
    overflow_scores: np.ndarray = field(
        default_factory=lambda: np.empty(0, dtype=np.float32)
    )
    # score provenance (obs/explain.PlacementExplanation), attached only
    # when the pass ran with explain=True; purely observational — never
    # consulted by repair or the schedulers' placement decisions
    explanation: Optional[object] = None
    # set by repair_batch_conflicts on an ``exact`` lane it placed after
    # every other lane of the pass, on the usage that holds them all: the
    # lane's plan commits after theirs, at an index of its own
    deferred: bool = False


class PlacementKernel:
    """Host wrapper: pads a list of GroupAsks into batch tensors, runs the
    compiled kernel, unpacks results. Shape-bucketed so node churn and
    varying batch sizes hit a small set of compiled programs."""

    def __init__(
        self,
        algorithm: str = "binpack",
        force_scan: bool = False,
        mesh=None,  # utils.backend.MeshConfig override; None = process mesh
    ):
        self.algorithm = algorithm
        self.algorithm_spread = algorithm == "spread"
        self.force_scan = force_scan  # parity testing: disable the fast path
        self._mesh = mesh

    def mesh_cfg(self):
        return self._mesh if self._mesh is not None else get_mesh()

    def _n_shards(self, pn: int) -> int:
        """Static node-axis shard count for the hierarchical top-k; 1
        unless the mesh is active AND divides the padded bucket (pn is a
        power of two ≥ 8 and mp is a power of two, so a non-dividing mp
        means mp > pn — a tiny cluster on a big mesh)."""
        cfg = self.mesh_cfg()
        mp = cfg.n_node_shards
        return mp if mp > 1 and pn % mp == 0 else 1

    def _call(self, kernel, cluster, used0, batch, jitter, **statics):
        """One kernel call with its operands handed to the device, as
        the stage ``place.upload`` and the dispatch after it. Capacity
        is the cache generation's resident buffer (an upload of its own
        only for hand-built tensors), usage goes through its seam
        ``used_device``, and everything else that is new each call —
        the batch, the tie-break jitter, the algorithm flag — goes up
        as one packed buffer that the kernel's outer program unpacks;
        under an active mesh array by array, each with its
        PartitionSpec, into the kernel itself. The span's tags
        ``transfers`` and ``bytes`` say what it handed over."""
        cfg = self.mesh_cfg()
        batch = dict(batch, algorithm_spread=np.asarray(self.algorithm_spread))
        if jitter is not None:
            batch["jitter"] = jitter
        with _tracer.span("place.upload") as sp:
            before = transfer_totals()
            capacity = self._capacity_dev(cluster, cfg)
            used = used_device(cluster, used0, cfg)
            if cfg.active:
                operands = _device_batch(batch, cfg)
            else:
                layout, words = _pack_operands(batch)
                words = host_put(words)
            if sp is not None:
                after = transfer_totals()
                sp.tags["transfers"] = after[0] - before[0]
                sp.tags["bytes"] = after[1] - before[1]
        if cfg.active:
            return kernel(capacity, used, **operands, **statics)
        return _packed_entry(kernel, tuple(sorted(statics)))(
            capacity, used, words, layout=layout, **statics
        )

    @staticmethod
    def _capacity_dev(cluster, cfg):
        """The DeviceStateCache's resident capacity buffer when one rode
        along on the tensors; else upload via the seam."""
        dev = getattr(cluster, "device_capacity", None)
        if dev is not None:
            return dev
        return shard_put(cluster.capacity, ("nodes",), cfg)

    def place(
        self,
        cluster,
        asks: list,
        *,
        overflow: int = OVERFLOW_CANDIDATES,
        decorrelate: bool = False,
        decorrelate_salt: int = 0,
        used_override=None,  # [pn, D] optimistic usage (pipelined passes)
        explain: bool = False,  # attach score provenance (obs/explain)
    ) -> list[PlacementResult]:
        """``overflow`` = extra greedy candidates emitted per lane for
        conflict repair. ``decorrelate``: stripe each lane onto a disjoint
        node partition so concurrent-eval lanes stop argmaxing onto the
        same nodes — the vector analog of the reference's per-worker
        shuffle sampling (stack.go:74-90); repair re-scores any shortfall
        against the full node set, so partitioning is purely an
        optimization. ``decorrelate_salt`` rotates which lane gets which
        stripe and seeds the tie-break jitter; the worker derives it
        from the work (the first eval's job lane in lane mode), not from
        how many workers run."""
        if not asks:
            return []
        from ..resilience.breaker import degraded

        if degraded():
            # one tick per scoring pass executed while any kernel breaker
            # is open / forced open — the pass runs on the reference path
            from ..utils.metrics import global_metrics as _metrics

            _metrics.incr("nomad.resilience.fallback_passes")
        used0 = (
            np.asarray(cluster.used)
            if used_override is None
            else np.asarray(used_override)
        )
        work = asks
        jitter = None
        if decorrelate:
            work = _decorrelate_lanes(
                cluster, asks, salt=decorrelate_salt, used0=used0
            )
            rows = np.arange(cluster.padded_n, dtype=np.int64)
            h = (rows * 2654435761 + (decorrelate_salt + 1) * 40503) & 0xFFFFFFFF
            jitter = ((h % 65536).astype(np.float32) / 65536.0) * 2e-5
        # routing: uncoupled groups → closed-form top-k; large
        # spread-coupled groups → chunked (one-per-value variant when an
        # even block is present); small / capped groups → exact scan
        fast, chunked, opv, scan = [], [], [], []
        for i, a in enumerate(work):
            coupled = a.blocks is not None and a.blocks.num_blocks > 0
            if self.force_scan or (coupled and self._needs_exact_scan(a)):
                scan.append(i)
            elif coupled:
                if bool((a.blocks.kinds == BLOCK_EVEN_SPREAD).any()):
                    opv.append(i)
                else:
                    chunked.append(i)
            else:
                fast.append(i)
        out: list[Optional[PlacementResult]] = [None] * len(asks)
        # the span carries the routing split so a trace shows WHICH
        # kernel family scored each pass. Its children are the stages of
        # each family's call: place.assemble (host batch), place.upload,
        # kernel:<name> (the dispatch, from traced_jit's hook),
        # place.pull (the blocking fetch: device wait + transfer).
        # ``narrowed``: lanes that decorrelation confined to a stripe or
        # a worker's slice of the nodes; the rest score the full set
        with _tracer.span(
            "kernel.place",
            tags={
                "lanes": len(asks),
                "fast": len(fast),
                "chunked": len(chunked),
                "opv": len(opv),
                "scan": len(scan),
                "narrowed": sum(w is not a for w, a in zip(work, asks)),
            },
        ):
            for idxs, fn in (
                (fast, self._place_closed_form),
                (chunked, self._place_spread_chunked),
                (opv, self._place_spread_opv),
                (scan, self._place_scan_batch),
            ):
                if idxs:
                    for i, r in zip(
                        idxs,
                        fn(
                            cluster, [work[i] for i in idxs], overflow,
                            jitter, used0,
                        ),
                    ):
                        out[i] = r
        if explain:
            # Python-level gate, exactly like the hetero ``None`` gate:
            # explain-off passes run the identical code above (no new
            # traced program exists in either mode) and place
            # bit-for-bit. Explanations are built host-side against the
            # ORIGINAL asks and the pass's base usage — decorrelation
            # stripes/jitter are a placement optimization repair undoes,
            # not part of the score semantics being explained.
            from ..obs.explain import explain_group

            with _tracer.span("explain", tags={"step": "groups"}):
                sharded = self.mesh_cfg().n_node_shards > 1
                for a, res in zip(asks, out):
                    if res is not None:
                        cand = None
                        if sharded:
                            # node axis sharded: rank only the candidate
                            # columns the kernel actually surfaced (primary +
                            # overflow) instead of gathering full score rows
                            # back to host — the per-shard top-k union
                            # provably contains every global winner
                            cand = np.unique(
                                np.concatenate(
                                    [res.node_rows, res.overflow_rows]
                                )
                            )
                            cand = cand[cand >= 0]
                        res.explanation = explain_group(
                            cluster, a, used0,
                            algorithm=self.algorithm,
                            algorithm_spread=self.algorithm_spread,
                            candidate_rows=cand,
                        )
        return out

    @staticmethod
    def _needs_exact_scan(a) -> bool:
        """Cap (distinct_property) blocks can overshoot a per-value
        budget within one chunk, and small groups compile to short exact
        scans anyway — both stay on the stepwise path. So does an even
        block whose map holds a value at 0: its boost is flat off that
        min, and the one-per-value chunks no longer follow greedy."""
        if a.count <= EXACT_SCAN_MAX_COUNT:
            return True
        b = a.blocks
        return bool((b.kinds == BLOCK_DISTINCT_CAP).any()) or bool(
            b.held_at_zero.any())

    @staticmethod
    def _j_bucket(n: int) -> int:
        """Multiples of 16 up to 128, then multiples of 64. Plane work
        scales with J and padding is pure overhead, so the buckets stay
        fine (a sixteenth at a time) while a typical workload still
        touches only 1-2 compiled variants; every bucket is a cold
        compile (ROADMAP A8, C4: not re-measured on the chip)."""
        if n <= 16:
            return 16
        if n <= 24:
            return 24  # the spread-opv J cap (n_chunks+1) lives here
        if n <= 128:
            return -(-n // 16) * 16
        return -(-n // 64) * 64

    def _max_j(self, cluster, asks: list) -> int:
        """J bound: most instances of one identical ask any node could
        hold, bucketed (see _j_bucket)."""
        cap_max = np.asarray(cluster.capacity).max(axis=0)  # [D]
        max_j = 1
        for a in asks:
            pos = a.ask > 0
            if pos.any():
                j = int(np.floor(np.min(cap_max[pos] / a.ask[pos]))) + 1
            else:
                j = a.count
            max_j = max(max_j, min(j, a.count))
        return self._j_bucket(max_j)

    def _place_closed_form(
        self, cluster, asks: list, overflow: int = OVERFLOW_CANDIDATES,
        jitter=None, used0=None,
    ) -> list[PlacementResult]:
        if used0 is None:
            used0 = np.asarray(cluster.used)
        pn = cluster.padded_n
        max_count = max(a.count for a in asks)
        k = _steps_bucket(max(max_count + overflow, 1))
        max_j = self._max_j(cluster, asks)

        # chunk the group axis so the [chunk, N, J] planes stay within an
        # HBM budget (~4 GB of live f32 planes on a 16 GB v5e chip);
        # splitting a pass costs an extra dispatch and fetch, so the
        # budget errs large
        bytes_per_lane = pn * max_j * 4 * 4
        chunk = max(1, int((4 << 30) // max(bytes_per_lane, 1)))
        if len(asks) > chunk:
            out: list[PlacementResult] = []
            for i in range(0, len(asks), chunk):
                out.extend(
                    self._place_closed_form(
                        cluster, asks[i:i + chunk], overflow, jitter, used0
                    )
                )
            return out

        with _tracer.span("place.assemble"):
            real_n = len(asks)
            asks = _pad_group_axis(asks, pn)
            batch = _shared_batch(asks, pn)
        packed = self._call(
            place_closed_form_kernel, cluster, used0, batch, jitter,
            max_j=max_j, k=k, n_shards=self._n_shards(pn),
        )
        with _tracer.span("place.pull"):
            fused = np.array(packed)
        choices = fused[:, :k]  # writable copies: repair mutates rows
        scores = fused[:, k:].view(np.float32)
        return [
            PlacementResult(
                node_rows=choices[gi, : a.count],
                scores=scores[gi, : a.count],
                overflow_rows=choices[gi, a.count :],
                overflow_scores=scores[gi, a.count :],
            )
            for gi, a in enumerate(asks[:real_n])
        ]

    def _place_scan_batch(
        self, cluster, asks: list, overflow: int = OVERFLOW_CANDIDATES,
        jitter=None, used0=None,
    ) -> list[PlacementResult]:
        if used0 is None:
            used0 = np.asarray(cluster.used)
        from .flatten import pad_value_blocks

        pn = cluster.padded_n
        with _tracer.span("place.assemble"):
            real_n = len(asks)
            asks = _pad_group_axis(asks, pn)
            max_count = max(a.count for a in asks)
            max_steps = _steps_bucket(max(max_count + overflow, 1))
            max_j = self._max_j(cluster, asks)

            batch = _shared_batch(asks, pn)
            # emit overflow candidates past each lane's primary count
            batch["counts"] = np.minimum(
                batch["counts"] + overflow, max_steps
            ).astype(np.int32)
            # zero-count padding lanes stay inert (eligible nowhere)
            batch["counts"] = np.where(
                np.array([a.count for a in asks]) > 0, batch["counts"], 0
            ).astype(np.int32)
            batch.update(pad_value_blocks([a.blocks for a in asks], pn))
        choices, scores = self._call(
            place_value_scan_kernel, cluster, used0, batch, jitter,
            max_j=max_j, max_steps=max_steps,
        )
        return self._unpack_coupled(choices, scores, asks[:real_n], overflow)

    def _place_spread_chunked(
        self, cluster, asks: list, overflow: int = OVERFLOW_CANDIDATES,
        jitter=None, used0=None,
    ) -> list[PlacementResult]:
        if used0 is None:
            used0 = np.asarray(cluster.used)
        from .flatten import pad_value_blocks

        pn = cluster.padded_n
        with _tracer.span("place.assemble"):
            real_n = len(asks)
            asks = _pad_group_axis(asks, pn)
            max_count = max(a.count for a in asks)
            max_j = self._max_j(cluster, asks)
            # round chunk count to a multiple of 4, not a power of two —
            # the sequential depth is the dominant cost and 2× overshoot
            # is real wall-clock; a handful of extra compile variants is
            # not
            n_chunks = max(
                4, -(-max(-(-(max_count + overflow) // CHUNK), 1) // 4) * 4
            )

            batch = _shared_batch(asks, pn)
            batch["counts"] = np.minimum(
                batch["counts"] + overflow, n_chunks * CHUNK
            ).astype(np.int32)
            batch["counts"] = np.where(
                np.array([a.count for a in asks]) > 0, batch["counts"], 0
            ).astype(np.int32)
            batch.update(pad_value_blocks([a.blocks for a in asks], pn))
        choices, scores = self._call(
            place_spread_chunked_kernel, cluster, used0, batch, jitter,
            max_j=max_j, chunk=CHUNK, n_chunks=n_chunks,
            n_shards=self._n_shards(pn),
        )
        return self._unpack_coupled(choices, scores, asks[:real_n], overflow)

    def _place_spread_opv(
        self, cluster, asks: list, overflow: int = OVERFLOW_CANDIDATES,
        jitter=None, used0=None,
    ) -> list[PlacementResult]:
        if used0 is None:
            used0 = np.asarray(cluster.used)
        from .flatten import pad_value_blocks

        pn = cluster.padded_n
        with _tracer.span("place.assemble"):
            real_n = len(asks)
            asks = _pad_group_axis(asks, pn)
            max_j = self._max_j(cluster, asks)

            batch = _shared_batch(asks, pn)
            blocks_list = [a.blocks for a in asks]
            batch.update(pad_value_blocks(blocks_list, pn))
            nv = batch["block_counts0"].shape[2]
            k_seg = min(CHUNK, nv + 1)

            # per-lane: dominant even block + how many picks one chunk can
            # actually yield (active values of that block, +1 for value-less
            # nodes) — lanes with few values need more sequential chunks
            enforce_idx = np.zeros(len(asks), dtype=np.int32)
            lane_steps = 1
            for gi, a in enumerate(asks):
                b = a.blocks
                if b is None or a.count <= 0:
                    continue
                even = np.flatnonzero(b.kinds == BLOCK_EVEN_SPREAD)
                if even.size:
                    enforce_idx[gi] = even[np.argmax(b.weights[even])]
                ev = b.value_ids[enforce_idx[gi]]
                # a step can only yield picks from segments that hold at
                # least one ELIGIBLE node (pad rows and unreachable values
                # yield nothing — counting them under-provisions n_chunks
                # and truncates the lane's placements)
                elig = a.eligible
                v_act = len(np.unique(ev[(ev >= 0) & elig])) + int(
                    ((ev < 0) & elig).any()
                )
                per_chunk = max(1, min(k_seg, v_act))
                lane_steps = max(
                    lane_steps, -(-(a.count + overflow) // per_chunk)
                )
            # multiple-of-4 rounding, not power-of-two (sequential depth is
            # the dominant cost; see _place_spread_chunked). +2 slack chunks:
            # the rotation guard makes a chunk starting from uneven counts
            # yield fewer than v_act picks; the host repair re-score rescues
            # any residue, but slack keeps that path cold.
            n_chunks = max(4, -(-(lane_steps + 2) // 4) * 4)
            # J bound tightened by the kernel's own structure: each chunk
            # step picks DISTINCT nodes (the first pick and the one-per-value
            # segment picks are disjoint), so one node gains at most one
            # instance per step — head_j never exceeds n_chunks. At the
            # config-3 shape that is J 24 where the ask alone gives 80;
            # what the kernel costs at that shape is in PERF.md §5–6.
            max_j = min(max_j, self._j_bucket(n_chunks + 1))

            batch["counts"] = np.minimum(
                batch["counts"] + overflow, n_chunks * k_seg
            ).astype(np.int32)
            batch["counts"] = np.where(
                np.array([a.count for a in asks]) > 0, batch["counts"], 0
            ).astype(np.int32)
            batch["enforce_idx"] = enforce_idx
        choices, scores = self._call(
            place_spread_opv_kernel, cluster, used0, batch, jitter,
            max_j=max_j, k_seg=k_seg, n_chunks=n_chunks,
        )
        return self._unpack_coupled(choices, scores, asks[:real_n], overflow)

    @staticmethod
    def _unpack_coupled(choices, scores, asks, overflow):
        """Compact each lane's valid picks (greedy emission order) into
        count primary + overflow slots. The one-per-value kernel can
        intersperse empty slots between chunks (a chunk is capped at one
        pick per value, not by feasibility), so valid picks are compacted
        rather than sliced positionally."""
        with _tracer.span("place.pull"):
            choices = np.array(choices)
            scores = np.array(scores)
        out = []
        for gi, a in enumerate(asks):
            row = choices[gi]
            valid = row >= 0
            vrows = row[valid]
            vscores = scores[gi][valid]
            node_rows = np.full(a.count, -1, dtype=np.int32)
            sc = np.full(a.count, -np.inf, dtype=np.float32)
            n_primary = min(a.count, vrows.shape[0])
            node_rows[:n_primary] = vrows[:n_primary]
            sc[:n_primary] = vscores[:n_primary]
            of_rows = np.full(overflow, -1, dtype=np.int32)
            of_sc = np.full(overflow, -np.inf, dtype=np.float32)
            n_of = min(overflow, max(0, vrows.shape[0] - a.count))
            of_rows[:n_of] = vrows[a.count : a.count + n_of]
            of_sc[:n_of] = vscores[a.count : a.count + n_of]
            out.append(
                PlacementResult(
                    node_rows=node_rows,
                    scores=sc,
                    overflow_rows=of_rows,
                    overflow_scores=of_sc,
                )
            )
        return out


def _decorrelate_lanes(cluster, asks: list, salt: int = 0, used0=None) -> list:
    """Stripe each batch lane onto a disjoint subset of node rows
    (hash(row) % n_lanes == lane). Concurrent lanes scoring the same
    snapshot otherwise compute near-identical greedy sequences, pile onto
    the same nodes and leave the conflicts to the host repair. The
    reference decorrelates its parallel workers by per-worker node
    shuffling + limit sampling (stack.go:74-90); a 1/L stripe of a 10k
    cluster still offers each lane more candidates than the reference's
    ≥100-node sample. Lanes whose stripe leaves thin headroom (or whose
    constraints concentrate eligibility) keep the full node set — repair
    resolves whatever conflicts remain. What a stripe costs in placement
    quality is in PERF.md §2 and §7 (jobs of a pass with several
    registrations, against the best on offer); a lane marked ``exact``
    (``GroupAsk.exact``) does not pay it and keeps the full node set."""
    from dataclasses import replace

    n_lanes = len(asks)
    if n_lanes < 2:
        return asks
    pn = cluster.padded_n
    # stripes decorrelate lanes WITHIN one batch. The salt rotates which
    # lane gets which stripe and seeds the score jitter in place(); it is
    # a function of the work (the first eval's job lane), so a run with
    # more batching workers reproduces the one-worker placements. Across
    # workers, lane ownership and claims (server/lanes.py) keep passes
    # apart; nothing here knows the worker count.
    rows = np.arange(pn)
    # Stripe on a HASHED row index, not the raw row: raw `rows % l_eff`
    # interacts arithmetically with any attribute laid out periodically
    # over rows (racks assigned round-robin: rack = row % n_racks). When
    # gcd(l_eff, n_racks) > 1 each stripe reaches only n_racks/gcd of the
    # rack values, the reachability guard below rejects every lane, and
    # the whole batch falls back to the full node set and to repair. A
    # multiplicative hash de-correlates stripe membership from any
    # row-periodic attribute, so each stripe samples all values
    # ~uniformly.
    row_hash = (rows.astype(np.uint64) * np.uint64(2654435761)) & np.uint64(
        0xFFFFFFFF
    )
    free = np.asarray(cluster.capacity) - (
        np.asarray(cluster.used) if used0 is None else np.asarray(used0)
    )  # [pn, D]
    out = []
    for i, a in enumerate(asks):
        if a.count <= 0 or a.exact:
            out.append(a)
            continue
        # Widest stripe count that still leaves this lane comfortable
        # headroom, measured in feasible INSTANCE SLOTS (Σ per-node jmax),
        # not node count — a node holds many instances of one ask, and
        # sizing by nodes caps l_eff at ~N/(2·count), which makes lanes
        # share stripes and collide. When even the slot-based 1/n_lanes
        # stripe is too thin, lanes SHARE coarser stripes (conflicts only
        # within a stripe group) instead of abandoning decorrelation
        # entirely.
        pos = a.ask > 0
        if pos.any():
            jn = np.floor(
                np.min(free[:, pos] / a.ask[pos], axis=1)
            ).clip(min=0)
        else:
            jn = np.full(pn, float(a.count))
        jn = np.where(a.eligible, jn, 0.0)
        if a.slot_caps is not None:
            # a node takes no more instances than its free device
            # instances allow (the kernel's jmax has the same cap): on a
            # fleet whose instances are nearly all held, headroom counted
            # in cpu and memory alone cuts a stripe that holds almost none
            # of the free instances, and the lane leaves them unused
            jn = np.minimum(jn, a.slot_caps)

        # full-set value vocabulary per block, computed ONCE per ask
        full_vals_per_block = (
            [
                np.unique(
                    a.blocks.value_ids[b][
                        (a.blocks.value_ids[b] >= 0) & a.eligible
                    ]
                ).shape[0]
                for b in range(a.blocks.num_blocks)
            ]
            if a.blocks is not None
            else []
        )

        def values_reachable(mask) -> bool:
            # a node subset must not silently amputate spread/cap values:
            # every value reachable from the full eligible set must stay
            # reachable from the subset (rack-contiguous row orderings
            # with racks smaller than the lane count would otherwise skew
            # the spread with no error surfaced)
            if a.blocks is None:
                return True
            for b in range(a.blocks.num_blocks):
                vids = a.blocks.value_ids[b]
                sub_vals = np.unique(vids[(vids >= 0) & mask])
                if full_vals_per_block[b] != sub_vals.shape[0]:
                    return False
            return True

        total_elig = int(a.eligible.sum())
        slots = float(jn.sum())
        l_eff = min(
            n_lanes,
            max(1, min(
                int(slots // max(4 * a.count, 1)), total_elig // 8
            )),
        )
        if l_eff < 2:
            out.append(a)
            continue
        in_stripe = (
            (row_hash % np.uint64(l_eff)).astype(np.int64)
            == ((i + salt) % l_eff)
        )
        elig = a.eligible & in_stripe
        # the stripe must still hold 2× the lane's ask in feasible slots
        ok = float(jn[elig].sum()) >= 2 * a.count and int(
            elig.sum()
        ) >= 8
        if ok:
            ok = values_reachable(elig)
        out.append(replace(a, eligible=elig) if ok else a)
    return out


def even_boost(c, held_at_zero):
    """NumPy mirror of _block_tables' even branch over count rows ``c``
    ``[..., V]``: min and max over the values the combined-use map holds
    (a positive count, or ``held_at_zero``); 0 where no count is
    positive."""
    c = np.asarray(c)
    held = (c > 0) | held_at_zero
    minc = np.where(held, c, np.inf).min(axis=-1, keepdims=True)
    maxc = np.where(held, c, -np.inf).max(axis=-1, keepdims=True)
    pos_min = np.isfinite(minc) & (minc > 0)
    safe_min = np.where(pos_min, minc, 1.0)
    at_min = np.where(
        minc == maxc, -1.0, np.where(pos_min, (maxc - minc) / safe_min, 1.0))
    off_min = np.where(pos_min, (minc - c) / safe_min, -1.0)
    boost = np.where(c == minc, at_min, off_min)
    return np.where((c > 0).any(axis=-1, keepdims=True), boost, 0.0)


def _host_block_tables(c, blocks):
    """NumPy mirror of _block_tables for one lane's [B, V] count state."""
    boost = np.zeros_like(c)
    allow = np.ones_like(c, dtype=bool)
    for b in range(blocks.num_blocks):
        kind = blocks.kinds[b]
        if kind == BLOCK_TARGET_SPREAD:
            d = blocks.desired[b]
            boost[b] = np.where(
                d > 0,
                (d - (c[b] + 1.0)) / np.maximum(d, 1e-9) * blocks.weights[b],
                -1.0,
            )
        elif kind == BLOCK_EVEN_SPREAD:
            boost[b] = even_boost(c[b], blocks.held_at_zero[b])
        elif kind == BLOCK_DISTINCT_CAP:
            allow[b] = c[b] < blocks.caps[b]
    return boost, allow


def _rescore_pick(capacity, used, a, placed_on_node, counts, algorithm_spread):
    """Exact host-side argmax for one additional placement of ``a``
    against a usage overlay — the same component semantics as the device
    kernels (see module docstring), in one vectorized NumPy pass. Used by
    repair when a lane's precomputed overflow candidates run out, so a
    conflicted placement is re-placed instead of aborting the whole eval.
    Returns (row, score) with row −1 when nothing fits."""
    prop = used + a.ask[None, :]
    fits = np.all(prop <= capacity, axis=1) & a.eligible
    jc = a.job_counts + placed_on_node
    if a.distinct_hosts:
        fits &= jc == 0
    if a.slot_caps is not None:
        fits &= placed_on_node < a.slot_caps
    blocks = a.blocks
    boost = np.zeros(capacity.shape[0], dtype=np.float32)
    has_spread_any = False
    if blocks is not None:
        tbl_boost, tbl_allow = _host_block_tables(counts, blocks)
        for b in range(blocks.num_blocks):
            vids = blocks.value_ids[b]
            safe = np.maximum(vids, 0)
            if blocks.kinds[b] == BLOCK_DISTINCT_CAP:
                fits &= np.where(vids >= 0, tbl_allow[b][safe], True)
            elif blocks.kinds[b] in (BLOCK_TARGET_SPREAD, BLOCK_EVEN_SPREAD):
                has_spread_any = True
                boost += np.where(vids >= 0, tbl_boost[b][safe], -1.0)
    if not fits.any():
        return -1, -np.inf
    free = np.where(
        capacity > 0, (capacity - prop) / np.maximum(capacity, 1e-9), 1.0
    )
    pow_sum = 10.0 ** free[:, 0] + 10.0 ** free[:, 1]
    binpack = np.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
    spread_fit = np.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
    fit_score = (spread_fit if algorithm_spread else binpack) / BINPACK_MAX_SCORE
    coll = jc.astype(np.float32)
    anti = np.where(jc > 0, -(coll + 1.0) / max(a.desired_total, 1.0), 0.0)
    resched = np.where(a.penalty_nodes, -1.0, 0.0)
    aff = a.affinity_scores if a.has_affinities else 0.0
    spread_on = has_spread_any & (boost != 0.0)
    num = fit_score + anti + resched + aff + np.where(spread_on, boost, 0.0)
    den = (
        1.0
        + (jc > 0)
        + a.penalty_nodes
        + (1.0 if a.has_affinities else 0.0)
        + spread_on
    )
    score = np.where(fits, num / den, -np.inf)
    row = int(np.argmax(score))
    return row, float(score[row])


class _LaneRescore:
    """``_rescore_pick`` for the placements of one lane's repair walk,
    kept from one pick to the next. Built with one full pass, it keeps
    what depends on a row's own state and the ask alone — the fit mask
    before the value blocks, the numerator and the denominator without
    their spread term — and ``commit`` recomputes them at the one row a
    placement changed. A pick recomputes only what the per-value counts
    decide (the V-wide block tables and their gather over the rows), so
    it returns the oracle's ``(row, score)`` on the same ``used``, lane
    placements and ``counts``, bit for bit: the same expressions, in the
    same order and dtypes. ``used`` and ``counts`` are the walk's own
    arrays, read at each pick; nothing else may write ``used`` while the
    walk holds one of these."""

    def __init__(self, capacity, used, a, placed_on_node, counts,
                 algorithm_spread):
        self.capacity, self.used, self.a = capacity, used, a
        self.counts, self.algorithm_spread = counts, algorithm_spread
        self.pm = np.zeros(capacity.shape[0], dtype=np.float32)
        for r, m in placed_on_node.items():
            self.pm[r] = m
        self.fits, self.num, self.den = self._parts(slice(None))
        # per block: each row's index into its table, V where the row
        # has no value (the table's extra last entry: _rescore_pick's
        # fill for a row without one)
        blocks = a.blocks
        self.cols = [] if blocks is None else [
            np.where(vids >= 0, vids, blocks.num_values).astype(np.intp)
            for vids in blocks.value_ids
        ]

    def _parts(self, sl):
        """The row-local parts of ``_rescore_pick`` over the rows ``sl``."""
        a, capacity = self.a, self.capacity[sl]
        pm = self.pm[sl]
        prop = self.used[sl] + a.ask[None, :]
        fits = np.all(prop <= capacity, axis=1) & a.eligible[sl]
        jc = a.job_counts[sl] + pm
        if a.distinct_hosts:
            fits &= jc == 0
        if a.slot_caps is not None:
            fits &= pm < a.slot_caps[sl]
        free = np.where(
            capacity > 0, (capacity - prop) / np.maximum(capacity, 1e-9), 1.0
        )
        pow_sum = 10.0 ** free[:, 0] + 10.0 ** free[:, 1]
        binpack = np.clip(20.0 - pow_sum, 0.0, BINPACK_MAX_SCORE)
        spread_fit = np.clip(pow_sum - 2.0, 0.0, BINPACK_MAX_SCORE)
        fit_score = (
            spread_fit if self.algorithm_spread else binpack
        ) / BINPACK_MAX_SCORE
        coll = jc.astype(np.float32)
        anti = np.where(jc > 0, -(coll + 1.0) / max(a.desired_total, 1.0), 0.0)
        resched = np.where(a.penalty_nodes[sl], -1.0, 0.0)
        aff = a.affinity_scores[sl] if a.has_affinities else 0.0
        num = fit_score + anti + resched + aff
        den = (
            1.0
            + (jc > 0)
            + a.penalty_nodes[sl]
            + (1.0 if a.has_affinities else 0.0)
        )
        return fits, num, den

    def commit(self, row: int) -> None:
        """One more placement of the lane at ``row``, after the walk has
        added it to ``used``."""
        self.pm[row] += 1
        sl = slice(row, row + 1)
        self.fits[sl], self.num[sl], self.den[sl] = self._parts(sl)

    def pick(self):
        """``_rescore_pick`` on the walk's state: (row, score), row −1
        when nothing fits."""
        blocks, fits = self.a.blocks, self.fits
        boost = np.zeros(fits.shape[0], dtype=np.float32)
        has_spread_any = False
        if blocks is not None:
            tbl_boost, tbl_allow = _host_block_tables(self.counts, blocks)
            for b, col in enumerate(self.cols):
                if blocks.kinds[b] == BLOCK_DISTINCT_CAP:
                    fits = fits & np.append(tbl_allow[b], True).take(col)
                elif blocks.kinds[b] in (
                    BLOCK_TARGET_SPREAD, BLOCK_EVEN_SPREAD
                ):
                    has_spread_any = True
                    tbl = np.append(tbl_boost[b], -1.0)
                    boost += tbl.astype(tbl_boost.dtype).take(col)
        if not fits.any():
            return -1, -np.inf
        spread_on = has_spread_any & (boost != 0.0)
        num = self.num + np.where(spread_on, boost, 0.0)
        den = self.den + spread_on
        score = np.where(fits, num / den, -np.inf)
        row = int(np.argmax(score))
        return row, float(score[row])


def repair_batch_conflicts(
    cluster,
    asks: list,
    results: list,
    algorithm_spread: bool = False,
    fail_on_contention: bool = False,
    lane_groups: Optional[list] = None,
    used_override=None,  # [pn, D] optimistic base usage (pipelined passes)
) -> list[bool]:
    """Host-side optimistic-conflict resolution for one batched pass.

    Every lane scored against the same snapshot ``used0``, so lanes can
    pile onto the same best nodes (true argmax removes the decorrelation
    the reference gets from per-worker shuffle sampling, stack.go:74-90;
    _decorrelate_lanes removes most of the correlation up front). Walk
    the lanes in order with a usage overlay: placements that no longer
    fit move to the lane's next overflow candidate, and when overflow
    runs out an exact NumPy re-score places them directly — only the
    *conflicted placement* is re-placed, never the whole eval. Kernel
    failures (row −1, e.g. a lane whose stripe ran dry) get the same
    re-score. A lane marked ``exact`` (``GroupAsk.exact``) is never moved
    to a runner-up scored on the shared snapshot: when a placement of its
    own no longer fits, its eval waits for every other lane of the pass
    and is then placed by the exact re-score on the usage that holds them
    all — what its own pass would find after their commit — and marked
    ``deferred`` (``PlacementResult.deferred``) for the caller to commit
    it after them, deferred evals one after the other in lane order. The
    plan applier's per-node AllocsFit re-check (plan_apply.go:638-689)
    remains the authority.

    Mutates each PlacementResult in place. Returns per-lane ``ok`` —
    False only when a placement is unplaceable under the batch overlay
    but WOULD fit without the other lanes' placements (true cross-eval
    contention): that eval should re-run individually against fresh
    state, where preemption and retries apply. Intrinsically infeasible
    placements (caps exhausted, cluster full even alone) stay −1 with
    ok=True — they'd fail individually too, and become blocked evals.

    ``lane_groups`` (optional, parallel to ``asks``) marks lanes that
    belong to one EVAL (a multi-task-group eval spans several lanes and
    the caller discards the whole eval when any lane fails): a contention
    failure releases the overlay reservations of EVERY processed lane in
    the group and skips its remaining lanes — sibling placements of a
    discarded plan must not stay reserved against later lanes.

    Writes the ``repair`` span. Its tags ``full`` and ``row``, and the
    counters ``nomad.worker.repair_rescores_full`` and ``_row``, count
    the exact re-scores: full passes (a walk's first re-score, which
    builds its ``_LaneRescore``, and each contention probe) and picks
    that reuse the walk's re-scorer.
    """
    rescores = {"full": 0, "row": 0}
    with _tracer.span("repair") as sp:
        ok_lanes = _repair_walks(
            cluster, asks, results, algorithm_spread, fail_on_contention,
            lane_groups, used_override, rescores,
        )
        if sp is not None:
            sp.tags.update(rescores)
    for kind, n in rescores.items():
        _metrics.incr(f"nomad.worker.repair_rescores_{kind}", n)
    return ok_lanes


def _repair_walks(
    cluster, asks, results, algorithm_spread, fail_on_contention,
    lane_groups, used_override, rescores,
) -> list[bool]:
    """The body of ``repair_batch_conflicts``; counts its exact
    re-scores into ``rescores``."""
    capacity = np.asarray(cluster.capacity)
    used0 = (
        np.asarray(cluster.used)
        if used_override is None
        else np.asarray(used_override)
    )
    used = used0.copy()
    ok_lanes: list[bool] = [True] * len(asks)
    # group id -> [(placed_on_node, ask), ...] commit journal for rollback
    group_commits: dict = {}
    failed_groups: set = set()
    # groups of ``exact`` lanes that lost a node to a lane ahead of them:
    # placed again, whole, once every other lane has been
    deferred_groups: set = set()

    def release(group, placed_on_node, ask) -> None:
        """Take back what this lane and the processed lanes of its eval
        reserved: the eval's placements are not (yet) the pass's."""
        for r, m in placed_on_node.items():
            used[r] -= m * ask
        for sib_placed, sib_ask in group_commits.pop(group, ()):
            for r, m in sib_placed.items():
                used[r] -= m * sib_ask

    def walk(lane_idx: int, group) -> None:
        a, res = asks[lane_idx], results[lane_idx]
        ok = True
        # within-lane placements per node (distinct_hosts, slot caps,
        # anti-affinity collisions all key off it)
        placed_on_node: dict[int, int] = {}
        blocks = a.blocks
        counts = blocks.counts0.copy() if blocks is not None else None
        rows = res.node_rows.tolist()
        overflow = list(
            zip(res.overflow_rows.tolist(), res.overflow_scores.tolist())
        )
        if res.deferred:
            # what the kernel chose, candidates included, was scored
            # without the other lanes: every placement is re-scored
            rows, overflow = [-1] * len(rows), []
        of_idx = 0
        dead = False  # lane-intrinsic infeasibility: stop re-scoring
        # the exact re-score, built at the walk's first and kept up to
        # date by every commit after it: a lane that re-scores k
        # placements pays one full pass and k − 1 picks
        rescorer: Optional[_LaneRescore] = None

        def commit(row: int) -> None:
            used[row] += a.ask
            placed_on_node[row] = placed_on_node.get(row, 0) + 1
            if blocks is not None:
                for b in range(blocks.num_blocks):
                    v = blocks.value_ids[b, row]
                    if v >= 0:
                        counts[b, v] += 1
            if rescorer is not None:
                rescorer.commit(row)

        def acceptable(row: int) -> bool:
            if row < 0:
                return False
            if not np.all(used[row] + a.ask <= capacity[row]):
                return False
            mine = placed_on_node.get(row, 0)
            if a.distinct_hosts and (a.job_counts[row] + mine) > 0:
                return False
            if a.slot_caps is not None and mine >= a.slot_caps[row]:
                return False
            if blocks is not None:
                for b in range(blocks.num_blocks):
                    if blocks.kinds[b] != BLOCK_DISTINCT_CAP:
                        continue
                    v = blocks.value_ids[b, row]
                    if v >= 0 and counts[b, v] >= blocks.caps[b, v]:
                        return False
            return True

        def rescore(i: int) -> str:
            """Exact re-place of placement ``i``. Returns 'placed',
            'contention' (fits alone, not under the overlay), or
            'intrinsic'."""
            nonlocal rescorer
            if rescorer is None:
                rescorer = _LaneRescore(
                    capacity, used, a, placed_on_node, counts,
                    algorithm_spread,
                )
                rescores["full"] += 1
            else:
                rescores["row"] += 1
            row, sc = rescorer.pick()
            if row >= 0:
                res.node_rows[i] = row
                res.scores[i] = sc
                commit(row)
                return "placed"
            # would it fit with only this lane's own placements applied?
            pm = rescorer.pm
            lane_used = used0 + pm[:, None] * a.ask[None, :]
            rescores["full"] += 1
            row, _sc = _rescore_pick(
                capacity, lane_used, a, pm, counts, algorithm_spread
            )
            return "contention" if row >= 0 else "intrinsic"

        for i, row in enumerate(rows):
            if row >= 0 and acceptable(row):
                commit(row)
                continue
            if dead:
                res.node_rows[i] = -1
                res.scores[i] = -np.inf
                continue
            if a.exact and row >= 0:
                # its best node went to a lane ahead of it: the eval
                # waits for the other lanes and is placed on what they
                # leave (below), not on a runner-up of this snapshot
                release(group, placed_on_node, a.ask)
                deferred_groups.add(group)
                return
            # conflicted or unplaced: advance through overflow candidates
            repl = -1
            while of_idx < len(overflow):
                cand, sc = overflow[of_idx]
                of_idx += 1
                if acceptable(cand):
                    repl = cand
                    res.node_rows[i] = cand
                    res.scores[i] = sc
                    commit(cand)
                    break
            if repl >= 0:
                continue
            outcome = rescore(i)
            if outcome == "contention" and not fail_on_contention:
                # this eval re-runs individually on fresh state — its plan
                # is NOT submitted, so its already-committed placements
                # must not stay reserved in the shared overlay (phantom
                # reservations would cascade later lanes into serial
                # fallbacks a fresh-state rerun would avoid). Release this
                # lane AND every processed sibling lane of the same eval.
                release(group, placed_on_node, a.ask)
                failed_groups.add(group)
                ok = False
                break
            if outcome in ("intrinsic", "contention"):
                # fail_on_contention (single-eval path): there is no
                # fresher state to retry against, so an unplaceable
                # placement becomes a recorded failure instead of a
                # shipped-overcommitted row the applier would bounce
                res.node_rows[i] = -1
                res.scores[i] = -np.inf
                dead = True
        if ok and lane_groups is not None:
            group_commits.setdefault(group, []).append(
                (placed_on_node, a.ask)
            )
        ok_lanes[lane_idx] = ok

    def visit(lane_idx: int, group) -> None:
        if group in failed_groups:
            # a sibling lane of this eval already hit contention: the
            # whole eval re-runs individually, so don't reserve anything
            ok_lanes[lane_idx] = False
        else:
            walk(lane_idx, group)

    groups = lane_groups if lane_groups is not None else range(len(asks))
    for lane_idx, group in enumerate(groups):
        if group not in deferred_groups:
            visit(lane_idx, group)
    # the deferred evals, every lane of each, on the usage that now holds
    # every other lane's placements and the deferred ones before them
    for lane_idx, group in enumerate(groups):
        if group in deferred_groups:
            results[lane_idx].deferred = True
            visit(lane_idx, group)
    return ok_lanes
