"""DeviceStateCache — resident cluster tensors refreshed incrementally.

SURVEY.md §7 "latency floor": the device arrays are a *derived cache* of
the state store's node/alloc tables, refreshed by state-index watermark
(the ``SnapshotMinIndex`` analog, nomad/worker.go:536-549) — NOT rebuilt
per evaluation. The store's ChangeJournal (state/store.py) records which
node rows were touched; the cache patches exactly those rows.

Generational copy-on-write: a refresh builds new arrays (cheap — O(N·D)
numpy copies) and swaps the generation, so evals holding the previous
``ClusterTensors`` keep reading frozen state — the same MVCC discipline
the store itself uses.

Full rebuilds happen only when the journal can't cover the interval, a
node disappears or changes class/datacenter (representative-node
semantics would go stale), or the padded node bucket overflows.
"""

from __future__ import annotations

import threading
from dataclasses import replace

import numpy as np

from ..obs.trace import global_tracer
from ..structs.resources import node_comparable_capacity
from ..utils.metrics import global_metrics
from .flatten import ClusterTensors, flatten_cluster
from .preempt import carry_victim_cache


def _node_used(snap, node_id: str, dims: int) -> np.ndarray:
    vec = np.zeros(dims, dtype=np.float32)
    for a in snap.allocs_by_node(node_id):
        if not a.terminal_status():
            vec += a.comparable_resources().to_vector()
    return vec


class ScoreState:
    """One generation of the persisted device-resident score view.

    The score planes every placement kernel computes are pure functions
    of ``(capacity, used, ask)``; capacity is already device-resident
    (``_device_capacity_locked``) and the asks are per-pass, so the
    persisted half of the score state is ``used`` — the alloc-churn-hot
    tensor that the from-scratch path re-uploads whole every pass. A
    generation is immutable once built (jax buffers are, and the host
    mirror is a private copy): the double-buffered pipeline hands the
    previous generation to an in-flight pass while the next one is
    staged, and ``score_commit`` swaps staged → committed at the merge
    point. ``used_host`` is the exact bytes on device — the dirty-row
    diff and ``verify_score_view`` both compare against it bitwise."""

    __slots__ = ("used_dev", "used_host", "layout_gen", "gen")

    def __init__(self, used_dev, used_host, layout_gen: int, gen: int):
        self.used_dev = used_dev
        self.used_host = used_host
        self.layout_gen = layout_gen
        self.gen = gen


class DeviceStateCache:
    """One per server/harness; thread-safe. ``tensors(snap)`` returns a
    ClusterTensors at exactly ``snap.index`` whose ``used`` array is a
    private copy (schedulers overlay in-plan stops/preemptions onto it)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ct: ClusterTensors | None = None
        # instrumentation: test_device_cache asserts full_flattens stays 1
        # across eval storms; metrics surface these (nomad.worker.* analog)
        self.full_flattens = 0
        self.incremental_refreshes = 0
        self.hits = 0
        self.stale_builds = 0  # older-than-resident snapshots (transient)
        # device-resident capacity of the resident generation: capacity
        # changes only when a node is written, so a pass reads the
        # buffer of the pass before it. On one device a refresh that
        # changed a capacity row marks the buffer stale and the next
        # access uploads it whole. Under a mesh it is refreshed per
        # shard: dirty-REGION tracking (region ids are stable across
        # incremental refreshes; only a full reflatten may re-sort rows)
        # maps journal changes to the node-axis shards that must
        # re-upload; clean shards keep their existing device buffers.
        self._dev_capacity = None  # committed jax.Array | None
        self._dev_layout_gen = 0
        self._dev_capacity_stale = False
        self._dirty_regions: set[int] = set()
        self.shard_uploads = 0  # per-shard (partial) device refreshes
        self.full_uploads = 0  # whole-tensor device uploads
        # score-state persistence (NOMAD_TPU_INCREMENTAL): double-
        # buffered device-resident ``used`` generations. ``_score`` is
        # the committed generation; ``score_view`` stages the next one
        # (dirty rows diffed bitwise against the newest mirror, clean
        # shards keep their buffers) and ``score_commit`` swaps it in
        # from the worker's commit path. Dirty detection is an exact
        # host compare rather than journal bookkeeping: overlay
        # overrides and partially-landed commits self-heal on the next
        # pass because ANY divergence from the mirror re-uploads.
        self._score: ScoreState | None = None  # committed generation
        self._score_staged: ScoreState | None = None
        self.score_rows_rescored = 0  # rows re-uploaded (score inputs changed)
        self.score_rows_reused = 0  # rows served from the resident buffer
        self.score_patch_uploads = 0  # partial (dirty-slice) refreshes
        self.score_full_rebuilds = 0  # whole-tensor score-state uploads
        self.score_swaps = 0  # staged → committed generation swaps
        self.pipeline_overlap_ms = 0.0  # commit time hidden behind passes

    # -- public -----------------------------------------------------------
    def tensors(self, snap) -> ClusterTensors:
        from ..utils.backend import get_mesh, incremental_enabled

        with global_tracer.span("flatten") as sp, self._lock:
            flattens = self.full_flattens
            ct = self._refresh_locked(snap)
            if sp is not None:
                sp.tags["full"] = self.full_flattens > flattens
            out = replace(ct, used=ct.used.copy())
            out.device_capacity = self._device_capacity_locked(
                ct, get_mesh()
            )
            if incremental_enabled():
                # the incremental seam the kernels read (device/score.py
                # used_device): present ⇒ the pass's ``used`` upload may
                # be served from the persisted score state. Off-mode
                # tensors carry None and take the from-scratch path
                # untouched — the Python-level gate the jaxpr-identity
                # pin depends on.
                out.score_cache = self
            return out

    def resident(self):
        """The resident generation as it stands, or ``None``: its row
        table, for a reader that has node ids and needs rows."""
        with self._lock:
            return self._ct

    def invalidate(self) -> None:
        with self._lock:
            self._ct = None
            self._dev_capacity = None
            self._dev_capacity_stale = False
            self._dirty_regions.clear()
            self._score = None
            self._score_staged = None

    def device_counters(self) -> dict:
        with self._lock:
            state = self._score_staged or self._score
            return {
                "shard_uploads": self.shard_uploads,
                "full_uploads": self.full_uploads,
                "dirty_regions": len(self._dirty_regions),
                "score_rows_rescored": self.score_rows_rescored,
                "score_rows_reused": self.score_rows_reused,
                "score_patch_uploads": self.score_patch_uploads,
                "score_full_rebuilds": self.score_full_rebuilds,
                "score_swaps": self.score_swaps,
                "score_gen": 0 if state is None else state.gen,
                "pipeline_overlap_ms": round(self.pipeline_overlap_ms, 3),
            }

    def note_overlap(self, ms: float) -> None:
        """Worker-reported pipeline overlap: wall-clock the commit
        thread ran underneath the NEXT pass's prepare + device work."""
        with self._lock:
            self.pipeline_overlap_ms += max(0.0, float(ms))

    def verify_device_view(self) -> list[str] | None:
        """Invariant law 12 (shard_consistency) probe: re-gather every
        device-resident capacity shard to host and compare *bitwise*
        against the resident generation's store-derived capacity.
        Returns None when no device view is materialized (never
        accessed, or a mesh that does not divide the bucket); else a
        list of mismatch details (empty == consistent). Pending dirty
        regions are fine — they re-upload on the next access — but a
        shard that claims to be clean must match."""
        with self._lock:
            ct = self._ct
            arr = self._dev_capacity
            if ct is None or arr is None:
                return None
            if self._dirty_regions or self._dev_capacity_stale:
                # flush pending refreshes so the comparison sees what
                # the next eval would read
                from ..utils.backend import get_mesh

                arr = self._device_capacity_locked(ct, get_mesh())
                if arr is None:
                    return None
            problems: list[str] = []
            ref = np.asarray(ct.capacity)
            for sh in arr.addressable_shards:
                host = np.asarray(sh.data)
                want = ref[sh.index]
                if host.shape != want.shape or not np.array_equal(
                    host, want
                ):
                    start = sh.index[0].start or 0
                    problems.append(
                        f"rows[{start}:{start + host.shape[0]}] on "
                        f"{sh.device} diverge from store-derived capacity"
                    )
            return problems

    # -- score-state persistence (incremental rescoring) -------------------
    def score_view(self, ct, used0: np.ndarray, cfg=None):
        """Device-resident ``used`` for one scoring pass, bitwise equal
        to ``used0`` — or None when the incremental path is inactive
        (callers ``shard_put`` from scratch, exactly the off-mode path).

        Stages the next score-state generation: rows whose bytes differ
        from the newest mirror re-upload (per dirty shard under a mesh,
        whole-tensor when degenerate or chaos-dropped); clean shards
        keep their existing device buffers and their per-shard top-k
        heads are recomputed from resident data — the hierarchical
        merge in device/score.py (``_topk_nodes``) runs unchanged, so
        the traced program is identical to from-scratch and only the
        host→device traffic scales with the dirt. The staged generation
        becomes committed at ``score_commit`` (worker commit path)."""
        from ..utils.backend import get_mesh, incremental_enabled

        if not incremental_enabled():
            return None
        if cfg is None:
            cfg = get_mesh()
        used0 = np.asarray(used0, dtype=np.float32)
        layout_gen = getattr(ct, "layout_gen", 0)
        with self._lock:
            base = self._score_staged or self._score
            n_rows = int(used0.shape[0])
            if (
                base is None
                or base.layout_gen != layout_gen
                or base.used_host.shape != used0.shape
            ):
                # first access, layout change (full reflatten re-sorts
                # rows: every cached partial is row-misaligned), or a
                # shape flip — rebuild the whole score state
                return self._score_rebuild_locked(used0, layout_gen, cfg)
            dirty = np.flatnonzero(
                np.any(base.used_host != used0, axis=1)
            )
            if dirty.size == 0:
                self.score_rows_reused += n_rows
                self._score_staged = ScoreState(
                    base.used_dev, base.used_host, layout_gen, base.gen
                )
                return base.used_dev
            from ..chaos.plane import chaos_site

            if chaos_site("cache.score_refresh_drop") == "drop":
                # a dropped dirty-slice refresh must never serve stale
                # score inputs: recovery is a whole-tensor re-upload on
                # this access (mesh.shard_refresh_drop discipline)
                return self._score_rebuild_locked(used0, layout_gen, cfg)
            self.score_rows_rescored += int(dirty.size)
            self.score_rows_reused += n_rows - int(dirty.size)
            dev = self._score_patch_locked(base, used0, dirty, cfg)
            self._score_staged = ScoreState(
                dev, used0.copy(), layout_gen, base.gen + 1
            )
            self.score_patch_uploads += 1
            return dev

    def _score_rebuild_locked(self, used0, layout_gen: int, cfg):
        from ..utils.backend import shard_put

        # upload from a PRIVATE copy: on the CPU backend device_put may
        # alias the host numpy buffer zero-copy, and a buffer aliasing
        # the caller's live ``used`` array would mutate under alloc
        # churn — the generation must hold the exact bytes it was built
        # from. The copy doubles as the mirror.
        host = used0.copy()
        dev = shard_put(host, ("nodes",), cfg)
        base = self._score_staged or self._score
        gen = 1 if base is None else base.gen + 1
        self._score_staged = ScoreState(
            dev, host, layout_gen, gen
        )
        self.score_full_rebuilds += 1
        self.score_rows_rescored += int(used0.shape[0])
        return dev

    def _score_patch_locked(self, base: ScoreState, used0, dirty, cfg):
        """New device buffer for ``used0``: under a mesh whose node axis
        divides the rows, re-upload only the shards containing dirty
        rows and reassemble around the clean shards' existing buffers
        (the capacity protocol); degenerate single-device falls back to
        a whole-tensor upload — there is no partial-placement primitive
        for an unsharded buffer, and the reuse win there is the
        zero-dirty case above."""
        from ..utils.backend import shard_put

        mp = cfg.n_node_shards
        n_rows = int(used0.shape[0])
        arr = base.used_dev
        if (
            mp <= 1
            or n_rows % mp != 0
            or getattr(arr, "sharding", None) is None
        ):
            # .copy() for the same aliasing reason as the shard path
            return shard_put(used0.copy(), ("nodes",), cfg)
        import jax

        seg = n_rows // mp
        dirty_shards = {int(r) // seg for r in dirty}
        bufs = []
        for sh in arr.addressable_shards:
            start = sh.index[0].start or 0
            if start // seg in dirty_shards:
                # .copy(): CPU device_put may alias host memory (see
                # _score_rebuild_locked) — a dirty-slice buffer must
                # not track the caller's live ``used`` rows
                bufs.append(
                    jax.device_put(
                        used0[start : start + seg].copy(), sh.device
                    )
                )
            else:
                bufs.append(sh.data)
        return jax.make_array_from_single_device_arrays(
            used0.shape, arr.sharding, bufs
        )

    def score_commit(self) -> None:
        """Swap the staged score-state generation in as committed — the
        double buffer's merge point, called from the worker's commit
        path. The ONE ``jax.block_until_ready`` fence of the pipeline
        lives here: patch uploads dispatch async and overlap the
        previous pass's verify/commit; by swap time they must be real
        buffers, never in-flight transfers a holder could stall on."""
        from ..utils.backend import transfer_fence

        with self._lock:
            staged = self._score_staged
            if staged is None:
                return
            self._score_staged = None
            if self._score is not None and staged.gen == self._score.gen:
                return  # zero-dirty pass: same generation, no swap
            self._score = staged
            self.score_swaps += 1
        transfer_fence(staged.used_dev)

    def score_abort(self) -> None:
        """Drop the staged generation (a pass that died before commit);
        the next pass diffs against the committed mirror and re-uploads
        whatever the aborted pass had staged — correctness never
        depends on an abort being observed."""
        with self._lock:
            self._score_staged = None

    def verify_score_view(self) -> list[str] | None:
        """Invariant law 12 (shard_consistency), score half: re-gather
        every device-resident ``used`` shard of the newest score-state
        generation and compare *bitwise* against its host mirror — the
        ``verify_device_view`` analog for the incremental path. Returns
        None when no score state is materialized (incremental off, or
        never accessed); else a list of mismatch details (empty ==
        consistent)."""
        with self._lock:
            state = self._score_staged or self._score
            if state is None:
                return None
            problems: list[str] = []
            ref = state.used_host
            for sh in state.used_dev.addressable_shards:
                host = np.asarray(sh.data)
                want = ref[sh.index]
                if host.shape != want.shape or (
                    host.tobytes() != want.tobytes()
                ):
                    start = sh.index[0].start or 0
                    problems.append(
                        f"score rows[{start}:{start + host.shape[0]}] on "
                        f"{sh.device} diverge bitwise from the gen-"
                        f"{state.gen} mirror"
                    )
            return problems

    # -- device view -------------------------------------------------------
    def _capacity_upload_locked(self, ct: ClusterTensors, cfg):
        """Whole-tensor upload of the resident generation's capacity."""
        from ..utils.backend import shard_put

        self._dev_capacity = shard_put(ct.capacity, ("nodes",), cfg)
        self._dev_layout_gen = ct.layout_gen
        self._dev_capacity_stale = False
        self._dirty_regions.clear()
        self.full_uploads += 1
        global_metrics.incr("nomad.device_cache.capacity_uploads")
        return self._dev_capacity

    def _device_capacity_locked(self, ct: ClusterTensors, cfg):
        """Device-resident capacity for the resident generation: the
        buffer of the last access unless a node write changed a row
        since. On one device that is a whole-tensor upload; under a
        mesh steady-state node updates re-upload ONLY the shards whose
        regions went dirty. A first access, a layout change (full
        reflatten) or a chaos-dropped shard refresh upload the whole
        tensor. Returns None when the mesh doesn't divide the bucket
        (callers shard on the fly)."""
        import jax

        from ..chaos.plane import chaos_site

        mp = cfg.n_node_shards
        pn = ct.padded_n
        if mp > 1 and (pn % mp != 0 or ct.region_ids is None):
            return None
        if (
            self._dev_capacity is None
            or self._dev_layout_gen != ct.layout_gen
            or self._dev_capacity.shape != ct.capacity.shape
            or (mp <= 1 and self._dev_capacity_stale)
        ):
            return self._capacity_upload_locked(ct, cfg)
        if mp <= 1 or not self._dirty_regions:
            return self._dev_capacity
        if chaos_site("mesh.shard_refresh_drop") == "drop":
            # a dropped shard upload must never serve stale capacity:
            # recovery is a whole-tensor re-upload on this access
            return self._capacity_upload_locked(ct, cfg)
        seg = pn // mp
        rows = np.flatnonzero(
            np.isin(ct.region_ids, list(self._dirty_regions))
        )
        dirty_shards = {int(r) // seg for r in rows}
        arr = self._dev_capacity
        bufs = []
        for sh in arr.addressable_shards:
            start = sh.index[0].start or 0
            if start // seg in dirty_shards:
                bufs.append(
                    jax.device_put(
                        ct.capacity[start : start + seg], sh.device
                    )
                )
            else:
                bufs.append(sh.data)
        self._dev_capacity = jax.make_array_from_single_device_arrays(
            ct.capacity.shape, arr.sharding, bufs
        )
        self._dev_capacity_stale = False
        self._dirty_regions.clear()
        self.shard_uploads += 1
        return self._dev_capacity

    # -- refresh machinery -------------------------------------------------
    def _rebuild_locked(self, snap) -> ClusterTensors:
        self.full_flattens += 1
        global_metrics.incr("nomad.device_cache.full_flattens")
        self._ct = replace(
            flatten_cluster(snap), layout_gen=self.full_flattens
        )
        return self._ct

    def _refresh_locked(self, snap) -> ClusterTensors:
        ct = self._ct
        if ct is not None and snap.index < ct.index:
            # A worker holding an older snapshot than the resident
            # generation: serve the RESIDENT build. Its usage is newer
            # than the snapshot — strictly MORE accurate for optimistic
            # placement (it already includes commits the snapshot
            # missed); the plan applier re-checks against live state
            # either way. The alternative (a transient rebuild from the
            # old snapshot) is quadratically worse under pipelined
            # workers: it is a full reflatten per pass, its row order
            # differs from the resident layout (layout_gen 0) so the
            # shared optimistic overlay gets dropped, and its usage
            # EXCLUDES the other workers' in-flight commits — measured
            # as >90% applier rejection of whole passes.
            self.stale_builds += 1
            return ct
        if ct is None:
            return self._rebuild_locked(snap)
        if snap.index == ct.index:
            self.hits += 1
            return ct
        journal = getattr(snap, "journal", None)
        if journal is None:
            return self._rebuild_locked(snap)
        changes = journal.since(ct.index, snap.index)
        if changes is None:
            return self._rebuild_locked(snap)
        node_keys = changes.get("nodes", set())
        alloc_nodes = changes.get("node_allocs", set())
        if not node_keys and not alloc_nodes:
            # index advanced without touching schedulable state
            self._ct = replace(ct, index=snap.index)
            self.hits += 1
            return self._ct

        new_nodes: list = []
        for nid in node_keys:
            node = snap.node_by_id(nid)
            if node is None:
                return self._rebuild_locked(snap)  # node removed
            row = ct.node_row.get(nid)
            if row is None:
                new_nodes.append(node)
                continue
            # class/dc changes invalidate representative-node memoization.
            # device_class folds into computed_class (structs/node.py), so
            # an accelerator-class flip always lands here and forces the
            # rebuild — the cache can never serve a stale class column.
            cid = ct.class_vocab.get(node.computed_class or "")
            if cid is None or cid != ct.class_ids[row]:
                return self._rebuild_locked(snap)
            did = ct.dc_vocab.get(node.datacenter)
            if did is None or did != ct.dc_ids[row]:
                return self._rebuild_locked(snap)
            # belt-and-braces for hand-mutated nodes that skipped
            # compute_class(): a raw device_class change alone still
            # invalidates the heterogeneity column
            dcid = ct.device_class_vocab.get(
                getattr(node, "device_class", "")
            )
            dcol = ct.device_class_ids
            if dcid is None or (
                dcol is not None and dcid != dcol[row]
            ):
                return self._rebuild_locked(snap)
        if ct.num_nodes + len(new_nodes) > ct.padded_n:
            return self._rebuild_locked(snap)  # bucket overflow

        self.incremental_refreshes += 1
        dims = ct.capacity.shape[1]
        capacity = ct.capacity.copy()
        used = ct.used.copy()
        ready = ct.ready.copy()
        dc_ids = ct.dc_ids.copy()
        class_ids = ct.class_ids.copy()
        region_ids = (
            ct.region_ids.copy() if ct.region_ids is not None else None
        )
        region_vocab = dict(ct.region_vocab)
        node_ids = list(ct.node_ids)
        nodes = list(ct.nodes)
        node_row = dict(ct.node_row)
        dc_vocab = dict(ct.dc_vocab)
        class_vocab = dict(ct.class_vocab)
        class_rep = list(ct.class_rep)
        device_class_ids, _ = ct.device_class_column()
        device_class_ids = device_class_ids.copy()
        device_class_vocab = dict(ct.device_class_vocab)
        num_nodes = ct.num_nodes
        # attribute columns: alloc churn never touches them, and a node
        # write (a drain, a change of eligibility or status, a new
        # fingerprint) touches its own rows only — filled in below, once
        # the rows hold their new nodes
        attr_cache = dict(ct.attr_cache)

        for node in new_nodes:
            row = num_nodes
            num_nodes += 1
            node_row[node.id] = row
            node_ids.append(node.id)
            nodes.append(node)
            if not node.computed_class:
                node.compute_class()
            cid = class_vocab.setdefault(node.computed_class, len(class_vocab))
            if cid == len(class_rep):
                class_rep.append(row)
            class_ids[row] = cid
            dc_ids[row] = dc_vocab.setdefault(node.datacenter, len(dc_vocab))
            device_class_ids[row] = device_class_vocab.setdefault(
                getattr(node, "device_class", ""), len(device_class_vocab)
            )
            capacity[row] = node_comparable_capacity(node).to_vector()
            self._dev_capacity_stale = True
            ready[row] = node.ready()
            used[row] = _node_used(snap, node.id, dims)
            if region_ids is not None:
                # appended rows break strict region-major contiguity
                # until the next full reflatten re-sorts; sharding
                # correctness (hierarchical top-k) never depends on
                # contiguity — only shard-locality of the prefilters does
                from .flatten import _region_name, region_key

                region_ids[row] = region_vocab.setdefault(
                    _region_name(region_key(node)), len(region_vocab)
                )
                self._dirty_regions.add(int(region_ids[row]))

        for nid in node_keys:
            row = node_row[nid]
            if row >= ct.num_nodes:
                continue  # appended above
            node = snap.node_by_id(nid)
            nodes[row] = node
            vec = node_comparable_capacity(node).to_vector()
            if (capacity[row] != vec).any():
                # a drain or a change of eligibility writes the node and
                # leaves its capacity: the device's copy still holds
                self._dev_capacity_stale = True
            capacity[row] = vec
            ready[row] = node.ready()
            used[row] = _node_used(snap, nid, dims)
            if region_ids is not None:
                self._dirty_regions.add(int(region_ids[row]))

        if node_keys:
            global_metrics.incr(
                "nomad.device_cache.node_rows_patched", len(node_keys)
            )
            rows = [node_row[nid] for nid in node_keys]
            for attr, (ids, vocab) in attr_cache.items():
                # copies: the generation before this one keeps its own
                with global_tracer.span(
                    "attr_column", tags={"attr": attr, "nodes": len(rows)}
                ):
                    ids, vocab = ids.copy(), dict(vocab)
                    for row in rows:
                        v = nodes[row].lookup_attribute(attr)
                        # append-only, as ``attr_column`` fills it
                        ids[row] = (
                            -1 if v is None
                            else vocab.setdefault(str(v), len(vocab))
                        )
                    attr_cache[attr] = (ids, vocab)

        for nid in alloc_nodes:
            if nid in node_keys:
                continue  # already recomputed
            row = node_row.get(nid)
            if row is None:
                continue  # alloc on an unknown node — nothing resident
            used[row] = _node_used(snap, nid, dims)

        # the lazily filled per-row tables follow the rows: what was
        # learned about an untouched node stays, a touched one is looked
        # at again by whoever needs it next
        touched = sorted(
            {node_row[nid] for nid in alloc_nodes | node_keys
             if nid in node_row}
        )
        device_caps = {}
        for key, table in ct.device_caps.items():
            table = table.copy()
            table[touched] = np.nan
            device_caps[key] = table
        victim_cache = carry_victim_cache(ct.victim_cache, touched)
        self._ct = ClusterTensors(
            device_caps=device_caps,
            victim_cache=victim_cache,
            node_ids=node_ids,
            index=snap.index,
            num_nodes=num_nodes,
            capacity=capacity,
            used=used,
            ready=ready,
            dc_ids=dc_ids,
            class_ids=class_ids,
            dc_vocab=dc_vocab,
            class_vocab=class_vocab,
            class_rep=class_rep,
            node_row=node_row,
            nodes=nodes,
            attr_cache=attr_cache,
            device_class_ids=device_class_ids,
            device_class_vocab=device_class_vocab,
            region_ids=region_ids,
            region_vocab=region_vocab,
            # incremental refresh never reorders existing rows (new nodes
            # append) — row-indexed overlays stay valid
            layout_gen=ct.layout_gen,
        )
        return self._ct
