"""BlockedEvals — evals that failed placement wait here for capacity.

Reference: nomad/blocked_evals.go (:33-96). One blocked eval per job; a
capacity change (node registered/updated, alloc stopped) unblocks the
evals whose class eligibility doesn't rule the change out, re-enqueuing
them into the EvalBroker. Evals that escaped computed-class filtering
unblock on any change.
"""

from __future__ import annotations

import threading
from typing import Optional

from ..structs import Evaluation
from ..structs.evaluation import EVAL_STATUS_PENDING, TRIGGER_QUEUED_ALLOCS


class BlockedEvals:
    def __init__(self, broker=None):
        self._lock = threading.Lock()
        self.broker = broker
        self.enabled = False
        # job key → blocked eval (one per job, blocked_evals.go:33)
        self._captured: dict[tuple[str, str], Evaluation] = {}
        # eval id → job key
        self._by_id: dict[str, tuple[str, str]] = {}
        # state index of the last capacity change — an eval whose snapshot
        # predates it missed an unblock and is released immediately
        # (blocked_evals.go missedUnblock / unblockIndexes)
        self._last_unblock_index = 0
        self.stats = {"total_blocked": 0, "total_escaped": 0, "total_unblocked": 0}

    def captured(self) -> list:
        """Snapshot of currently-parked blocked evals (bench/ops
        accounting: every unplaced alloc must be attributable)."""
        with self._lock:
            return list(self._captured.values())

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self.enabled = enabled
            if not enabled:
                self._captured.clear()
                self._by_id.clear()

    def block(self, ev: Evaluation) -> None:
        with self._lock:
            if not self.enabled:
                return
            if ev.snapshot_index and ev.snapshot_index < self._last_unblock_index:
                # capacity changed after the scheduler's snapshot: the
                # unblock already happened, re-run immediately
                self.stats["total_unblocked"] += 1
                if self.broker is not None:
                    ev.status = EVAL_STATUS_PENDING
                    self.broker.enqueue(ev)
                return
            key = (ev.namespace, ev.job_id)
            old = self._captured.get(key)
            if old is not None and old.modify_index > ev.modify_index:
                return  # keep the newer one
            if old is not None:
                self._by_id.pop(old.id, None)
            self._captured[key] = ev
            self._by_id[ev.id] = key
            self.stats["total_blocked"] += 1
            if ev.escaped_computed_class:
                self.stats["total_escaped"] += 1

    def untrack(self, namespace: str, job_id: str) -> None:
        """Job deregistered/updated — its blocked eval is stale."""
        with self._lock:
            ev = self._captured.pop((namespace, job_id), None)
            if ev is not None:
                self._by_id.pop(ev.id, None)

    def unblock(
        self, computed_class: str = "", quota: str = "", index: int = 0
    ) -> list[Evaluation]:
        """Capacity changed (for nodes of ``computed_class``, or any when
        empty): release matching evals back to the broker. ``index`` is the
        state index of the change, recorded so in-flight evals that block
        afterwards know they missed it."""
        with self._lock:
            if not self.enabled:
                return []
            self._last_unblock_index = max(self._last_unblock_index, index)
            release: list[Evaluation] = []
            keep: dict[tuple[str, str], Evaluation] = {}
            for key, ev in self._captured.items():
                eligible = (
                    not computed_class
                    or ev.escaped_computed_class
                    or ev.class_eligibility.get(computed_class, True)
                )
                if eligible:
                    release.append(ev)
                    self._by_id.pop(ev.id, None)
                else:
                    keep[key] = ev
            self._captured = keep
            self.stats["total_unblocked"] += len(release)
        for ev in release:
            ev.status = EVAL_STATUS_PENDING
            ev.triggered_by = TRIGGER_QUEUED_ALLOCS
        if self.broker is not None and release:
            self.broker.enqueue_all(release)
        return release

    def blocked_count(self) -> int:
        with self._lock:
            return len(self._captured)

    def get_blocked(self, namespace: str, job_id: str) -> Optional[Evaluation]:
        with self._lock:
            return self._captured.get((namespace, job_id))
