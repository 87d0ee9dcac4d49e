"""Drive a rehearsal run with the timed path broken underneath.

    python benchmark/tests/fault_run.py <fault> --workload <cell> --seed <n>

Skips the harness's look for a chip (``--rehearse``: toy fleet, any
backend) and drives the rest of a run exactly as ``run.py`` does, after
planting one fault in the program. ``test_faults.py`` sees ``correct`` come
out false for each; ``none`` plants nothing and must come out true.

- ``state_unchanged``: the commit reports success and writes nothing (a
  step that returns its state unchanged).
- ``half_left_out``: the second half of every plan's placements is dropped
  where the plan enters the queue (half of the batch left out; dropped from
  the kernel's result instead, the program's host repair puts them back).
- ``score_altered``: every served score is halved where it is produced.
- ``rows_altered``: every placement is moved one node row on from where the
  kernel put it (an answer altered where it is produced).
- ``sampled_selection``: the kernel is shown a random eighth of the nodes
  for each ask and places on the best of those, its scores honest (a
  cheaper selection that returns a feasible node, not the best one).

The fault is planted when the window's traffic starts, so set-up runs sound
(a fault in set-up ends the run with an error and no result line). There is
no exchange between chips to leave out: every cell runs on one.
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def plant(fault: str) -> None:
    import numpy as np

    from nomad_tpu.broker.plan_queue import PlanQueue
    from nomad_tpu.device.score import PlacementKernel
    from nomad_tpu.server.server import Server

    if fault == "none":
        return
    if fault == "state_unchanged":
        raft_apply = Server.raft_apply

        def dropped(self, mtype, payload=None):
            if mtype in (self._msg.PLAN_RESULT, self._msg.MERGED_PLAN_RESULT):
                return self.store.latest_index, None
            return raft_apply(self, mtype, payload)

        Server.raft_apply = dropped
        return
    if fault == "half_left_out":
        def halve(plan):
            for node_id in list(plan.node_allocation)[1::2]:
                del plan.node_allocation[node_id]

        enqueue, enqueue_merged = PlanQueue.enqueue, PlanQueue.enqueue_merged

        def one(self, plan, *args, **kwargs):
            halve(plan)
            return enqueue(self, plan, *args, **kwargs)

        def merged(self, mplan, *args, **kwargs):
            for plan in mplan.plans:
                halve(plan)
            return enqueue_merged(self, mplan, *args, **kwargs)

        PlanQueue.enqueue, PlanQueue.enqueue_merged = one, merged
        return
    place = PlacementKernel.place

    rng = np.random.default_rng(0)

    def broken(self, cluster, asks, **kwargs):
        if fault == "sampled_selection":
            eligible = [a.eligible for a in asks]
            for a in asks:
                a.eligible = a.eligible & (rng.random(a.eligible.shape) < 0.125)
            try:
                return place(self, cluster, asks, **kwargs)
            finally:
                for a, e in zip(asks, eligible):
                    a.eligible = e
        results = place(self, cluster, asks, **kwargs)
        for r in results:
            if r is None:
                continue
            if fault == "score_altered":
                r.scores = np.asarray(r.scores) * 0.5
            elif fault == "rows_altered":
                rows = np.asarray(r.node_rows)
                r.node_rows = np.where(
                    rows >= 0, (rows + 1) % cluster.num_nodes, rows
                )
        return results

    PlacementKernel.place = broken


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    from benchmark import run
    from benchmark.driver import Driver

    for name in ("run_open", "run_closed"):
        def planted(self, *args, _loop=getattr(Driver, name), **kwargs):
            plant(fault)
            return _loop(self, *args, **kwargs)

        setattr(Driver, name, planted)

    return run.main(argv + ["--seconds", "4", "--trace", "0", "--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
