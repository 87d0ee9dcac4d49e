"""``node_loss/judge.py`` ``failed_on_a_dying_rack``: a node eval that ran
out of plan attempts is excused only where its own node went down in a
failure of which a node went down in the eval's life, whatever the job's
type; every other failed eval is unexplained."""

import numpy as np
import pytest

from benchmark.node_loss import judge


class _Failure:
    def __init__(self, down_index: dict):
        self.down_index = down_index
        self.ready_index: dict = {}


# a rack of nodes 4 and 5, down at indices 3 and 5; another of node 8, at 20
DOWN = judge.Down(10, [_Failure({4: 3, 5: 5}), _Failure({8: 20})])
SPECS = {0: {"type": "service"}, 1: {"type": "batch"}}


@pytest.mark.parametrize("job, node, create, modify, max_plans, kind", [
    # made by node 4's down commit, failed once node 5 had gone down
    (0, 4, 4, 6, True, "service"),
    (1, 4, 4, 6, True, "batch"),
    # its own node never went down, though the rack did in its life
    (0, 2, 4, 6, True, "unexplained"),
    # no node went down in its life
    (0, 4, 6, 9, True, "unexplained"),
    # a node of another failure went down in its life, none of its own
    (0, 4, 10, 25, True, "unexplained"),
    # failed otherwise than by running out of attempts
    (0, 4, 4, 6, False, "unexplained"),
    # not a node eval
    (0, -1, 4, 6, True, "unexplained"),
])
def test_a_failed_eval_is_excused_only_on_its_own_dying_rack(
        job, node, create, modify, max_plans, kind):
    # the failed eval, beside one that completed on the same node
    ev = {"failed": np.array([True, False]),
          "max_plans": np.array([max_plans, False]),
          "job": np.array([job, job]), "node": np.array([node, node]),
          "create": np.array([create, create]),
          "modify": np.array([modify, modify])}
    want = {"service": 0, "batch": 0, "unexplained": 0}
    want[kind] = 1
    assert judge.failed_on_a_dying_rack(SPECS, ev, DOWN) == want
