"""The lanes the check compares: every lane drawn from the seed and the
same seeded sample of the pool's lanes, for a given seed and count of
calls, whatever else changes in the harness."""

import pytest

from bench import traffic
from bench.drivers.closed_batch import check_lanes
from cells import SEED

CALLS = 40
LIM = {"sample": 32, "largest": 8}
# the pool's lanes picked in 40 calls, as (call, index)
POOL_PICKS = {
    SEED: [(0, 10), (0, 13), (1, 5), (4, 2), (5, 6), (5, 12), (7, 11),
           (10, 1), (10, 7), (11, 3), (13, 4), (13, 12), (14, 15), (16, 5),
           (18, 12), (19, 1), (20, 9), (21, 0), (21, 14), (22, 6), (22, 9),
           (24, 2), (26, 4), (27, 3), (27, 4), (31, 7), (32, 2), (32, 13),
           (35, 2), (38, 7), (38, 9), (39, 12)],
    3141592653: [(1, 0), (1, 5), (1, 8), (2, 1), (2, 12), (3, 1), (4, 9),
                 (5, 6), (5, 9), (6, 2), (7, 2), (8, 2), (9, 1), (10, 1),
                 (10, 7), (10, 10), (13, 0), (13, 5), (16, 8), (17, 3),
                 (18, 0), (19, 0), (19, 12), (21, 0), (22, 1), (25, 1),
                 (25, 6), (26, 8), (28, 3), (29, 4), (33, 5), (38, 11)],
}


@pytest.mark.parametrize("seed", sorted(POOL_PICKS))
def test_check_picks_the_same_lanes(harness, seed):
    cell = harness.load_cell("plan-nofe.ragged")
    families = traffic.planning_calls(seed, cell["config_data"],
                                      cell["traffic"])
    calls = [(fam, None, new) for (fam, new), _ in zip(families, range(CALLS))]
    picks = check_lanes(calls, seed, LIM)
    fresh = [(c, k) for c, (_, _, new) in enumerate(calls)
             for k, is_new in enumerate(new) if is_new]
    assert [(c, k) for _, c, k in picks] == fresh + POOL_PICKS[seed]
    for lane, c, k in picks:
        assert lane is calls[c][0][k]
