"""Seeds change the order of the work and the values of single-source
lanes, not the amount of work."""

from bench import traffic

SEEDS = (3, 2**31 + 5, 2**33 + 7)


CFG = {"sources": [1, 3], "processors": [1, 20], "G": [0.5, 0.7],
       "R": [2.0, 4.0], "A": [1.1, 3.0], "J": [100.0, 500.0],
       "lanes_per_call": 16}
MIX = {"fresh_sources": 1, "pool_seed": 1902, "pool_calls": 3}


def _sizes(fam):
    return sorted((len(g), len(a)) for g, _, a, _ in fam)


def test_pool_families_pad_to_one_shape():
    # every family holds N = 3 and M = 20, so each pads to 3 x 20
    for fam in traffic.planning_pool(CFG, MIX):
        assert max(len(lane[0]) for lane in fam) == 3
        assert max(len(lane[2]) for lane in fam) == 20


def test_planning_calls_draw_only_single_source_lanes_from_the_seed():
    pool = traffic.planning_pool(CFG, MIX)
    runs = []
    for s in SEEDS:
        calls = traffic.planning_calls(s, CFG, MIX)
        got = [next(calls) for _ in range(2 * len(pool))]
        for k, (fam, fresh) in enumerate(got):
            want = pool[k % len(pool)]
            assert _sizes(fam) == _sizes(want)          # the same work
            kept = sorted(lane[3] for lane, new in zip(fam, fresh) if not new)
            assert kept == sorted(lane[3] for lane in want if len(lane[0]) > 1)
            assert fresh == [len(lane[0]) == 1 for lane in fam]
        runs.append(got)
        # the same seed draws the same calls
        again = next(traffic.planning_calls(s, CFG, MIX))[0]
        assert [lane[3] for lane in again] == [lane[3] for lane in got[0][0]]
    # other seeds: other lane orders and other single-source values
    js = [sorted(lane[3] for lane, new in zip(*got[0]) if new) for got in runs]
    assert js[0] != js[1] != js[2]
    orders = [[lane[3] for lane in got[0][0]] for got in runs]
    assert orders[0] != orders[1]
