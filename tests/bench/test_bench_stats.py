"""The whole-window arithmetic, on hand-worked cases."""
import pytest

from bench import stats
from bench.traffic import Req


def req(tenant, due, times, picked=None):
    r = Req(rid=0, tenant=tenant, due=due, prompt_len=1, out_len=1)
    r.token_times = list(times)
    r.picked = picked
    return r


def test_percentile_is_exact():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile([], 95) is None


def test_ttft_counts_an_unfinished_request_at_its_age():
    rs = [req(0, 10.0, [10.5, 10.6]),        # 0.5
          req(0, 11.0, []),                  # none by the close: 20 - 11
          req(0, 12.0, [21.0]),              # first token after the close
          req(0, 9.0, [9.1]),                # due before the window
          req(1, 13.0, [13.2])]              # another tenant
    got = stats.ttft_samples(rs, 10.0, 20.0, {0})
    assert sorted(got) == pytest.approx([0.5, 8.0, 9.0])


def test_queue_wait_uses_the_pick_and_the_age():
    rs = [req(0, 10.0, [], picked=10.25), req(0, 15.0, [], picked=None)]
    assert sorted(stats.queue_wait_samples(rs, 10.0, 20.0, {0})) == \
        pytest.approx([0.25, 5.0])


def test_gaps_are_cut_at_the_window_edges():
    r = req(0, 0.0, [9.0, 9.5, 10.5, 12.0, 19.5, 20.5])
    # the gap ending at 9.5 is before; the one ending at 20.5 after
    assert stats.itl_samples([r], 10.0, 20.0) == pytest.approx(
        [1.0, 1.5, 7.5])
    assert stats.tokens_in_window([r], 10.0, 20.0) == 3


def test_max_min_fair_hand_worked():
    # capacity 10, demands 2, 4, 10: 2 is satisfied, then 4 each
    a = stats.max_min_fair(10, {0: 2, 1: 4, 2: 10})
    assert a == pytest.approx({0: 2, 1: 4, 2: 4})
    # weights 1:2 over two greedy tenants
    a = stats.max_min_fair(9, {0: 100, 1: 100}, {0: 1, 1: 2})
    assert a == pytest.approx({0: 3, 1: 6})
    # a light tenant beside two weighted greedy ones
    a = stats.max_min_fair(12, {0: 3, 1: 50, 2: 50}, {0: 1, 1: 1, 2: 2})
    assert a == pytest.approx({0: 3, 1: 3, 2: 6})


def test_fair_jain():
    w = {0: 1, 1: 1, 2: 2}
    # served exactly the max-min shares: perfectly fair
    assert stats.fair_jain({0: 3, 1: 3, 2: 6}, {0: 3, 1: 50, 2: 50}, w) \
        == pytest.approx(1.0)
    # the weight-2 tenant got only the weight-1 share: x = 1, 1.5, 0.75
    x = [1.0, 4.5 / 3, 4.5 / 6]
    want = sum(x) ** 2 / (3 * sum(v * v for v in x))
    assert stats.fair_jain({0: 3, 1: 4.5, 2: 4.5}, {0: 3, 1: 50, 2: 50},
                           w) == pytest.approx(want)
    assert stats.jain_index([1.0, 0.0]) == pytest.approx(0.5)
    assert stats.jain_index([]) == 1.0
