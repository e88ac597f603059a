"""The seeded schedules repeat exactly, and every seed gets the same work."""
from collections import Counter

import numpy as np
import pytest

import _paths  # noqa: F401
import schedules


def _open(seed):
    return schedules.open_loop(seed, seconds=51.0, rate_per_s=3.2,
                               functions=16, zipf_s=1.0)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 5])
def test_open_loop_repeats_exactly(seed):
    assert _open(seed) == _open(seed)


def test_open_loop_seeds_share_counts_and_gaps_in_another_order():
    a, b = _open(11), _open(2**33 + 11)
    assert [x.t for x in a] != [x.t for x in b]
    assert Counter(x.function for x in a) == Counter(x.function for x in b)
    gaps = lambda s: sorted(np.round(np.diff([x.t for x in s] + [51.0]), 9))
    assert gaps(a) == gaps(b)
    assert len(a) == round(3.2 * 51.0)
    assert all(0.0 <= x.t < 51.0 for x in a)


def test_open_loop_zipf_counts():
    counts = Counter(x.function for x in _open(3))
    shares = schedules.zipf_shares(16, 1.0)
    assert sum(counts.values()) == 163
    for f in range(16):
        assert abs(counts[f] - 163 * shares[f]) < 1.0
    assert counts[0] > counts[1] > counts[15]


def test_split_counts_largest_remainder():
    assert schedules.split_counts(10, [0.55, 0.25, 0.2]) == [6, 2, 2]
    assert sum(schedules.split_counts(163, schedules.zipf_shares(32, 1.0))) == 163


def test_derived_seeds_differ_past_32_bits():
    # JAX's PRNGKey keeps 32 bits; 2**40 + 5 and 5 must not collide
    assert schedules.derive_seed(5) != schedules.derive_seed(2**40 + 5)
    assert 0 <= schedules.derive_seed(2**31 + 9, 3) < 2**32
    with pytest.raises(ValueError):
        schedules.derive_seed(-1)


def test_closed_loop_seeds_repeat_and_differ():
    s = [schedules.closed_loop_seed(99, c, k) for c in range(16) for k in range(50)]
    assert s == [schedules.closed_loop_seed(99, c, k)
                 for c in range(16) for k in range(50)]
    assert len(set(s)) == len(s)
