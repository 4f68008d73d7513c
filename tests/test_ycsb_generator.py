"""benchmark/ycsb.py: the Zipfian law, the scramble, the values."""

import math
import random

import pytest

from benchmark import ycsb


@pytest.mark.parametrize("n, draws, seed", [(1000, 200_000, 1),
                                            (250_000, 300_000, 2)])
def test_ranks_are_drawn_by_the_zipfian_law(n, draws, seed):
    z = ycsb.Zipfian(n)
    zeta = sum(r ** -0.99 for r in range(1, n + 1))
    assert z.zeta == pytest.approx(zeta)
    rng = random.Random(seed)
    counts = {}
    for _ in range(draws):
        r = z.rank(rng.random())
        assert 1 <= r <= n
        counts[r] = counts.get(r, 0) + 1
    for rank in (1, 2, 10):
        p = 1.0 / (rank ** 0.99 * zeta)
        assert z.probability(rank) == pytest.approx(p)
        sigma = math.sqrt(draws * p * (1 - p))
        assert abs(counts.get(rank, 0) - draws * p) <= 3 * sigma, rank
    assert z.rank(0.0) == 1 and z.rank(1.0 - 1e-16) <= n


def test_the_skew_at_the_cells_scale():
    z = ycsb.Zipfian(250_000)
    assert 0.07 < z.probability(1) < 0.075
    assert 0.21 < sum(z.probability(r) for r in range(1, 11)) < 0.22


def test_one_scramble_for_the_generator_the_driver_and_the_reference():
    from benchmark import ycsbgen
    from benchmark.drivers import fleet_ycsb
    assert ycsbgen.ycsb is ycsb and fleet_ycsb.ycsb is ycsb
    # FNV-1a 64, by its published test vector for eight zero bytes'
    # neighbour: the offset basis run through eight rounds
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 0x100000001B3) & (2 ** 64 - 1)
    assert ycsb.fnv1a64(0) == h
    n = 5000
    chooser = ycsb.KeyChooser(9, n)
    again = ycsb.KeyChooser(9, n)
    items = [chooser.next_item() for _ in range(2000)]
    assert items == [again.next_item() for _ in range(2000)]
    assert all(0 <= i < n for i in items)
    hot = ycsb.scramble(1, n)
    assert items.count(hot) == max(items.count(i) for i in set(items))
    keys = ycsb.distinct_keys(n)
    assert all(len(k) == 23 and k.startswith(b"user") for k in keys)
    assert keys[hot] == ycsb.key_of(hot)


def test_values_have_the_records_size_and_differ():
    v = ycsb.Values(2 ** 31 + 5, 1000)
    got = {v.loaded(i) for i in range(500)} | {v.update(i)
                                               for i in range(500)}
    assert len(got) == 1000 and {len(x) for x in got} == {1000}
    assert all(b"=" not in x for x in got)
    pairs = list(ycsb.records(7, 50, 100))
    assert pairs[3] == (ycsb.key_of(3), ycsb.Values(7, 100).loaded(3))
