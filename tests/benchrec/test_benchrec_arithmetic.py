"""Percentiles, spreads, whole-pass accounting and arrival schedules."""

import statistics

import pytest

from benchmark import passes, stats
from benchmark.loadgen import Fleet, _Conn, _Write, window_arrivals, ws_frame
from benchmark.probe import union_seconds


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 0.5, 5.0),
    ([1, 2, 3, 4], 0.5, 2),
    ([1, 2, 3, 4, 5], 0.5, 3),
    (list(range(1, 101)), 0.95, 95),
    (list(range(1, 101)), 0.99, 99),
    (list(range(1, 101)), 1.0, 100),
    ([9, 1, 5, 3, 7], 0.95, 9),
    (list(range(1, 21)), 0.95, 19),
])
def test_percentile_is_nearest_rank(values, p, want):
    assert stats.percentile(values, p) == want


def test_percentile_edges():
    assert stats.percentile([], 0.5) is None
    with pytest.raises(ValueError):
        stats.percentile([1], 0.0)


def test_quartile_spread_is_the_builders():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
    assert stats.quartile_spread([1.0]) is None
    assert stats.share(1, 0) is None and stats.share(1, 4) == 25.0


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("seconds", [0.0, 1.0, 7.3, 10.0, 45.0, 51.0])
def test_a_known_pass_time_gives_the_known_rate_whatever_the_window(seconds):
    clock = FakeClock()
    PASS_S, BETWEEN_S, WORK = 2.5, 0.75, 500

    def between():
        clock.t += BETWEEN_S

    def one_pass(_):
        t0 = clock()
        clock.t += PASS_S
        return passes.Pass(t0, PASS_S, WORK)

    done = passes.run_passes(one_pass, seconds, between=between, clock=clock)
    assert passes.rate(done) == pytest.approx(WORK / PASS_S)
    assert len(done) >= 2
    spent = clock() - 100.0
    cycle = PASS_S + BETWEEN_S
    # no pass is cut, and the window is overrun by less than one cycle
    assert spent == pytest.approx(len(done) * cycle)
    assert spent < max(seconds, 2 * cycle) + cycle
    # another would have started had there been room for it
    assert len(done) == 2 or seconds - (spent - cycle) >= cycle


def test_a_slow_pass_keeps_the_next_from_starting_late():
    clock = FakeClock()
    times = iter([1.0, 4.0, 1.0, 1.0, 1.0])

    def one_pass(_):
        t0, dt = clock(), next(times)
        clock.t += dt
        return passes.Pass(t0, dt, 10)

    done = passes.run_passes(one_pass, 9.0, clock=clock)
    # after 1 + 4 + 1 = 6 s, 3 s are left and the longest pass took 4
    assert [p.seconds for p in done] == [1.0, 4.0, 1.0]
    assert passes.rate(done) == pytest.approx(30 / 6.0)
    assert passes.rate([]) is None


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_every_seed_offers_the_same_count_at_other_times(seed):
    a = window_arrivals(seed, 1000.0, 45.0, 400.0)
    b = window_arrivals(seed + 1, 1000.0, 45.0, 400.0)
    assert len(a) == len(b) == 18000
    assert a == sorted(a) and a != b
    assert 1000.0 <= a[0] and a[-1] < 1045.0
    assert a == window_arrivals(seed, 1000.0, 45.0, 400.0)


def test_ws_frame_lengths():
    assert ws_frame(b"x" * 5)[1] == 0x80 | 5
    assert ws_frame(b"x" * 300)[1] == 0x80 | 126
    assert ws_frame(b"x" * 70000)[1] == 0x80 | 127


@pytest.mark.parametrize("dropped", [0, 1, 2])
def test_a_commit_is_learned_from_the_block_whose_tx_event_was_evicted(
        dropped):
    """A node's subscription evicts the oldest of more than 1,024 Tx
    events: the NewBlock event still names every write of the block."""
    import hashlib
    import json
    fleet = Fleet({"seed": 3, "tx_bytes": 32})
    fleet.by_hash = [{}, {}]
    txs = [b"k%d=v%d" % (i, i) for i in range(3)]
    for i, tx in enumerate(txs):
        w = _Write(1.0, i % 2, "k%d" % i, b"v%d" % i, "window")
        fleet.writes.append(w)
        fleet.by_hash[i % 2][hashlib.sha256(tx).hexdigest().upper()] = w
    assert not fleet.window_answered()
    block = {"jsonrpc": "2.0", "id": "#event", "result": {
        "query": "tm.event = 'NewBlock'", "tags": {"tm.event": "NewBlock"},
        "data": {"block": {"header": {"height": 9},
                           "data": {"txs": [t.hex() for t in txs]}}}}}
    for i, tx in list(enumerate(txs))[dropped:]:
        fleet._on_frame(_Conn(None, i % 2), json.dumps({
            "jsonrpc": "2.0", "id": "#event", "result": {
                "tags": {"tx.hash": hashlib.sha256(tx).hexdigest().upper()},
                "data": {"height": 9, "index": i}}}).encode())
    for target in (0, 1):
        fleet._on_frame(_Conn(None, target), json.dumps(block).encode())
    assert fleet.window_answered()
    assert [(w.height, w.index) for w in fleet.writes] == [
        (9, 0), (9, 1), (9, 2)]
    assert fleet.learned == {"tx": 3 - dropped, "block": dropped}


def test_union_seconds_counts_overlap_once():
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert union_seconds([(0, 10)], 2, 4) == 2
    assert union_seconds([]) == 0
