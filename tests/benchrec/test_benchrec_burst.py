"""The cell `net_4v_kvstore.writes_burst`: the steady cell's traffic in
bursts on a clock, data alone (a traffic file for the `fleet` driver
that is there): its entries in the manifest, found by name; its traffic
file against the steady cell's; the arrivals a window of the timed size
draws; and its rehearsal on the CPU, both lines."""

from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

BURST = "net_4v_kvstore.writes_burst"
STEADY = "net_4v_kvstore.writes_steady"


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_the_cell_is_declared():
    doc = manifest()
    burst, = [w for w in doc["workloads"] if w["name"] == BURST]
    assert burst == dict(burst, config="net_4v_kvstore",
                         traffic="writes_burst", chips=1)
    assert len(burst["why"]) <= 200 and "bursts" in burst["why"]
    rates = {m["name"]: m for m in doc["end_to_end"]}
    assert {STEADY, BURST} <= set(rates["commit_p50_ms"]["workloads"])
    assert rates["commit_p50_ms"]["bound"] == 0.25
    man = Manifest(REPO)
    assert {m["name"] for m in man.metrics(BURST, "end_to_end")} == {
        "commit_p50_ms", "setup_s"}
    assert man.traffic(burst)["driver"] == "fleet"


def test_the_burst_cell_lists_what_the_steady_cell_lists():
    doc = manifest()
    steady = {m["name"] for m in doc["per_layer"]
              if STEADY in m.get("workloads", ())}
    burst = {m["name"] for m in doc["per_layer"]
             if BURST in m.get("workloads", ())}
    assert steady == burst and len(burst) >= 12
    man = Manifest(REPO)
    a = man.traffic(man.cell(STEADY))
    b = man.traffic(man.cell(BURST))
    assert b["driver"] == a["driver"] == "fleet"
    assert b["rehearsal"] == a["rehearsal"]
    assert b["params"] == dict(
        a["params"], rate=240,
        arrivals={"law": "bursts", "period_s": 2, "duty": 0.25})
    # a mean of two fifths of the steady knee; exactly 10,800 arrivals
    assert b["params"]["rate"] * 5 == 600 * 2
    assert b["params"]["rate"] * doc["run_seconds"] == 10800


def test_a_burst_windows_arrivals_all_fall_in_the_on_phases():
    from benchmark.loadgen import window_arrivals
    man = Manifest(REPO)
    p = man.traffic(man.cell(BURST))["params"]
    for seed in (3, 2**31 + 9):
        due = window_arrivals(seed, 100.0, 45.0, p["rate"], p["arrivals"])
        assert len(due) == 10800 and due == sorted(due)
        assert all(0.0 <= (d - 100.0) % 2.0 < 0.5 + 1e-9 for d in due)
        assert {int((d - 100.0) // 2.0) for d in due} == set(range(23))


def test_the_burst_cell_rehearses_both_lines(monkeypatch):
    """The steady cell's rehearsal under the burst file's law: the same
    count as a uniform window of that rate, every write due in the first
    quarter of a two-second period, correct, and the steady cell's
    per-layer metrics in the traced line."""
    from benchmark.harness import Harness
    kept, result = {}, Harness.result

    def keeping(self, outcome, device):
        kept["client"] = outcome.client
        return result(self, outcome, device)
    monkeypatch.setattr(Harness, "result", keeping)
    line = rehearse(BURST, seed=2**31 + 47, seconds=4.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 160     # the rehearsal's rate 40 for 4 s
    due = kept["client"]["due_s"]
    assert len(due) == 160 and all(0.0 <= d < 4.0 for d in due)
    assert all(d % 2.0 < 0.5 + 1e-6 for d in due)
    assert {int(d // 2.0) for d in due} == {0, 1}
    got = values(line)
    assert {"gen_lateness_p99_ms", "txs_per_block", "block_interval_ms",
            "commit_p95_ms", "propose_wait_p50_ms", "admit_p50_ms",
            "steady_committed_tx_per_s"} <= set(got)
    assert set(values(rehearse(BURST, seconds=1.0))) == {
        "commit_p50_ms", "setup_s"}
