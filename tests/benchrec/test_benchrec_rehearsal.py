"""The whole command's path at toy sizes on the CPU (behind the test
suite only; the command itself never falls back), the controls that
have to come out as not correct, and the timed path broken underneath.
No kernel compiles: every batch here sits under auto_threshold."""

import pytest

from benchrec_util import rehearse

LITE = "chain_64v.lite_certify"
SYNC = "chain_64v.fastsync_5ktx"
STEADY = "net_4v_kvstore.writes_steady"


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


@pytest.mark.parametrize("cell,want", [
    (LITE, {"headers_per_s", "setup_s"}),
    (SYNC, {"commits_per_s", "setup_s"}),
])
def test_chain_cell_end_to_end_line(cell, want):
    line = rehearse(cell, seed=2**31 + 11)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == want
    assert all(v > 0 for v in values(line).values())
    assert line["attempted"] > 0 and line["device"]["rehearsal"] is True
    rate = (want - {"setup_s"}).pop()
    assert line["metrics"][rate]["unit"] == rate.replace("_per_", "/")


@pytest.mark.parametrize("cell,want", [
    (LITE, {"lite_verify_wall_share", "lite_sigs_on_device_share",
            "lite_compiles_in_window", "lite_setup_compile_s"}),
    (SYNC, {"window_collect_share", "wire_decode_share",
            "apply_ms_per_block", "merkle_native_share",
            "verify_wall_share", "sigs_on_device_share",
            "compiles_in_window", "setup_compile_s"}),
])
def test_chain_cell_traced_line_leaves_out_what_has_no_trace(cell, want):
    line = rehearse(cell, trace=True)
    assert line["correct"] is True
    # no device here: the trace's metrics find nothing to read and are
    # left out, never reported from the CPU under a device's name
    assert want <= set(line["metrics"])
    assert not [m for m in line["metrics"] if m.endswith(
        ("kernel_busy_share", "kernel_sigs_per_s", "device_idle_share"))]
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert [v for k, v in values(line).items()
            if k.endswith("compiles_in_window")] == [0]


@pytest.mark.parametrize("cell", [LITE, SYNC])
@pytest.mark.parametrize("control", ["accept_all", "truncate"])
def test_a_verifier_that_checks_less_is_not_correct(cell, control):
    line = rehearse(cell, control=control)
    assert line["correct"] is False


def test_lite_with_certification_skipped_is_not_correct(monkeypatch):
    import benchmark.drivers.certify  # noqa: F401
    from tendermint_tpu.lite import certifier
    monkeypatch.setattr(certifier, "certify_chain",
                        lambda *a, **k: None)
    assert rehearse(LITE)["correct"] is False


def test_sync_that_ignores_verdicts_is_not_correct(monkeypatch):
    from tendermint_tpu.types.validator_set import ValidatorSet
    monkeypatch.setattr(ValidatorSet, "check_commit_results",
                        lambda self, ok, item_power: None)
    assert rehearse(SYNC)["correct"] is False


def test_sync_whose_app_drops_part_of_a_block_is_not_applied(monkeypatch):
    """The app leaves out a part of the batch: the node's own check of
    the next header's app hash stops it, and the run is not correct."""
    from tendermint_tpu.abci.apps.kvstore import KVStoreApp
    whole = KVStoreApp.deliver_tx_batch
    state = {"on": False}

    def partial(self, txs):
        return whole(self, txs[:-1] if state["on"] and len(txs) > 1 else txs)
    monkeypatch.setattr(KVStoreApp, "deliver_tx_batch", partial)
    import benchmark.drivers.sync as sync
    fresh = sync.fresh_reactor

    def fresh_and_break(*a, **k):
        state["on"] = True      # the builder's chain was whole
        return fresh(*a, **k)
    monkeypatch.setattr(sync, "fresh_reactor", fresh_and_break)
    try:
        line = rehearse(SYNC)
    except Exception:
        return      # the program refused the block outright: not a result
    assert line["correct"] is False


def test_steady_net_cell_both_lines():
    line = rehearse(STEADY, seed=2**31 + 3, seconds=2.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    got = values(line)
    assert {"gen_lateness_p99_ms", "txs_per_block", "block_interval_ms",
            "rounds_gt0_share", "tail_p99_ms", "steady_committed_tx_per_s",
            "commit_p95_ms", "longest_block_gap_ms",
            "admit_p50_ms", "propose_wait_p50_ms",
            "net_sigs_on_device_share"} <= set(got)
    assert "net_device_idle_share" not in got
    assert line["attempted"] == 80      # rate 40 for 2 s, for every seed
    assert got["steady_committed_tx_per_s"] == pytest.approx(40.0, rel=0.02)


def test_net_whose_app_answers_reads_wrongly_is_not_correct(monkeypatch):
    from tendermint_tpu.abci.apps.kvstore import KVStoreApp
    honest = KVStoreApp.query

    def altered(self, path, data, height=0, prove=False):
        res = honest(self, path, data, height=height, prove=prove)
        if res.value:
            res.value = res.value[:-1] + bytes([res.value[-1] ^ 1])
        return res
    monkeypatch.setattr(KVStoreApp, "query", altered)
    assert rehearse(STEADY, seconds=1.0)["correct"] is False


def test_net_audit_with_a_verifier_that_accepts_all_is_not_correct():
    assert rehearse(STEADY, seconds=1.0,
                    control="accept_all")["correct"] is False
