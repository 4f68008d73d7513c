"""benchmark/joinref.py, the plain reference of a fast-syncing full node
on a chain whose validator set moves, held to chains written out here
with `json`, hashlib and OpenSSL alone: nothing in this file, or in the
reference, imports the program."""

import json
import sys

import pytest

from benchmark import commitref, joinref, literef
from benchmark.kvref import PlainKV, openssl_signer

CHAIN_ID = "plain-join"
KEYS = [openssl_signer(bytes([i + 1]) * 32) for i in range(5)]
PUBS = [k.public_key().public_bytes_raw() for k in KEYS]
SIGN = {pub: k.sign for pub, k in zip(PUBS, KEYS)}


def val(pub: bytes, power: int) -> bytes:
    return b"val:%s/%d" % (pub.hex().encode(), power)


def genesis(powers) -> bytes:
    return json.dumps({"chain_id": CHAIN_ID, "validators": [
        {"pubkey": pub.hex(), "power": power}
        for pub, power in zip(PUBS, powers)]}).encode()


def plain_chain(genesis_wire, blocks_txs, absent=lambda height: ()):
    """Wire bytes of the blocks of `blocks_txs` and a sentinel above
    them, every header and commit as the reference wants them;
    `absent(height)` gives the keys that do not sign that height."""
    _chain, vals = joinref.parse_genesis(genesis_wire)
    app, app_hash = PlainKV(), b""
    wire, last_id, last_commit = [], None, []
    for height, txs in enumerate(list(blocks_txs) + [[]], 1):
        header = {"chain_id": CHAIN_ID, "height": height,
                  "app_hash": app_hash.hex(),
                  "validators_hash": literef.validators_hash(vals).hex(),
                  "last_block_id": last_id}
        wire.append(json.dumps({
            "header": header, "data": {"txs": [t.hex() for t in txs]},
            "last_commit": {"precommits": last_commit}}).encode())
        block_id = (literef.header_hash(header), 1, b"\x07" * 32)
        last_id = {"hash": block_id[0].hex(),
                   "parts": {"total": 1, "hash": block_id[2].hex()}}
        last_commit = []
        for index, (pub, _power) in enumerate(vals):
            if pub in absent(height):
                last_commit.append(None)
                continue
            vote = commitref.PlainVote(height, 0, commitref.PRECOMMIT,
                                       height * 10 ** 9 + index, block_id, b"")
            last_commit.append({
                "height": height, "round": 0, "type": commitref.PRECOMMIT,
                "timestamp_ns": vote.timestamp_ns, "block_id": last_id,
                "validator_address": literef.address_of(pub).hex(),
                "signature": SIGN[pub](
                    commitref.sign_bytes(CHAIN_ID, vote)).hex()})
        vals = joinref.update(vals, txs)
        app_hash = app.apply_block(
            [t for t in txs if not t.startswith(b"val:")])
    return wire


def test_it_imports_nothing_of_the_program():
    for name in ("benchmark.joinref", "benchmark.literef",
                 "benchmark.commitref", "benchmark.kvref",
                 "benchmark.stakeref"):
        with open(sys.modules[name].__file__) as f:
            assert "tendermint_tpu" not in f.read().split('"""', 2)[2]


# ------------------------------------------------------------ the set replay

A, B, C, D, E = PUBS
SET = joinref.in_order([(A, 10), (B, 20), (C, 30)])


@pytest.mark.parametrize("txs, want", [
    ([], [(A, 10), (B, 20), (C, 30)]),
    ([b"k=v", b"val:"], [(A, 10), (B, 20), (C, 30)]),
    ([val(A, 0)], [(B, 20), (C, 30)]),                  # 0 removes
    ([val(D, 7)], [(A, 10), (B, 20), (C, 30), (D, 7)]),  # joins
    ([val(B, 21)], [(A, 10), (B, 21), (C, 30)]),        # a new power
    ([val(A, 0), val(D, 11)], [(B, 20), (C, 30), (D, 11)]),
    ([val(D, 5), val(D, 0)], [(A, 10), (B, 20), (C, 30)]),  # in block order
    ([val(D, 0)], [(A, 10), (B, 20), (C, 30)]),         # not in the set
    ([val(A, -1)], [(A, 10), (B, 20), (C, 30)]),
    ([b"val:zz/3", b"val:" + A.hex().encode() + b"/x",
      b"val:" + A.hex()[:10].encode() + b"/3"], [(A, 10), (B, 20), (C, 30)]),
])
def test_a_blocks_val_transactions_move_the_set(txs, want):
    assert joinref.update(SET, txs) == joinref.in_order(want)
    assert SET == joinref.in_order([(A, 10), (B, 20), (C, 30)])


def test_the_last_member_stays():
    one = [(A, 10)]
    assert joinref.update(one, [val(A, 0)]) == one
    assert joinref.update(one, [val(B, 1), val(A, 0)]) == [(B, 1)]


def test_a_set_is_ordered_by_address_not_by_key_or_power():
    vals = joinref.in_order([(p, i + 1) for i, p in enumerate(PUBS)])
    addresses = [literef.address_of(p) for p, _ in vals]
    assert addresses == sorted(addresses)
    assert [p for p, _ in vals] != sorted(PUBS)
    assert joinref.parse_genesis(genesis([1, 2, 3, 4, 5])) == (CHAIN_ID, vals)


# ------------------------------------------------------------------ the replay

def test_an_update_at_h_is_in_force_from_h_plus_1():
    gen = genesis([10, 10, 10])
    wire = plain_chain(gen, [[b"a=1"], [b"b=2", val(D, 10)], [b"c=3"],
                             [val(A, 0), val(E, 9)], [b"d=4"]])
    ref = joinref.replay(gen, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (5, None, None)
    hashes = ref.validators_hashes              # in force at 1 .. 6
    assert len(hashes) == 6
    assert hashes[0] == hashes[1]               # heights 1, 2
    assert hashes[2] != hashes[1]               # block 2's update: from 3
    assert hashes[3] == hashes[2]               # height 4
    assert hashes[4] != hashes[3] and hashes[5] == hashes[4]    # 5, 6
    assert ref.validators == joinref.in_order(
        [(B, 10), (C, 10), (D, 10), (E, 9)])
    # the commit of height 3 has four votes, that of height 2 three
    assert [len(json.loads(w)["last_commit"]["precommits"])
            for w in wire] == [0, 3, 3, 4, 4, 4]
    # a `val:` transaction is no key of the store
    plain = PlainKV()
    assert ref.app_hashes == [plain.apply_block(txs) for txs in (
        [b"a=1"], [b"b=2"], [b"c=3"], [], [b"d=4"])]


def test_a_commit_judged_under_the_set_of_the_height_below_is_refused():
    """Height 3's commit signed by the set in force at 2: one vote
    short, so the reference, which holds four validators at 3, refuses
    its size."""
    gen = genesis([10, 10, 10])
    wire = plain_chain(gen, [[b"a=1"], [val(D, 10)], [b"c=3"]])
    stale = plain_chain(gen, [[b"a=1"], [b"x=1"], [b"c=3"]])
    doc = json.loads(wire[3])
    doc["last_commit"] = json.loads(stale[3])["last_commit"]
    ref = joinref.replay(gen, wire[:3] + [json.dumps(doc).encode()])
    assert (ref.height, ref.refused_at, ref.kind) == (2, 3, joinref.COMMIT)
    assert "wrong set size" in ref.why


@pytest.mark.parametrize("powers", [[2, 2, 2], [3, 2, 2], [2, 2, 3],
                                    [5, 4, 1], [1, 1, 1]])
def test_a_quorum_exactly_at_two_thirds_is_refused(powers):
    """Each validator absent in turn from height 2's commit: accepted
    iff three times the signing stake is MORE than twice the total (4
    of 6 is refused, 5 of 7 passes), with OpenSSL and without."""
    gen = genesis(powers)
    _chain, vals = joinref.parse_genesis(gen)
    total = sum(powers)
    for gone, power in vals:
        signing = total - power
        wire = plain_chain(gen, [[b"a=1"], [b"b=2"]],
                           absent=lambda h, gone=gone: (gone,) if h == 2
                           else ())
        for check in (lambda h: True, lambda h: False):
            ref = joinref.replay(gen, wire, check_signatures=check)
            if 3 * signing > 2 * total:
                assert (ref.height, ref.refused_at) == (2, None)
            else:
                assert (ref.height, ref.refused_at, ref.kind) == (
                    1, 2, joinref.QUORUM)
                assert f"got {signing} of {total}" in ref.why


def test_one_unit_above_two_thirds_passes_and_at_it_fails():
    # 20 of 30 is two thirds to the unit; 7 of 10 and 21 of 31 are above
    for powers, ok in (([7, 3], True), ([20, 10], False), ([21, 10], True)):
        gen = genesis(powers)
        _chain, vals = joinref.parse_genesis(gen)
        small = min(vals, key=lambda v: v[1])[0]
        wire = plain_chain(gen, [[b"a=1"]], absent=lambda h: (small,))
        ref = joinref.replay(gen, wire)
        assert (ref.refused_at is None) == ok, (powers, ref)
        if not ok:
            assert ref.kind == joinref.QUORUM and ref.height == 0


def test_the_headers_set_app_hash_chain_and_height_are_held():
    gen = genesis([10, 10, 10])
    txs = [[b"a=1"], [val(D, 10)], [b"c=3"], [b"d=4"]]
    wire = plain_chain(gen, txs)
    assert joinref.replay(gen, wire).height == 4

    def tampered(at, **fields):
        doc = json.loads(wire[at - 1])
        doc["header"].update(fields)
        return wire[:at - 1] + [json.dumps(doc).encode()] + wire[at:]

    for height, fields, kind in (
            (3, {"validators_hash": json.loads(wire[0])["header"]
                 ["validators_hash"]}, joinref.VALIDATORS_HASH),
            (2, {"app_hash": "00" * 32}, joinref.APP_HASH),
            (2, {"chain_id": "other"}, joinref.CHAIN_ID),
            (3, {"height": 4}, joinref.HEIGHT),
            (2, {"time": 5}, joinref.BLOCK_ID)):    # the hash moves
        ref = joinref.replay(gen, tampered(height, **fields))
        assert (ref.height, ref.refused_at, ref.kind) == (
            height - 1, height, kind), (fields, ref.why)
        assert len(ref.app_hashes) == height - 1
        assert len(ref.validators_hashes) == height


def test_a_flipped_signature_bit_is_found_only_where_openssl_looks():
    gen = genesis([10, 10, 10])
    wire = plain_chain(gen, [[b"a=1"], [b"b=2"], [b"c=3"]])
    doc = json.loads(wire[2])                   # carries height 2's commit
    sig = bytes.fromhex(doc["last_commit"]["precommits"][1]["signature"])
    doc["last_commit"]["precommits"][1]["signature"] = (
        sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]).hex()
    bad = wire[:2] + [json.dumps(doc).encode()] + wire[3:]
    ref = joinref.replay(gen, bad)
    assert (ref.height, ref.refused_at, ref.kind) == (
        1, 2, joinref.SIGNATURE)
    assert ref.why == "invalid signature @ index 1"
    assert joinref.replay(gen, bad, lambda h: h != 2).height == 3
    assert joinref.replay(gen, bad, lambda h: h == 2).refused_at == 2
