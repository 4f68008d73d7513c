"""Seeds change contents and never sizes; the plain references agree
with the program where the program is sound."""

import hashlib
import random

import pytest

from benchmark import chain, kvref


@pytest.fixture(scope="module")
def two_chains():
    out = []
    for seed in (1, 2**31 + 9):
        b = chain.ChainBuilder(seed, n_vals=4, n_txs=7, tx_bytes=96,
                               key_space=3)
        wire, expect = b.build_wire(5)
        out.append((b, wire, expect))
    return out


def test_sync_chain_sizes_do_not_depend_on_the_seed(two_chains):
    (a, wa, ea), (b, wb, eb) = two_chains
    assert [len(x) for x in wa] == [len(x) for x in wb]
    assert wa != wb and ea != eb
    assert a.gen.chain_id != b.gen.chain_id
    assert len(a.gen.validators) == len(b.gen.validators) == 4
    for h in (1, 2, 5):
        assert {len(t) for t in a.txs_of(h)} == {96}
        assert {len(t) for t in b.txs_of(h)} == {96}
        assert len(a.txs_of(h)) == 7


def test_same_seed_same_bytes():
    one = chain.ChainBuilder(5, 4, 3, 64, 2).build_wire(3)
    two = chain.ChainBuilder(5, 4, 3, 64, 2).build_wire(3)
    assert one == two


def test_plain_kv_gives_the_chain_builders_app_hashes(two_chains):
    from benchmark.drivers.sync import txs_of_wire
    _b, wire, expect = two_chains[0]
    ref = kvref.PlainKV()
    after = [ref.apply_block(txs_of_wire(raw)) for raw in wire]
    # block h+1's header carries the app hash after block h
    assert after[:-1] == [e[1] for e in expect[1:]]
    assert txs_of_wire(wire[0])[0].startswith(b"k1.0=v1.")


def test_plain_kv_follows_overwrites_like_the_program():
    from tendermint_tpu.abci.apps.kvstore import KVStoreApp
    rng = random.Random(4)
    app, ref = KVStoreApp(use_native=False), kvref.PlainKV()
    for _ in range(6):
        txs = [b"k%d=%d" % (rng.randrange(12), rng.randrange(10**6))
               for _ in range(20)] + [b"opaque%d" % rng.randrange(3)]
        for tx in txs:
            app.deliver_tx(tx)
        assert ref.apply_block(txs) == app.commit()
    assert dict(app.store) == ref.store


def test_merkle_root_of_digests_matches_the_program():
    from tendermint_tpu.ops import merkle
    for n in (1, 2, 3, 256):
        ds = [hashlib.sha256(b"%d" % i).digest() for i in range(n)]
        assert kvref.merkle_root_of_digests(ds) == \
            merkle.root_from_digests_host(ds)


def test_openssl_oracle():
    key = kvref.openssl_signer(b"\x07" * 32)
    pub = key.public_key().public_bytes_raw()
    sig = key.sign(b"msg")
    assert kvref.openssl_verify(pub, b"msg", sig)
    assert not kvref.openssl_verify(pub, b"msh", sig)
    assert not kvref.openssl_verify(pub, b"msg", sig[:-1] + b"\x00")


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_lite_chain_sizes_and_forgery(seed):
    lc = chain.LiteChain(seed, n_headers=6, n_vals=4, sign="openssl")
    assert len(lc.wire) == 6 and len(lc.sigs) == 24
    assert len({len(w) for w in lc.wire}) == 1
    valset, fcs = lc.decode()
    assert [fc.height for fc in fcs] == [1, 2, 3, 4, 5, 6]
    assert all(kvref.openssl_verify(lc.pubkeys[j], lc.msgs[i],
                                    lc.sigs[i * 4 + j])
               for i in range(6) for j in range(4))
    forged = lc.forged_header(4)
    assert forged != lc.wire[3] and len(forged) == len(lc.wire[3])


def test_padded_tx_is_exact_and_never_cuts_the_record():
    pad = chain.pad_blob(1, "t", 1000)
    assert len(chain.padded_tx(b"k1", b"v", pad, 12345, 250)) == 250
    assert chain.padded_tx(b"k1", b"v", pad, 0, 3) == b"k1=v."
    assert chain.pad_blob(1, "t", 1000) == pad != chain.pad_blob(2, "t", 1000)


def test_forged_precommit_changes_one_signature_bit(two_chains):
    from tendermint_tpu.types.block import Block
    _b, wire, _e = two_chains[0]
    forged = chain.forge_precommit(wire[2], 1)
    a, b = Block.from_bytes(wire[2]), Block.from_bytes(forged)
    diff = [i for i, (x, y) in enumerate(zip(a.last_commit.precommits,
                                             b.last_commit.precommits))
            if x.signature != y.signature]
    assert diff == [1]
    b.validate_basic()      # still consistent with itself
