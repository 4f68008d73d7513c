"""A block decoded from its wire bytes: the native decoder of the
transaction list (native/codec.cpp split_hex_array, reached through
encoding.cloads_hex_array and Block.from_wire) against the specification
path (cloads + Block.from_obj). Every test runs twice: with the
extension, and with `native.codec()` patched to None, where the pure
path must do all of it and the counter must say so."""

import json
import time

import pytest

from tendermint_tpu import native, telemetry
from tendermint_tpu.types import encoding
from tendermint_tpu.types.block import TXS_PATH, Block


@pytest.fixture(params=["native", "pure"])
def how(request, monkeypatch):
    """Which way blocks are decoded in this test; encoding resolves the
    codec anew, and again after the test."""
    if request.param == "pure":
        monkeypatch.setattr(native, "codec", lambda: None)
    elif native.codec() is None:
        pytest.skip("native codec unavailable")
    monkeypatch.setattr(encoding, "_native_state", None)
    return request.param


@pytest.fixture
def counted(block_decodes):
    """Reads the program's own family, counting from zero."""
    return lambda: {h: block_decodes.labels(h).value
                    for h in ("native", "pure")}


def spec(doc):
    """Today's path, the specification."""
    return Block.from_obj(encoding.cloads(doc))


def outcome(fn, *args):
    """("ok", result) or ("raised", the exception's type)."""
    try:
        return "ok", fn(*args)
    except RecursionError:
        return "raised", RecursionError
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return "raised", type(e)


_WIRE = {}


def wire_of(n_txs: int) -> bytes:
    """The wire bytes of a block of the benchmark's chain with a real
    LastCommit (height 2) and n_txs transactions of 250 bytes."""
    if n_txs not in _WIRE:
        from benchmark.chain import ChainBuilder
        _WIRE[n_txs] = ChainBuilder(33, 4, n_txs, 250, 16).build_wire(2)[0][1]
    return _WIRE[n_txs]


def with_txs(doc: bytes, txs_json: str, at: str = '"txs":') -> bytes:
    """`doc` with the text of its (empty) transaction array replaced."""
    head, sep, tail = doc.partition(b'"data":{"txs":[]')
    assert sep, doc[:80]
    return head + b'"data":{' + at.encode() + txs_json.encode() + tail


# ----------------------------------------------------- (a) differential

@pytest.mark.parametrize("n_txs", [0, 1, 130, 5000])
def test_decodes_as_the_specification_does(how, counted, n_txs):
    w = wire_of(n_txs)
    got, want = Block.from_bytes(w), spec(w)
    assert got == want
    assert type(got.data.txs) is list and len(got.data.txs) == n_txs
    assert all(type(t) is bytes and len(t) == 250 for t in got.data.txs)
    assert got.data.txs == want.data.txs
    assert counted() == {"native": float(how == "native"),
                         "pure": float(how == "pure")}


@pytest.mark.parametrize("txs_json, want", [
    ('[""]', [b""]),
    ('["","",""]', [b"", b"", b""]),
    ('["AABB","aAbB","00ff","0123456789abcdefABCDEF"]',
     [b"\xaa\xbb", b"\xaa\xbb", b"\x00\xff",
      bytes.fromhex("0123456789abcdefabcdef")]),
    ('["",' + ",".join('"%02X"' % i for i in range(256)) + "]",
     [b""] + [bytes([i]) for i in range(256)])])
def test_empty_and_uppercase_transactions(how, counted, txs_json, want):
    doc = with_txs(wire_of(0), txs_json)
    got = Block.from_bytes(doc)
    assert got.data.txs == want == spec(doc).data.txs
    assert got == spec(doc)
    # these the decoder is sure of: none of them fell back
    assert counted()[how] == 1.0


# ------------------------------------------- (b) hostile, non-canonical

def _deep(n):
    return "[" * n + "]" * n


# a JSON unicode escape, spelled so that no tool reads it as one
U00 = "\\" + "u00"
_ESCAPED = json.dumps('x"txs":["deadbeef"],"data":{"txs":["ff"]},"y')

HOSTILE = {
    # the issue's Fallback list
    "whitespace in a hex string": lambda w: with_txs(w, '["aa bb"," cc"]'),
    "odd length": lambda w: with_txs(w, '["aa","abc"]'),
    "non-hex character": lambda w: with_txs(w, '["aa","zz"]'),
    "second txs key": lambda w: with_txs(w, '["aa"],"txs":["bb","cc"]'),
    "second txs key, first not a list":
        lambda w: with_txs(w, '7,"txs":["bb"]'),
    "second data key": lambda w: w.replace(
        b'"data":{"txs":[]}', b'"data":{"txs":["aa"]},"data":{"txs":["bb"]}'),
    "txs a string": lambda w: with_txs(w, '"aabb"'),
    "txs an object": lambda w: with_txs(w, '{"aa":1}'),
    "txs a number": lambda w: with_txs(w, "5"),
    "txs null": lambda w: with_txs(w, "null"),
    "txs of numbers": lambda w: with_txs(w, "[1,2]"),
    "txs of lists": lambda w: with_txs(w, '[["aa"]]'),
    "txs of null": lambda w: with_txs(w, '["aa",null]'),
    "data a list": lambda w: w.replace(b'"data":{"txs":[]}',
                                       b'"data":[{"txs":["aa"]}]'),
    "data a string": lambda w: w.replace(b'"data":{"txs":[]}',
                                         b'"data":"txs"'),
    "no data": lambda w: w.replace(b'"data":{"txs":[]},', b""),
    "no txs": lambda w: w.replace(b'"data":{"txs":[]}', b'"data":{}'),
    "bytes after the end": lambda w: with_txs(w, '["aa"]') + b"x",
    "a document after the end": lambda w: with_txs(w, '["aa"]') + w,
    "a newline after the end": lambda w: with_txs(w, '["aa"]') + b"\n",
    "invalid UTF-8 after the array": lambda w: with_txs(w, '["aa"]').replace(
        b'"chain_id":"', b'"chain_id":"\xff\xfe'),
    "invalid UTF-8 before the array": lambda w: with_txs(w, '["aa"]').replace(
        b'{"data":', b'{"\xc3\x28":1,"data":'),
    "invalid UTF-8 in a transaction":
        lambda w: with_txs(w, '["aa"]').replace(b'["aa"]', b'["\xff\xfe"]'),
    # escapes
    "quote escaped in a transaction": lambda w: with_txs(w, r'["aa\"bb"]'),
    "quote escaped, even length": lambda w: with_txs(w, r'["a\"","bb"]'),
    "unicode escape in a transaction":
        lambda w: with_txs(w, '["' + U00 + '61a"]'),
    "backslash ends a transaction": lambda w: with_txs(w, '["aa\\\\"]'),
    "key written with an escape":
        lambda w: with_txs(w, '["aa"]', at='"t' + U00 + '78s":'),
    "data written with an escape": lambda w: with_txs(w, '["aa"]').replace(
        b'{"data":', b'{"d' + U00.encode() + b'61ta":'),
    "escaped and plain txs keys":
        lambda w: with_txs(w, '["aa"],"t' + U00 + '78s":["bb"]'),
    # text that looks like the array, where it is not
    "chain_id holds the text": lambda w: with_txs(w, '["aa","bb"]').replace(
        b'"chain_id":"', b'"chain_id":' + _ESCAPED.encode()[:-1]),
    "a string before data holds the text":
        lambda w: with_txs(w, '["aa"]').replace(
            b'{"data":', b'{"aaa":' + _ESCAPED.encode() + b',"data":'),
    "a look-alike under the header": lambda w: with_txs(w, '["aa"]').replace(
        b'"header":{', b'"header":{"data":{"txs":["ff"]},'),
    "a look-alike in an array": lambda w: with_txs(w, '["aa"]').replace(
        b'{"data":', b'{"aaa":[{"data":{"txs":["ff"]}}],"data":'),
    "txs one level too deep":
        lambda w: with_txs(w, '{"txs":["aa"]}', at='"data":'),
    # non-canonical but valid JSON
    "whitespace between transactions":
        lambda w: with_txs(w, '["aa", "bb"]'),
    "whitespace inside the brackets": lambda w: with_txs(w, '[ "aa" ]'),
    "an empty array with a space": lambda w: with_txs(w, "[ ]"),
    "whitespace around the colon": lambda w: with_txs(w, ' ["aa"]',
                                                      at='"txs" :'),
    "whitespace around the document":
        lambda w: b" \n" + with_txs(w, '["aa"]'),
    # broken JSON
    "trailing comma": lambda w: with_txs(w, '["aa",]'),
    "leading comma": lambda w: with_txs(w, '[,"aa"]'),
    "no comma": lambda w: with_txs(w, '["aa""bb"]'),
    "array closed by a brace": lambda w: with_txs(w, '["aa"}'),
    "no colon": lambda w: with_txs(w, '["aa"]', at='"txs"'),
    "a bad number before the array": lambda w: with_txs(w, '["aa"]').replace(
        b'{"data":', b'{"aaa":01,"data":'),
    "a bad literal after the array": lambda w: with_txs(w, '["aa"]').replace(
        b'"evidence":{', b'"evidence":nul,"x":{'),
    "a bad escape after the array": lambda w: with_txs(w, '["aa"]').replace(
        b'"chain_id":"', b'"chain_id":"\\x'),
    "a control character in a string":
        lambda w: with_txs(w, '["aa"]').replace(
            b'"chain_id":"', b'"chain_id":"\x01'),
    "a NUL between tokens": lambda w: with_txs(w, '["aa"]').replace(
        b'{"data":', b'{\x00"data":'),
    "an extra closing brace": lambda w: with_txs(w, '["aa"]') + b"}",
    "closed too early": lambda w: with_txs(w, '["aa"]}}', at='"txs":'),
    # truncated
    "cut in the array": lambda w: with_txs(w, '["aa","bb"]').partition(
        b'"bb')[0],
    "cut in a transaction": lambda w: with_txs(w, '["aabb"]').partition(
        b'bb"')[0],
    "cut after the array": lambda w: with_txs(w, '["aa"]').partition(
        b'"evidence"')[0],
    "cut in half": lambda w: wire_of(130)[:len(wire_of(130)) // 2],
    "cut by one": lambda w: wire_of(130)[:-1],
    "cut to the array's bracket": lambda w: w.partition(b'"txs":[')[0]
        + b'"txs":[',
    "empty": lambda w: b"",
    # other documents
    "null": lambda w: b"null",
    "an empty object": lambda w: b"{}",
    "a list": lambda w: b'[{"data":{"txs":["aa"]}}]',
    "a string": lambda w: b'"data"',
    "a number": lambda w: b"17",
    "only the array": lambda w: b'{"data":{"txs":["aa"]}}',
    # nesting
    "nested past the decoder's depth": lambda w: with_txs(
        w, '["aa"]').replace(b'{"data":', b'{"aaa":' + _deep(100).encode()
                             + b',"data":'),
    "nested past the interpreter's depth": lambda w: with_txs(
        w, '["aa"]').replace(b'{"data":', b'{"aaa":' + _deep(200_000).encode()
                             + b',"data":'),
    "deep after the array": lambda w: with_txs(w, '["aa"]').replace(
        b'"evidence":{', b'"eee":' + _deep(100).encode() + b',"evidence":{'),
    # other types than bytes
    "a bytearray": lambda w: bytearray(with_txs(w, '["aa"]')),
    "a memoryview": lambda w: memoryview(with_txs(w, '["aa"]')),
    "a str": lambda w: with_txs(w, '["aa"]').decode(),
}


@pytest.mark.parametrize("case", sorted(HOSTILE))
def test_hostile_documents_fare_as_by_the_specification(how, case):
    doc = HOSTILE[case](wire_of(0))
    want = outcome(spec, doc)
    assert outcome(Block.from_bytes, doc) == want, case
    if want[0] == "ok":
        assert Block.from_bytes(doc).data.txs == want[1].data.txs
    if how == "pure":
        return
    # the decoder itself: Fallback, or the specification's tree
    mod = native.codec()
    try:
        items, rest = mod.split_hex_array(doc, TXS_PATH)
    except mod.Fallback:
        return
    tree = outcome(encoding.cloads, rest)
    whole = outcome(encoding.cloads, doc)
    assert tree[0] == whole[0], case
    if whole[0] == "raised":
        assert tree[1] is whole[1], case
        return
    assert tree[1]["data"].pop("txs") == []
    assert items == [bytes.fromhex(t) for t in whole[1]["data"].pop("txs")]
    assert tree[1] == whole[1]
    # what it accepts is canonical here: nothing to skip, nothing escaped
    assert all(t.hex().encode() in bytes(doc).lower() for t in items)


def test_the_decoder_is_sure_of_little(how):
    """Of the hostile documents the decoder takes only those whose
    array is plain: a document it accepts that the specification
    refuses is refused by json.loads of the rest, not by it."""
    if how == "pure":
        pytest.skip("the decoder is not there")
    mod = native.codec()
    taken = set()
    for case, make in HOSTILE.items():
        try:
            mod.split_hex_array(make(wire_of(0)), TXS_PATH)
            taken.add(case)
        except mod.Fallback:
            pass
    assert taken == {
        # the array plain and found by structure: decoded natively
        "chain_id holds the text", "a string before data holds the text",
        "a look-alike under the header", "a look-alike in an array",
        "whitespace around the colon", "whitespace around the document",
        "only the array", "a bytearray",
        # the array plain, the fault elsewhere: json.loads of the rest
        # meets it as json.loads of the whole does
        "invalid UTF-8 after the array", "invalid UTF-8 before the array",
        "a bad number before the array", "a bad literal after the array",
        "a bad escape after the array", "a control character in a string",
        "a NUL between tokens", "no colon"}


def test_the_path_argument(how):
    if how == "pure":
        pytest.skip("the decoder is not there")
    mod = native.codec()
    doc = b'{"a":{"b":{"c":["00ff"]}},"c":["11"]}'
    assert mod.split_hex_array(doc, ("a", "b", "c")) == (
        [b"\x00\xff"], b'{"a":{"b":{"c":[]}},"c":["11"]}')
    assert mod.split_hex_array(doc, ("c",)) == (
        [b"\x11"], b'{"a":{"b":{"c":["00ff"]}},"c":[]}')
    for path in (("a",), ("a", "b"), ("b", "c"), ("a", "b", "c", "d")):
        with pytest.raises(mod.Fallback):
            mod.split_hex_array(doc, path)
    with pytest.raises(ValueError):
        mod.split_hex_array(doc, ())
    with pytest.raises(TypeError):
        mod.split_hex_array(doc, (b"c",))
    with pytest.raises(TypeError):
        mod.split_hex_array(doc, ["c"])


def test_every_length_and_every_byte_at_every_place(how):
    """The decoder's digit loop takes 16 digits a step and the rest one
    pair at a time: every length up to three steps decodes as
    bytes.fromhex does, and a byte that is no hex digit is met at any
    place of a step, and in the rest."""
    if how == "pure":
        pytest.skip("the decoder is not there")
    mod = native.codec()
    digits = b"0123456789abcdefABCDEF"
    for n in range(0, 2 * 52, 2):
        tx = bytes(digits[(7 * i + n) % 22] for i in range(n))
        doc = b'{"data":{"txs":["' + tx + b'","' + tx[::-1] + b'"]}}'
        assert mod.split_hex_array(doc, TXS_PATH) == (
            [bytes.fromhex(tx.decode()), bytes.fromhex(tx[::-1].decode())],
            b'{"data":{"txs":[]}}')
    tx = bytes(digits[i % 22] for i in range(40))
    for c in range(256):
        for at in range(40):
            bad = tx[:at] + bytes([c]) + tx[at + 1:]
            doc = b'{"data":{"txs":["' + bad + b'"]}}'
            if bytes([c]) in digits:
                items, _ = mod.split_hex_array(doc, TXS_PATH)
                assert items == [bytes.fromhex(bad.decode())]
                continue
            with pytest.raises(mod.Fallback):
                mod.split_hex_array(doc, TXS_PATH)


# ------------------------------------------- (c) what from_bytes leaves

@pytest.mark.parametrize("n_txs", [0, 130])
def test_from_bytes_keeps_the_wire_bytes_and_the_hashes(how, n_txs):
    w = wire_of(n_txs)
    got, want = Block.from_bytes(w), spec(w)
    assert got == want
    assert got.to_bytes() is w          # the bytes it was given, not a copy
    assert want.to_bytes() == w         # and what encoding it afresh gives
    assert got.data.hash() == want.data.hash() == got.header.data_hash
    assert got.hash() == want.hash()
    part_size = 4096
    assert got.make_part_set(part_size).header() == \
        want.make_part_set(part_size).header()
    assert got.block_id(part_size) == want.block_id(part_size)
    got.validate_basic()
    # a bytearray is kept as bytes, as before
    again = Block.from_bytes(bytearray(w))
    assert type(again.to_bytes()) is bytes and again.to_bytes() == w


def decode_spans(fn):
    """The program's `wire.decode_block` spans of one call."""
    from tendermint_tpu.telemetry import trace
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        t0 = time.perf_counter()
        fn()
        rows, _ = trace.TRACER.between("wire.decode_block", t0,
                                       time.perf_counter())
    finally:
        telemetry.set_enabled(was)
    return rows


def test_the_span_carries_the_bytes_decoded(how):
    w = wire_of(130)
    spans = decode_spans(lambda: Block.from_bytes(w))
    assert [e["args"]["bytes"] for e in spans] == [len(w)]


# --------------------------------------- (d) a block_response, received

class _Peer:
    id = "peer1"
    sent = []

    @classmethod
    def try_send_obj(cls, ch, obj):
        cls.sent.append(obj)
        return True


def _syncing_reactor(n_txs):
    """A fresh node's reactor that has asked _Peer for heights 1-2 of
    the chain `wire_of(n_txs)` is block 2 of."""
    from benchmark.chain import ChainBuilder
    from benchmark.drivers.sync import fresh_reactor
    from tendermint_tpu.models.verifier import BatchVerifier
    gen = ChainBuilder(33, 4, n_txs, 250, 16).gen
    r = fresh_reactor(gen, BatchVerifier("python"), 4)
    r.pool.send_request = lambda peer_id, height: True
    r.pool.set_peer_height(_Peer.id, 2)
    r.pool.make_next_requests()
    return r


def _response(block_doc: bytes) -> bytes:
    return b'{"block":' + block_doc + b',"type":"block_response"}'


@pytest.mark.parametrize("n_txs", [0, 130])
def test_a_block_response_hands_the_pool_an_equal_block(how, counted, n_txs):
    w = wire_of(n_txs)
    r = _syncing_reactor(n_txs)
    msg = _response(w)
    assert msg == encoding.cdumps(
        {"type": "block_response", "block": spec(w).to_obj()})
    r.receive(0x40, _Peer, msg)
    got = r.pool.requests[2].block
    assert got == spec(w) and got.data.txs == spec(w).data.txs
    assert type(got.data.txs) is list
    assert counted() == {"native": float(how == "native"),
                         "pure": float(how == "pure")}
    # unsolicited: the same block again is ignored, as before
    r.receive(0x40, _Peer, msg)
    assert r.pool.requests[2].block is got
    # the other messages are read as they were
    r.receive(0x40, _Peer, encoding.cdumps(
        {"type": "status_response", "height": 9}))
    assert r.min_peer_height() == 9
    assert counted()[how] == 2.0


def test_a_block_response_is_spanned_with_its_bytes(how):
    msg = _response(wire_of(130))
    r = _syncing_reactor(130)
    spans = decode_spans(lambda: r.receive(0x40, _Peer, msg))
    assert [(e["args"]["bytes"], e["req"]) for e in spans] == [(len(msg), 2)]


GARBAGE = {
    "not JSON": b"garbage",
    "invalid UTF-8": b"\xff\xfe",
    "cut": _response(b'{"data":{"txs":["aa"]}}')[:-9],
    "no block": b'{"type":"block_response"}',
    "a block of nothing": _response(b"{}"),
    "a block of transactions alone": _response(b'{"data":{"txs":["aa"]}}'),
    "bad hex and no header": _response(b'{"data":{"txs":["zz"]}}'),
    "block a list": _response(b'[{"data":{"txs":["aa"]}}]'),
    "a list": b'[{"type":"block_response"}]',
    "bad hex": None,
    "odd hex": None,
    "type named twice": b'{"block":{"data":{"txs":["aa"]}},'
                        b'"type":"block_response","type":"status_request"}',
    "an unknown type": b'{"block":{"data":{"txs":["aa"]}},"type":"nope"}',
}


@pytest.mark.parametrize("case", sorted(GARBAGE))
def test_garbage_is_dropped_as_it_was(how, monkeypatch, case):
    msg = GARBAGE[case]
    if msg is None:
        bad = '["aa","zz"]' if case == "bad hex" else '["aa","abc"]'
        msg = _response(with_txs(wire_of(0), bad))

    def old_receive(r):
        """The reactor as it was: the whole message through cloads."""
        monkeypatch.setattr(
            encoding, "cloads_hex_array",
            lambda data, path: (encoding.cloads(data), None))
        try:
            return outcome(r.receive, 0x40, _Peer, msg)
        finally:
            monkeypatch.undo()

    r_old, r_new = _syncing_reactor(0), _syncing_reactor(0)
    want = old_receive(r_old)
    if how == "pure":       # undo() took the fixture's patches with it
        monkeypatch.setattr(native, "codec", lambda: None)
    monkeypatch.setattr(encoding, "_native_state", None)
    assert outcome(r_new.receive, 0x40, _Peer, msg) == want
    assert want[0] == "raised" or case in ("type named twice",
                                           "an unknown type")
    assert r_new.pool.requests[2].block is None
