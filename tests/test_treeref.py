"""benchmark/treeref.py, the state tree's plain reference, against the
program's StateTree: the same hashes at every version, each side's
proofs under the other's verifier, and the forgeries both refuse."""

import ast
import json
import os
import random

import pytest

from benchmark import treeref
from tendermint_tpu import statetree
from tendermint_tpu.statetree import StateTree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_reference_imports_nothing_of_the_program():
    for name in ("treeref.py", "ycsb.py", "ycsbgen.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = [a.name for a in node.names] \
                if isinstance(node, ast.Import) else \
                [node.module or ""] if isinstance(node, ast.ImportFrom) \
                else []
            assert not any(n.split(".")[0] == "tendermint_tpu"
                           for n in names), (name, names)


def world(seed: int, n: int):
    rng = random.Random(seed)
    pairs = [(b"key-%d-%d" % (seed, i), rng.randbytes(rng.randrange(0, 60)))
             for i in range(n)]
    tree = StateTree()
    tree.load(pairs)
    return rng, pairs, tree, treeref.PlainTree(pairs)


@pytest.mark.parametrize("seed, n", [(1, 1), (2, 2), (3, 9), (4, 257),
                                     (5, 2000)])
def test_roots_are_equal_at_every_version(seed, n):
    rng, pairs, tree, ref = world(seed, n)
    assert tree.commit(0) == ref.app_hash()
    for version in range(1, 9):
        txs = []
        for _ in range(rng.randrange(0, 40)):
            key = pairs[min(n - 1, int(rng.expovariate(8.0 / n)))][0]
            value = rng.randbytes(rng.randrange(0, 50)).replace(b"=", b"-")
            txs.append(key + b"=" + value)
            tree.set(key, value)
        assert tree.commit(version) == ref.apply_block(txs)
        assert ref.get(pairs[0][0]) == tree.get(pairs[0][0])


@pytest.mark.parametrize("seed, n", [(6, 1), (7, 3), (8, 500)])
def test_each_sides_proofs_verify_under_the_others_verifier(seed, n):
    rng, pairs, tree, ref = world(seed, n)
    app_hash = tree.commit(0)
    for key in [k for k, _ in rng.sample(pairs, min(n, 20))] + \
            [b"absent-%d" % i for i in range(5)]:
        value, proof = tree.prove(key, 0)
        wire = statetree.proof_to_bytes(proof)
        assert wire == ref.prove(key)               # byte for byte
        assert treeref.verify(wire, key, value, app_hash) == \
            (value is not None)
        statetree.verify(statetree.proof_from_bytes(ref.prove(key)), key,
                         value, ref.app_hash())


def forged(wire: bytes, **changes) -> bytes:
    doc = dict(json.loads(wire), **changes)
    return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()


def both_reject(wire, key, value, app_hash) -> bool:
    try:
        statetree.verify(statetree.proof_from_bytes(wire), key, value,
                         app_hash)
        return False
    except statetree.ProofError:
        pass
    with pytest.raises(treeref.Rejected):
        treeref.verify(wire, key, value, app_hash)
    return True


def test_forgeries_are_rejected_by_both_verifiers():
    rng, pairs, tree, ref = world(9, 300)
    app_hash = tree.commit(0)
    key, value = pairs[17]
    wire = ref.prove(key)
    assert treeref.verify(wire, key, value, app_hash) is True
    doc = json.loads(wire)
    for at in (0, len(doc["steps"]) - 1):
        steps = [list(s) for s in doc["steps"]]
        raw = bytearray(bytes.fromhex(steps[at][1]))
        raw[31] ^= 0x80
        steps[at][1] = raw.hex()
        assert both_reject(forged(wire, steps=steps), key, value, app_hash)
    assert both_reject(wire, key, value + b"!", app_hash)       # wrong value
    assert both_reject(wire, pairs[18][0], value, app_hash)     # another key
    assert both_reject(wire, key, value, ref.apply_block(
        [pairs[3][0] + b"=moved"]))                             # older root
    # an absent key claimed present, with a value and with its
    # neighbour's leaf as its own
    absent = ref.prove(b"nobody")
    assert treeref.verify(absent, b"nobody", None, ref.app_hash()) is False
    assert both_reject(absent, b"nobody", b"here", ref.app_hash())
    assert both_reject(forged(absent, present=True), b"nobody", b"here",
                       ref.app_hash())
    assert both_reject(forged(absent, n_keys=299), b"nobody", None,
                       ref.app_hash())
    with pytest.raises(treeref.Rejected):
        treeref.verify(b"not json", key, value, app_hash)


def test_workload_a_inserts_nothing():
    _rng, _pairs, _tree, ref = world(10, 20)
    with pytest.raises(KeyError):
        ref.apply_block([b"new-key=1"])
