"""The chip smoke's CPU-checkable contract (chip_smoke.py itself only
runs on a TPU): it refuses a CPU before building anything, the compile
cache goes where utils/compile_cache says, device dispatches are
counted by kernel, a native extension is rebuilt when its sources
change, and legs B and C build their chains with the benchmark's
builders. No kernel is compiled here."""

import ast
import ctypes
import inspect
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_refuses_cpu_before_building_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""            # no result line, no leg output
    assert "needs a TPU" in proc.stderr and "cpu" in proc.stderr
    assert "Nothing was built or run" in proc.stderr


def test_smoke_builds_its_chains_with_the_benchmarks_builders():
    """chip_smoke.py keeps no chain builder of its own: legs B and C take
    their chains from benchmark/chain.py through the two helpers the legs
    call, and each forgery sits where its leg looks for the refusal. Two
    validators, three heights, the verifier on the host."""
    import chip_smoke
    from benchmark.drivers.sync import PEER_ID, drive, fresh_reactor
    from benchmark.spans import SpanLog
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    from tendermint_tpu.models.verifier import BatchVerifier
    from tendermint_tpu.types.block import Block

    assert not hasattr(chip_smoke, "lite_chain")
    imported = {node.module for node in ast.walk(ast.parse(
        inspect.getsource(chip_smoke))) if isinstance(node, ast.ImportFrom)}
    assert {"benchmark.chain", "benchmark.drivers.sync"} <= imported
    beside = {name[:-3] for name in os.listdir(REPO) if name.endswith(".py")}
    assert not {m.split(".")[0] for m in imported} & beside

    # leg B: 3 blocks and the sentinel; the commit FOR block 2 forged
    gen, wire, expect, fwire = chip_smoke.sync_chains(
        5, n_vals=2, n_txs=3, n_blocks=3, forged_blocks=2, forged_at=2)
    assert (len(wire), len(expect), len(fwire)) == (4, 4, 3)
    assert fwire[:2] == wire[:2] and fwire[2] != wire[2]
    genuine = Block.from_bytes(wire[2]).last_commit.precommits
    forged = Block.from_bytes(fwire[2]).last_commit.precommits
    differ = [i for i, (a, b) in enumerate(zip(genuine, forged))
              if a.signature != b.signature]
    assert differ == [5 % 2]
    assert forged[differ[0]].height == 2
    for chain, applied in ((wire, 3), (fwire, 1)):
        reactor = fresh_reactor(gen, BatchVerifier("python"), 4)
        drive(reactor, chain, SpanLog())
        reactor.stop()
        assert reactor.state.last_block_height == applied
        if chain is wire:
            assert reactor.state.app_hash == expect[3][1]
            assert not reactor.switch.stopped
        else:
            assert {p for p, _ in reactor.switch.stopped} == {PEER_ID}

    # leg C: 3 headers signed on the host; a header nobody signed at 2
    chain, fwire = chip_smoke.lite_chains(5, 3, 2, 3, 2, sign="host")
    assert fwire[0] == chain.wire[0] and fwire[2] == chain.wire[2]
    valset, fcs = chain.decode()
    certify_chain(chain.chain_id, fcs, trusted=valset)
    valset, forged = chain.decode(fwire)
    assert forged[1].signed_header.header.app_hash == b"\xff" * 32
    assert [v.signature for v in
            forged[1].signed_header.commit.precommits] == [
        v.signature for v in fcs[1].signed_header.commit.precommits]
    with pytest.raises(CertificationError, match="^height 2:"):
        certify_chain(chain.chain_id, forged, trusted=valset)


def test_compile_cache_rule(monkeypatch):
    import jax

    from tendermint_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))

    # set from outside: used as is, nothing set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable() == "/somewhere/else"
    assert updates == []

    # unset on a TPU backend: the fixed path under the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert updates == [("jax_compilation_cache_dir", want)]

    # a CPU backend gets none, whether JAX_PLATFORMS rules the TPU out
    # or jax reports the backend
    del updates[:]
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.enable() is None
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compile_cache.enable() is None
    assert updates == []

    # a path that moves never hits: nothing process-local may name it
    src = inspect.getsource(compile_cache)
    for word in ("tempfile", "getpid", "time"):
        assert word not in src, word


def test_dispatches_are_counted_by_kernel(monkeypatch):
    """A CPU dispatch is recorded as the jnp ladder, per variant, with
    its shape's first-call seconds (the kernels are stubbed: the
    selection and the record are under test, not the ladder)."""
    from tendermint_tpu.ops import ed25519

    def stub(*args):
        return np.ones(args[0].shape[0], np.bool_)

    monkeypatch.setattr(ed25519, "_verify_from_bytes_jnp", stub)
    monkeypatch.setattr(ed25519, "_verify_pre_jnp", stub)
    monkeypatch.setattr(ed25519, "_verify_from_bytes_pallas", None)
    monkeypatch.setattr(ed25519, "_verify_pre_pallas", None)
    z = np.zeros((24, 32), np.uint8)
    s0 = ed25519.predecomp_stats()
    ed25519.verify_prepared_async(z, z, z, z)          # bucket 32: full
    # rows as a mesh hands them over, then a table's mirror and slots
    ed25519._dispatch("pre", None, z, z, z, np.zeros((24, 65), np.uint8))
    ed25519._dispatch("pre", None, z, z, z, np.zeros((64, 65), np.uint8),
                      np.zeros(24, np.int32))
    s1 = ed25519.predecomp_stats()
    assert s1["jnp_full"] == s0["jnp_full"] + 1
    assert s1["jnp_pre"] == s0["jnp_pre"] + 2
    for k in ("pallas_full", "pallas_pre", "mesh_jnp", "sign_pallas"):
        assert s1[k] == s0[k], k
    assert {"jnp_full[32]", "jnp_pre[24]"} <= set(s1["first_call_s"])

    # host signing off a TPU is counted, not silent
    sigs = ed25519.sign_batch([b"\x07" * 32], [b"smoke"])
    assert len(sigs) == 1 and len(sigs[0]) == 64
    assert ed25519.predecomp_stats()["sign_scalar"] == s0["sign_scalar"] + 1


def test_pallas_choice_is_the_platforms(monkeypatch):
    from tendermint_tpu.ops import ed25519
    assert ed25519._pallas_available() is False        # conftest: cpu
    monkeypatch.setattr(ed25519, "_platform", lambda: "tpu")
    assert ed25519._pallas_available() is True
    monkeypatch.setattr(ed25519, "_platform", lambda: "gpu")
    assert ed25519._pallas_available() is False


def test_native_extension_rebuilt_when_sources_change(tmp_path):
    from tendermint_tpu import native
    if not native.available():
        pytest.skip("no C++ toolchain")
    src = tmp_path / "probe.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    ext = native._Ext("_probe", str(src), cpython=False)
    first = ext.build_lib()
    assert os.path.basename(first).startswith("_probe.")
    assert ext.build_lib() == first                        # same sources: kept
    assert ctypes.CDLL(first).answer() == 1

    # a binary built from other sources, left in the directory (as a
    # copied tree leaves one), is neither loaded nor kept
    src.write_text('extern "C" int answer() { return 2; }\n')
    second = ext.build_lib()
    assert second != first and not os.path.exists(first)
    assert ctypes.CDLL(second).answer() == 2

    # a compiler that refuses the sources is an error on every use
    src.write_text("this is not C++\n")
    for _ in range(2):
        with pytest.raises(native.NativeBuildError):
            ext.ensure_loaded()
