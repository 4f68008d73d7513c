"""The chip smoke's CPU-checkable contract (chip_smoke.py itself only
runs on a TPU): it refuses a CPU before building anything, the compile
cache goes where utils/compile_cache says, device dispatches are
counted by kernel, and a native extension is rebuilt when its sources
change. No kernel is compiled here."""

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
    ed25519._dispatch("pre", None, z, z, np.ones(24, np.bool_), z, z, z)
    s1 = ed25519.predecomp_stats()
    assert s1["jnp_full"] == s0["jnp_full"] + 1
    assert s1["jnp_pre"] == s0["jnp_pre"] + 1
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
    monkeypatch.setenv("TM_TPU_NO_PALLAS", "1")
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
