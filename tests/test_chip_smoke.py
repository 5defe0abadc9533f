"""ISSUE 21: the chip smoke's body, tiny, on the CPU — plus the bring-up
contracts around it: a requested native sequencer is native or an error,
native libraries are a function of their sources, the compile cache can be
placed from outside, and the root script refuses to run without a TPU."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from fluidframework_tpu.native import build
from fluidframework_tpu.server import native_deli, serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(not native_deli.available(),
                    reason="native sequencer unavailable")
def test_door_smoke_body_tiny_interpret(tmp_path):
    """sockets → door → C++ sequencer → native log → Pallas (interpreter)
    merge → acks → oracle parity → summary reload → kernel-vs-scan parity:
    the same function ``chip_smoke.py`` runs at deployment size."""
    from fluidframework_tpu.testing.door_smoke import run_door_smoke
    found = run_door_smoke(
        str(tmp_path / "oplog"), n_docs=32, capacity=128, n_clients=4,
        waves=(2, 2, 1), n_shared=2, n_sampled=4, seed=3,
        pallas="interpret", parity_shapes=((128, True, 32),),
        parity_ops=8, ack_timeout_s=120)
    assert found["native"] == {"sequencer": "NativeDeliAdapter",
                               "log": "NativePartitionedLog",
                               "decode": "native"}
    assert found["ops_acked"] >= 32 * 5 and found["windows"] > 0
    assert found["pallas"]["no_props"] == found["pallas"]["props"] == \
        {"tile": 32, "interpret": True}
    assert found["oracle_parity"]["docs"] >= 4 + 2
    assert found["oracle_parity"]["chars"] > 0
    assert found["reload_digest_equal"]
    assert [p["parity"] for p in found["pallas_parity"]] == [True, True]
    c = found["compile"]
    assert c["store_jax_compiles"] >= c["unpack_programs"] \
        >= c["unpack_distinct_R"] >= 1
    json.dumps(found)       # the root script prints it as a JSON line


def test_make_sequencer_native_is_native_or_an_error(monkeypatch):
    def no_toolchain():
        raise build.NativeBuildError("cannot build libdeli.so: g++: boom")

    monkeypatch.setattr(native_deli, "_load", no_toolchain)
    assert not native_deli.available()
    with pytest.raises(build.NativeBuildError, match="boom"):
        serving.make_sequencer("native")
    with pytest.raises(build.NativeBuildError):
        serving.StringServingEngine(n_docs=2, capacity=16,
                                    sequencer="native")
    assert type(serving.make_sequencer("python")).__name__ == \
        "DeliSequencer"
    with pytest.raises(ValueError):
        serving.make_sequencer("natve")


def test_native_build_is_a_function_of_source_content(tmp_path, monkeypatch):
    src = tmp_path / "ingress.cpp"
    shutil.copy(os.path.join(build.HERE, "ingress.cpp"), src)
    first = build.ensure_built("libingress.so", src_dir=str(tmp_path))
    assert os.path.exists(first)
    assert build.ensure_built("libingress.so",
                              src_dir=str(tmp_path)) == first
    # same mtime, different bytes: an mtime rule would keep the stale .so
    st = os.stat(src)
    src.write_text(src.read_text() + "\n// edited\n")
    os.utime(src, ns=(st.st_atime_ns, st.st_mtime_ns))
    second = build.ensure_built("libingress.so", src_dir=str(tmp_path))
    assert second != first and os.path.exists(second)
    assert not os.path.exists(first), "the stale build must not linger"

    # a compiler that refuses: its stderr is in the error, nothing is kept
    src.write_text("this is not C++\n")
    with pytest.raises(build.NativeBuildError, match="error"):
        build.ensure_built("libingress.so", src_dir=str(tmp_path))
    # no compiler at all: the error names the command that was tried
    src.write_text("// another version\n")
    monkeypatch.setattr(build, "CXX", "no-such-compiler-xyz")
    with pytest.raises(build.NativeBuildError,
                       match="no-such-compiler-xyz"):
        build.ensure_built("libingress.so", src_dir=str(tmp_path))


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch):
    import fluidframework_tpu as pkg
    before = jax.config.jax_compilation_cache_dir
    try:
        # set: JAX itself read the variable at import; the package must
        # not name another directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
        importlib.reload(pkg)
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        # unset: a fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        importlib.reload(pkg)
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # and tier-1 itself stays off the persistent cache (conftest)
    assert not jax.config.jax_enable_compilation_cache


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{")
                   for line in proc.stdout.splitlines())
