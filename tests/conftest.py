"""Test configuration: force an 8-device virtual CPU mesh for all tests.

Tier-1 runs on the CPU: sharding and collective paths are validated on a
virtual CPU mesh (``--xla_force_host_platform_device_count=8``), Pallas
kernels through the interpreter. The ROADMAP's tier-1 command sets
``JAX_PLATFORMS=cpu``; the ``jax.config.update`` below pins the same choice
for a bare ``pytest`` on a machine that has a chip, where the suite would
otherwise take the chip and find one device instead of eight.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tier-1 stays off the persistent compilation cache (the package root
# points it at <checkout>/.jax_cache): executables loaded WARM from the
# disk cache computed garbage on the CPU backend of an earlier jaxlib
# (a fresh cache dir passed, every later process failed ~50% with
# corrupted store planes), and nobody has shown the installed jaxlib
# 0.9.0 free of it. Cold compiles are correct; recompiles are the price.
# Through the environment, so the child processes tests spawn inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The full suite compiles many hundreds of distinct XLA programs; past a
# threshold the in-process CPU compiler segfaults (observed twice at
# different tests, always inside backend_compile_and_load). Bound the
# live-executable arena by clearing jit caches between test modules.

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_arena():
    yield
    jax.clear_caches()


# ``tests/perfbench/`` belongs to the benchmark, and a PR that adds a cell
# may add files there and edit none. ``test_string_deli_62k.py`` fetches
# its cell as ``BENCH["workloads"][-1]``; the manifest's contract has every
# new cell appended last, so the assertion fails from the first cell added
# after it (PR 32's ``richtext-marks-62k-mesh4.typing``). What it holds is
# held by name in ``test_richtext_marks_62k_mesh4.py``. Strict: the marker
# turns red, and goes, when a ``benchmark`` PR looks the cell up by name.
_HOLDS_ITS_CELL_TO_LAST_PLACE = (
    "test_string_deli_62k.py::test_manifest_states_the_file")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_HOLDS_ITS_CELL_TO_LAST_PLACE):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts string-deli-62k.replay is the last entry "
                       "of workloads; new cells are appended after it"))

# Hard-exit machinery: full-suite runs have died in XLA's C++ teardown
# (atexit destructors) AFTER every test passed, eating the terminal
# summary and the exit status — CI could not prove the green run. The
# latest safe point to bail is pytest_unconfigure: by then the terminal
# reporter's sessionfinish wrapper has completed (failure recap,
# warnings, --durations, the stats line are all printed); os._exit then
# skips only the crashing interpreter teardown, preserving the status.
_exit_status = [None]


def pytest_sessionfinish(session, exitstatus):
    _exit_status[0] = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    import sys
    # os._exit skips ALL buffered-stream flushing: flush every stream the
    # terminal reporter may have written through (capture swaps sys.stdout,
    # so the summary text can sit in the ORIGINAL stream's buffer)
    try:
        config.get_terminal_writer().flush()
    except Exception:
        pass
    for f in (sys.stdout, sys.stderr, sys.__stdout__, sys.__stderr__):
        try:
            f.flush()
        except Exception:
            pass
    # sessionfinish never ran (startup failure before the session): let
    # pytest's own error exit code through rather than forging a 0
    if _exit_status[0] is not None \
            and not os.environ.get("FLUID_NO_HARDEXIT"):
        os._exit(_exit_status[0])
