"""Multi-chip replicated/sharded step on the virtual 8-device CPU mesh:
parity with the single-device kernel, replica agreement, compaction under
shardings, and the graft entry points."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluidframework_tpu.ops.merge_tree_kernel import (
    StringState, apply_string_batch, string_state_digest,
)
from fluidframework_tpu.parallel import (
    make_mesh, make_replicated_step, shard_state, shard_ops,
)
from fluidframework_tpu.testing.synthetic import typing_storm

ORDER = ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")


def planes_for(n_docs, n_ops, seed=0):
    planes, _ = typing_storm(n_docs, n_ops, seed=seed)
    return tuple(jnp.asarray(planes[k]) for k in ORDER)


def test_replicated_step_matches_single_device():
    mesh = make_mesh(8)  # (2 replicas, 4 doc shards)
    _, doc_shards = mesh.devices.shape
    n_docs, n_ops, cap = 4 * doc_shards, 8, 64
    ops = planes_for(n_docs, n_ops)

    single = apply_string_batch(StringState.create(n_docs, cap), *ops)
    ref_digest = np.asarray(string_state_digest(single))

    step = make_replicated_step(mesh)
    state = shard_state(StringState.create(n_docs, cap), mesh)
    new_state, digest, agree = step(state, *shard_ops(mesh, *ops))
    assert int(agree) == 1
    assert np.array_equal(np.asarray(digest), ref_digest)
    for plane in ("seq", "length", "handle_op", "handle_off", "removed_seq"):
        assert np.array_equal(np.asarray(getattr(new_state, plane)),
                              np.asarray(getattr(single, plane))), plane


def test_replicated_step_multiple_rounds():
    mesh = make_mesh(8)
    _, doc_shards = mesh.devices.shape
    n_docs, n_ops, cap = 2 * doc_shards, 8, 128
    step = make_replicated_step(mesh)
    state = shard_state(StringState.create(n_docs, cap), mesh)
    ref = StringState.create(n_docs, cap)
    seq = 1
    for r in range(3):
        planes, seq = typing_storm(n_docs, n_ops, seed=r, start_seq=seq)
        ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
        state, digest, agree = step(state, *shard_ops(mesh, *ops))
        ref = apply_string_batch(ref, *ops)
        assert int(agree) == 1
        assert np.array_equal(np.asarray(digest),
                              np.asarray(string_state_digest(ref)))


@pytest.mark.slow  # two full subprocess engine drills, ~9 min — the
def test_graft_entry_and_dryrun():  # driver runs dryrun_multichip itself
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", "/root/repo/__graft_entry__.py")
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    # the dryrun runs in its own PROCESS, exactly as the driver invokes
    # it (the engine drill is heavyweight; in-process it shares this
    # long-lived suite interpreter's jit caches and native-lib state)
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8")
               .strip())
    for n in (8, 4):
        proc = subprocess.run(
            [sys.executable, "-c",
             f"import __graft_entry__ as g; g.dryrun_multichip({n})"],
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            env=env, capture_output=True, text=True,
            timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "dryrun_multichip OK" in proc.stdout
