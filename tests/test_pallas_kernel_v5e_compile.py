"""The merge kernel compiled for the v5e at the benchmark's sizes, without
a chip: the TPU's compiler is installed here and compiles for a chip that
is described, not attached. What the Pallas interpreter cannot refuse —
a data-dependent block index with aliased outputs, a tile over the scoped
VMEM limit — Mosaic refuses here, at no chip time. Nothing runs: results
and times come from ``chip_smoke.py`` and the benchmark on the chip.

One file, and the topology inside a fixture: only one process at a time
may load the TPU's library, and the worker given this file is that one.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fluidframework_tpu.ops.merge_tree_kernel import StringState
from fluidframework_tpu.parallel import sharded

SLOTS = 512
TILE = 64   # what ``TensorStringStore._pallas_choice`` gives at 512 slots


@pytest.fixture(scope="module")
def mesh_of():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return lambda chips: Mesh(np.array(topo.devices[:chips]), ("docs",))


# the five cells' merges: population, chips, columns, props form, fused
@pytest.mark.parametrize("n_docs,chips,columns,props,fused", [
    (63488, 1, 4, False, False),    # string-deli-62k.replay, a wide window
    (63488, 1, 1, False, True),     # ... and the one the zamboni rides
    (10240, 1, 4, False, False),    # string-deli-10k.replay
    (10240, 1, 1, True, False),     # richtext-marks-10k.typing
    (10240, 4, 4, False, False),    # string-deli-10k-mesh4.replay
    (63488, 4, 1, True, False),     # richtext-marks-62k-mesh4.typing
    (63488, 4, 1, True, True),
], ids=lambda v: str(v))
def test_the_merge_compiles_for_the_v5e(mesh_of, n_docs, chips, columns,
                                        props, fused):
    mesh = mesh_of(chips)

    def shaped(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = jax.tree.map(
        shaped, jax.eval_shape(lambda: StringState.create(n_docs, SLOTS)),
        sharded.doc_state_specs())
    plane = shaped(jax.ShapeDtypeStruct((n_docs, columns), jnp.int32),
                   P("docs", None))
    floor = shaped(jax.ShapeDtypeStruct((n_docs,), jnp.int32), P("docs"))
    merge = sharded.sharded_merge(mesh, True, TILE, False, props, fused)
    args = (state, (plane,) * 7) + ((floor,) if fused else ())
    text = merge.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
