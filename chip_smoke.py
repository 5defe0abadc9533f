"""Chip smoke: BASELINE config #4's string deployment, once, through the door.

    python chip_smoke.py [--seed N] [--mesh N_CHIPS]

One process, which owns the chip. Refuses to start without a TPU. Builds
the native libraries from the committed ``.cpp``, serves 10,240 docs ×
capacity 512 behind ``ColumnarAlfred`` to 8 socket clients, and checks
what comes out against the Python oracle, a summary reload and the XLA
scan (``fluidframework_tpu/testing/door_smoke.py`` is the body; tier-1
runs it tiny on the CPU). Stdout is two JSON lines: the smoke's
observations, then ``{"ok": true, "device": {...}}`` and nothing else on
the last line; exit 0 only if every assertion held.
"""

import argparse
import importlib.metadata
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=0, metavar="N_CHIPS",
                    help="shard the store's docs over N chips of this host")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    print(f"chip_smoke: device {device}, versions {versions}",
          file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2

    from fluidframework_tpu.testing.door_smoke import run_door_smoke

    mesh = None
    if args.mesh:
        from fluidframework_tpu.parallel.sharded import make_doc_mesh
        assert len(jax.devices()) >= args.mesh, \
            f"--mesh {args.mesh} on a host with {len(jax.devices())} chips"
        mesh = make_doc_mesh(args.mesh)
    # the log of THIS run only: nothing an earlier run left is read
    log_dir = os.path.join(HERE, "chip_smoke_out", "oplog")
    shutil.rmtree(log_dir, ignore_errors=True)
    found = run_door_smoke(log_dir, seed=args.seed, mesh=mesh)
    if mesh is not None:
        assert found["sharding_devices"] == args.mesh, found
        used = [m["bytes_in_use"] for m in found["device_memory"]]
        assert max(used) <= 2 * min(used), \
            f"state not spread evenly over the mesh: bytes_in_use {used}"
    print(json.dumps({"versions": versions, "seed": args.seed, **found}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
