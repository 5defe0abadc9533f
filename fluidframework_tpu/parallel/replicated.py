"""shard_map'd replicated merge step: the multi-chip op-apply pipeline.

The TPU-native shape of the reference's server pipeline (SURVEY.md §3.5):

- **doc axis sharded** over the ``docs`` mesh axis (Deli's Kafka partitioning
  of documents);
- **sequenced op batches broadcast** to every replica with an ICI
  ``all_gather`` over the ``replica`` axis (the Broadcaster → Redis → client
  fan-out);
- every replica applies the same ops to its copy of the doc-shard state, and
- a **cross-replica digest check** (``pmax``/``pmin`` over the replica axis)
  asserts bit-identical convergence — the race-detection analog of the
  reference's eventual-consistency fuzz asserts (SURVEY.md §5.2).

Each replica *ingests* a disjoint 1/R slice of each doc's op batch (its
"front door" share); the all-gather reassembles the full, seq-ordered batch
on every replica before applying.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.merge_tree_kernel import (
    StringState, apply_string_batch, string_state_digest,
)
from ..ops.pallas_string_kernel import apply_string_batch_pallas
from .mesh import DOC_AXIS, REPLICA_AXIS

# state planes: (D, S) sharded over docs, replicated over replica axis
STATE_SPEC = P(DOC_AXIS, None)
COUNT_SPEC = P(DOC_AXIS)
# op planes as ingested: (D, O) with the op axis split over replicas
OPS_INGEST_SPEC = P(DOC_AXIS, REPLICA_AXIS)


def _state_specs() -> StringState:
    return StringState(
        seq=STATE_SPEC, client=STATE_SPEC, removed_seq=STATE_SPEC,
        removers=STATE_SPEC, length=STATE_SPEC, handle_op=STATE_SPEC,
        handle_off=STATE_SPEC, prop_val=P(DOC_AXIS, None, None),
        count=COUNT_SPEC, overflow=COUNT_SPEC,
    )


def make_replicated_step(mesh, with_props: bool = True,
                         use_pallas: bool = False, pallas_tile: int = 8,
                         pallas_interpret: bool = False,
                         inject_divergence: bool = False):
    """Build the jitted multi-chip step: (state, 7×(D,O) op planes) → (state,
    digests, replicas_agree). Op planes arrive sharded (docs, replica).

    ``use_pallas`` runs each shard's apply through the fused VMEM kernel
    (VERDICT r1 #1: the multi-chip path runs the production kernel) —
    annotate-free stores only; ``pallas_tile`` must divide the per-shard doc
    count. ``pallas_interpret`` exercises the same code path on the virtual
    CPU mesh.

    ``inject_divergence`` is a chaos hook (faultpoint lineage, PR 1): it
    skews each replica's digest by its replica index BEFORE the pmax/pmin
    agreement check, so the on-device race detector itself has to notice —
    the health plane's divergence counter and SLO path get exercised by a
    real disagreement, not a mocked flag."""

    # check_vma=False: after the all-gather the op batch is value-identical
    # across replicas but typed as replica-varying; the explicit pmax/pmin
    # digest agreement below is the (stronger, runtime) replication check.
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_state_specs(),) + (OPS_INGEST_SPEC,) * 7,
        out_specs=(_state_specs(), COUNT_SPEC, P()),
        check_vma=False,
    )
    def step(state, kind, a0, a1, a2, seq, client, ref_seq):
        # Broadcaster: reassemble the full sequenced batch on every replica
        # via ICI all-gather over the replica axis (tiled on the op axis).
        gather = lambda x: jax.lax.all_gather(
            x, REPLICA_AXIS, axis=1, tiled=True)
        full = tuple(gather(x) for x in (kind, a0, a1, a2, seq, client,
                                         ref_seq))
        if use_pallas:
            new_state = apply_string_batch_pallas(
                state, *full, tile=pallas_tile,
                interpret=pallas_interpret, with_props=with_props)
        else:
            new_state = apply_string_batch(state, *full,
                                           with_props=with_props)
        digest = string_state_digest(new_state)
        if inject_divergence:
            # chaos: make the replicas genuinely disagree so the check
            # below (and everything downstream of it) proves itself
            digest = digest + jax.lax.axis_index(REPLICA_AXIS).astype(
                digest.dtype)
        # race detection: every replica must hold bit-identical state
        hi = jax.lax.pmax(digest, REPLICA_AXIS)
        lo = jax.lax.pmin(digest, REPLICA_AXIS)
        agree_local = jnp.all(hi == lo)
        agree = jax.lax.pmin(
            jax.lax.pmin(agree_local.astype(jnp.int32), REPLICA_AXIS),
            DOC_AXIS)
        return new_state, digest, agree

    return jax.jit(step, donate_argnums=0)


def shard_state(state: StringState, mesh) -> StringState:
    """Place host state onto the mesh with the step's shardings."""
    specs = _state_specs()
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, specs)


def shard_ops(mesh, *planes):
    sh = NamedSharding(mesh, OPS_INGEST_SPEC)
    return tuple(jax.device_put(jnp.asarray(p), sh) for p in planes)


class OplogFollower:
    """Warm-standby replica trailing a leader engine through its durable
    oplog — the host-tier failover half of replication (the shard_map
    step above is the device tier).

    The follower owns a SECOND engine of the same family, anchored on a
    leader summary and sharing the leader's durable :class:`PartitionedLog`
    (the stand-in for both replicas consuming one Kafka topic).
    ``catch_up()`` reads each partition's new records past the follower's
    offsets, expands columnar batches, sorts by ``(doc, seq)`` (partition
    scan order is not chronological — same hazard ``_replay_tail``
    documents), and replays: sequencer state, resilience state (member
    set + dedup ledger), then the device apply queue. A per-doc
    applied-seq cursor makes replay idempotent, so racing the leader's
    appends is safe — a record seen twice is skipped by seq.

    ``promote()`` is the failover moment: one final catch-up (the leader
    is dead; the durable log is the complete record of everything it
    acked), then the follower's engine IS the leader — same digests as a
    never-failed run over the same ops, by the determinism invariant the
    chaos drills pin. Promotion counts ``failover_promotions_total`` and
    notes the flight recorder so a post-mortem shows when authority
    moved.
    """

    def __init__(self, leader, family: str = "string",
                 summary: Optional[dict] = None):
        from ..testing.chaos import engine_class
        self.family = family
        self.log = leader.log
        summary = summary if summary is not None else leader.summarize()
        self.engine = engine_class(family).load(summary, self.log)
        # everything up to the current sequencer state replayed at load;
        # new records land past these cursors
        self._offsets = [self.log.size(p)
                         for p in range(self.log.n_partitions)]
        self._applied: dict = {}
        for doc_id in list(self.engine._doc_rows):
            self._applied[doc_id] = self.engine.deli.doc_seq(doc_id)
        self.promoted = False
        self.caught_up_ops = 0

    def catch_up(self) -> int:
        """Drain the leader's log tail into the follower; returns the
        number of newly applied messages. Idempotent per (doc, seq)."""
        from ..core.protocol import MessageType
        tail = []
        for p in range(self.log.n_partitions):
            size = self.log.size(p)
            if size <= self._offsets[p]:
                continue
            for rec in self.log.read(p, from_offset=self._offsets[p],
                                     to_offset=size):
                tail.extend(rec.expand() if hasattr(rec, "expand")
                            else (rec,))
            self._offsets[p] = size
        tail.sort(key=lambda m: (m.doc_id, m.seq))
        eng = self.engine
        n = 0
        for msg in tail:
            if msg.seq <= self._applied.get(msg.doc_id, 0):
                continue    # raced an already-replayed record: skip
            eng.deli.replay(msg)
            eng._absorb_resilience(msg)
            if msg.type == MessageType.OP:
                eng._enqueue(msg.doc_id, msg)
                eng._set_min_seq(msg.doc_id, max(
                    eng._min_seq.get(msg.doc_id, 0), msg.min_seq))
            self._applied[msg.doc_id] = msg.seq
            n += 1
        if n:
            eng._queue.sort(key=lambda dm: dm[1].seq)
            eng.flush()
        self.caught_up_ops += n
        return n

    def promote(self):
        """Fence the deposed leader, final catch-up from its durable log,
        then hand the engine over as the new authority.

        Order matters (ISSUE 10): the fence bump comes FIRST, so a
        not-actually-dead leader cannot land an append after the final
        catch-up read — anything it tries past this point raises
        ``FencedWriterError`` instead of silently extending a stream the
        follower already took over."""
        from ..utils import flight_recorder, telemetry
        new_epoch = self.engine.acquire_write_authority()
        n = self.catch_up()
        self.promoted = True
        telemetry.REGISTRY.inc("failover_promotions_total")
        flight_recorder.note("failover_promotion", family=self.family,
                             final_catchup_ops=n,
                             total_ops=self.caught_up_ops,
                             epoch=-1 if new_epoch is None else new_epoch)
        return self.engine


class ReplicaSetMetrics:
    """Health-plane rollup for a replicated mesh (ISSUE 4 piece 3).

    One labeled collector per replica rank attaches to the global
    registry (``ReplicaSet{replica=r}``), so the Prometheus exposition
    carries per-replica series instead of one anonymous blob. Digest
    agreement — the only race detector this stack has at scale — becomes
    a first-class signal: a disagreeing step increments
    ``replica_digest_divergence_total`` on the PROCESS registry (it is a
    property of the set, not a replica), warns through telemetry, and
    notes the flight recorder so a later crash dump carries the first
    divergence, not just the assertion that followed it.
    """

    def __init__(self, mesh, name: str = "ReplicaSet",
                 registry=None, logger=None):
        from ..utils import telemetry
        self.registry = registry if registry is not None \
            else telemetry.REGISTRY
        self.logger = logger if logger is not None \
            else telemetry.TelemetryLogger(namespace="replicaSet")
        self.n_replicas = int(mesh.shape.get(REPLICA_AXIS, 1))
        #: rank -> per-replica collector, attached with replica= labels
        self.per_replica = []
        for r in range(self.n_replicas):
            coll = telemetry.MetricsCollector()
            self.registry.attach(name, coll, labels={"replica": r})
            self.per_replica.append(coll)
        self.steps = 0
        self.divergences = 0

    def on_step(self, agree, n_ops: int) -> bool:
        """Account one replicated step: ``agree`` is the step's 0/1
        agreement scalar (device or host), ``n_ops`` the batch's op-slot
        count per replica. Returns the bool agreement."""
        ok = bool(agree)
        self.steps += 1
        for coll in self.per_replica:
            coll.inc("ops_applied", n_ops)
            coll.set_gauge("digest_agree", 1.0 if ok else 0.0)
        self.registry.set_gauge("digest_parity", 1.0 if ok else 0.0)
        if not ok:
            self.divergences += 1
            self.registry.inc("replica_digest_divergence_total")
            self.logger.send_warning(
                "replica_digest_divergence", step=self.steps,
                n_replicas=self.n_replicas)
            from ..utils import flight_recorder
            flight_recorder.note("replica_digest_divergence",
                                 step=self.steps,
                                 n_replicas=self.n_replicas)
        return ok
