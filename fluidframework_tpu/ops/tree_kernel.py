"""Batched SharedTree op-apply kernel: the tree DDS on device.

Reference counterpart: ``@fluidframework/tree`` — upstream's largest DDS
(SURVEY.md §2.6); host oracle: ``models.shared_tree`` (the merge-rule spec).
The oracle's id-anchored design was chosen FOR this kernel (its module
docstring promises the "node-id-indexed struct-of-arrays table" built here):
because every edit targets stable node ids, the device never resolves
positions — merge is total-order apply of id math.

Representation (D docs × N node slots, all int32):

- ``node_id``   interned id handle (0 = free slot). Slot position carries NO
  meaning — sibling ORDER lives in a doubly-linked list (``prev_sib`` /
  ``next_sib`` id handles, 0 = end), so an insert-after is a pointer splice
  (three one-hot writes), never a shift, and the struct never moves.
- ``parent`` / ``field``   attachment (id handle / field-name handle).
- ``value`` / ``type_``    LWW value handle / node type handle.
- ``created_seq``          the sequenced op that created the slot — the
  nested-insert dependency test (below).

Merge rules ON DEVICE (bit-for-bit the oracle's):

- insert: parent must exist; id must be absent; a dead/foreign ``after``
  anchor (not a live sibling under (parent, field)) degrades to
  start-of-field; later-sequenced concurrent inserts land closer to the
  anchor (list-head splice order gives this for free).
- remove: detach + delete the whole subtree — transitive closure by
  iterative parent-marking (an (N×N) masked compare per wave, no gathers);
  root immutable.
- move: dropped if node/destination missing or the destination lies inside
  the moved subtree (cycle); else splice out + splice in.
- setValue: last-sequenced-writer-wins (scan order is seq order).

Group atomicity WITHOUT cross-record control flow:

- A multi-node/nested insert expands host-side into per-node records that
  share the op's seq. ``INS_BEGIN`` resets the per-doc ``ok_ins`` flag;
  ``INS_GUARD_ABSENT(id)`` ANDs it with "id is absent" (one per top-level
  spec node — any collision drops the whole insert, as the oracle does).
  A NESTED record additionally requires its parent slot's
  ``created_seq == seq`` — "my parent was created by THIS op" — which
  reproduces the oracle's skip-the-subtree rule when a nested id survived
  elsewhere.
- A transaction wraps its sub-edits with ``TXN_BEGIN`` +
  ``TXN_GUARD_EXISTS(id)`` records gating a second flag ``ok_txn``; every
  record in the group applies only when both flags hold, so a failed
  constraint drops the group atomically while admitted sub-edits still
  degrade individually.

Capacity: an insert finding no free slot sets the doc's sticky overflow
flag and leaves the doc unchanged (same escape hatch as the string kernel).
"""

from __future__ import annotations

import dataclasses
import enum
import functools

import jax
import jax.numpy as jnp


class TreeOpKind(enum.IntEnum):
    NOOP = 0
    INS_BEGIN = 1         # reset ok_ins
    INS_GUARD_ABSENT = 2  # ok_ins &= (node absent)
    TXN_BEGIN = 3         # reset ok_txn AND ok_ins
    TXN_GUARD_EXISTS = 4  # ok_txn &= (node present)
    INSERT = 5            # node,parent,after,field,value,type_; meta bit 0:
    #                       nested (require parent.created_seq == seq)
    REMOVE = 6            # node
    MOVE = 7              # node,parent,after,field
    SET_VALUE = 8         # node,value
    # "solo" kinds: a COMPLETE one-record op — same math as the base kind
    # (solo − 4) but ignoring the group flags (a standalone edit's implicit
    # TXN_BEGIN reset would make ok == 1 anyway). They exist so the volume
    # paths (flat inserts, standalone removes/sets) cost ONE scan step per
    # op instead of a begin/guard preamble. Never valid inside a
    # transaction group (they would bypass its constraint gate).
    INSERT_SOLO = 9
    REMOVE_SOLO = 10
    MOVE_SOLO = 11
    SET_SOLO = 12
    # fused TXN_BEGIN + TXN_GUARD_EXISTS(node): resets both flags, then
    # ok_txn &= exists — the first constraint of every transaction rides
    # its begin record (one record less on the wire per transaction)
    TXN_BEGIN_EXISTS = 13


META_NESTED = 1

ROOT_HANDLE = 1  # every doc's root node id handle (host interner reserves it)

_TREE_PLANES = ("node_id", "parent", "field", "value", "type_",
                "prev_sib", "next_sib", "created_seq")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TreeState:
    node_id: jax.Array      # (D, N) id handle, 0 = free
    parent: jax.Array       # (D, N) parent id handle (0 for root)
    field: jax.Array        # (D, N) field handle
    value: jax.Array        # (D, N) value handle
    type_: jax.Array        # (D, N) type handle
    prev_sib: jax.Array     # (D, N) id handle, 0 = field head
    next_sib: jax.Array     # (D, N) id handle, 0 = field tail
    created_seq: jax.Array  # (D, N)
    overflow: jax.Array     # (D,) sticky

    @staticmethod
    def create(n_docs: int, capacity: int) -> "TreeState":
        z = lambda: jnp.zeros((n_docs, capacity), jnp.int32)
        st = TreeState(node_id=z(), parent=z(), field=z(), value=z(),
                       type_=z(), prev_sib=z(), next_sib=z(),
                       created_seq=z(),
                       overflow=jnp.zeros((n_docs,), jnp.int32))
        # slot 0 of every doc is the root
        st.node_id = st.node_id.at[:, 0].set(ROOT_HANDLE)
        return st


# ----------------------------------------------------------- single-doc math
# All helpers operate on one doc's (N,) planes in dict ``s`` (+ scalar
# carry flags) and are vmapped over the doc axis by the batch step.

def _exists(s, nid):
    """Is id handle ``nid`` present (and non-zero)?"""
    return (nid != 0) & jnp.any(s["node_id"] == nid)


def _slot_value(s, nid, plane):
    """plane[slot_of(nid)] via one-hot reduction (0 when absent)."""
    return jnp.sum(jnp.where(s["node_id"] == nid, s[plane], 0))


def _write_at_id(s, nid, plane, val):
    """plane[slot_of(nid)] = val (no-op when absent)."""
    return jnp.where(s["node_id"] == nid, val, s[plane])


def _subtree_mask(s, nid):
    """(N,) bool: slots inside the subtree rooted at id ``nid``.

    Iterative wave expansion: a slot joins when its parent's id is already
    marked. Each wave is one (N×N) masked compare — gather-free — and the
    loop runs until a wave adds nothing (≤ depth waves)."""
    live = s["node_id"] != 0
    mark0 = live & (s["node_id"] == nid)

    def cond(carry):
        mark, changed = carry
        return changed

    def body(carry):
        mark, _ = carry
        # parent[i] ∈ marked ids ⇔ ∃j: marked[j] & node_id[j] == parent[i]
        hit = jnp.any(mark[None, :] & (s["node_id"][None, :] ==
                                       s["parent"][:, None]), axis=1)
        new = mark | (live & hit & (s["parent"] != 0))
        return (new, jnp.any(new != mark))

    mark, _ = jax.lax.while_loop(cond, body, (mark0, jnp.any(mark0)))
    return mark


def _splice_out(s, nid):
    """Unlink ``nid`` from its sibling list: neighbors bridge over it, and
    its own attachment planes reset (a detached node must not match any
    head/anchor search on the intermediate state)."""
    prev = _slot_value(s, nid, "prev_sib")
    nxt = _slot_value(s, nid, "next_sib")
    me = s["node_id"] == nid
    out = dict(s)
    # next[prev] = next ; prev[next] = prev (one-hot writes, 0-guarded)
    out["next_sib"] = jnp.where((s["node_id"] == prev) & (prev != 0), nxt,
                                s["next_sib"])
    out["prev_sib"] = jnp.where((s["node_id"] == nxt) & (nxt != 0), prev,
                                s["prev_sib"])
    for k in ("parent", "field", "prev_sib", "next_sib"):
        out[k] = jnp.where(me, 0, out[k])
    return out, prev, nxt


def _head_of(s, parent, field):
    """Id handle of the first child in (parent, field), else 0."""
    is_head = (s["node_id"] != 0) & (s["parent"] == parent) & \
        (s["field"] == field) & (s["prev_sib"] == 0)
    return jnp.sum(jnp.where(is_head, s["node_id"], 0))


def _attach(s, nid, parent, field, after):
    """Splice ``nid`` (already materialized in a slot) into the sibling
    list: after a live same-(parent, field) anchor, else at field head."""
    anchor_ok = (after != 0) & _exists(s, after) & \
        (_slot_value(s, after, "parent") == parent) & \
        (_slot_value(s, after, "field") == field)
    prev = jnp.where(anchor_ok, after, 0)
    nxt = jnp.where(anchor_ok, _slot_value(s, after, "next_sib"),
                    _head_of(s, parent, field))
    nxt = jnp.where(nxt == nid, 0, nxt)  # self-link guard (fresh head)
    out = dict(s)
    me = out["node_id"] == nid
    out["parent"] = jnp.where(me, parent, out["parent"])
    out["field"] = jnp.where(me, field, out["field"])
    out["prev_sib"] = jnp.where(me, prev, out["prev_sib"])
    out["next_sib"] = jnp.where(me, nxt, out["next_sib"])
    # neighbors point at me
    out["next_sib"] = jnp.where((out["node_id"] == prev) & (prev != 0), nid,
                                out["next_sib"])
    out["prev_sib"] = jnp.where((out["node_id"] == nxt) & (nxt != 0), nid,
                                out["prev_sib"])
    return out


def _apply_insert(s, node, parent, after, field, value, type_, seq, nested,
                  ok):
    parent_ok = _exists(s, parent) | (parent == ROOT_HANDLE)
    dep_ok = jnp.where(
        nested, _slot_value(s, parent, "created_seq") == seq,
        True)
    do = ok & parent_ok & ~_exists(s, node) & dep_ok & (node != 0)

    free = (s["node_id"] == 0)
    n = s["node_id"].shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)[0]
    slot = jnp.min(jnp.where(free, idx, n))
    would_overflow = do & (slot >= n)
    do = do & (slot < n)

    is_slot = (idx == slot) & do
    out = dict(s)
    out["node_id"] = jnp.where(is_slot, node, s["node_id"])
    out["value"] = jnp.where(is_slot, value, s["value"])
    out["type_"] = jnp.where(is_slot, type_, s["type_"])
    out["created_seq"] = jnp.where(is_slot, seq, s["created_seq"])
    out["prev_sib"] = jnp.where(is_slot, 0, s["prev_sib"])
    out["next_sib"] = jnp.where(is_slot, 0, s["next_sib"])
    out["parent"] = jnp.where(is_slot, 0, s["parent"])
    out["field"] = jnp.where(is_slot, 0, s["field"])
    attached = _attach(out, node, parent, field, after)
    out = {k: jnp.where(do, attached[k], s[k]) for k in _TREE_PLANES}
    return out, would_overflow


def _apply_remove(s, node, ok):
    do = ok & _exists(s, node) & (node != ROOT_HANDLE)
    mask = _subtree_mask(s, node)
    spliced, _, _ = _splice_out(s, node)
    out = {}
    for k in _TREE_PLANES:
        cleared = jnp.where(mask, 0, spliced[k])
        out[k] = jnp.where(do, cleared, s[k])
    return out


def _apply_move(s, node, parent, after, field, ok):
    in_subtree = jnp.any(_subtree_mask(s, node) &
                         (s["node_id"] == parent))
    do = ok & _exists(s, node) & (node != ROOT_HANDLE) & \
        _exists(s, parent) & ~in_subtree
    spliced, _, _ = _splice_out(s, node)
    attached = _attach(spliced, node, parent, field, after)
    return {k: jnp.where(do, attached[k], s[k]) for k in _TREE_PLANES}


def _apply_set_value(s, node, value, ok):
    do = ok & _exists(s, node)
    out = dict(s)
    out["value"] = jnp.where(do & (s["node_id"] == node), value, s["value"])
    return out


# ------------------------------------------------------------- batched apply

def _one_record(c, k, solo, nd, pa, af, fi, va, ty, sq, me, *, structural):
    """Apply one record to one doc's planes. ``k`` is the BASE kind (solo
    already folded); ``structural`` statically includes the remove/move
    subtree math — the batch step gates it behind a column-level cond so
    insert/set-heavy batches never pay the (N×N) subtree walks."""
    s = {key: c[key] for key in _TREE_PLANES}
    begin = (k == TreeOpKind.TXN_BEGIN) | \
        (k == TreeOpKind.TXN_BEGIN_EXISTS)
    ok_ins = jnp.where((k == TreeOpKind.INS_BEGIN) | begin, 1, c["ok_ins"])
    ok_txn = jnp.where(begin, 1, c["ok_txn"])
    ok_ins = jnp.where(
        k == TreeOpKind.INS_GUARD_ABSENT,
        ok_ins & ~_exists(s, nd), ok_ins)
    ok_txn = jnp.where(
        (k == TreeOpKind.TXN_GUARD_EXISTS) |
        (k == TreeOpKind.TXN_BEGIN_EXISTS),
        ok_txn & _exists(s, nd), ok_txn)
    ok = (ok_ins & ok_txn).astype(bool) | solo

    ins, would_ovf = _apply_insert(
        s, nd, pa, af, fi, va, ty, sq, (me & META_NESTED) != 0,
        ok & (k == TreeOpKind.INSERT))
    sv = _apply_set_value(s, nd, va, ok & (k == TreeOpKind.SET_VALUE))
    if structural:
        rem = _apply_remove(s, nd, ok & (k == TreeOpKind.REMOVE))
        mov = _apply_move(s, nd, pa, af, fi, ok & (k == TreeOpKind.MOVE))

    out = {}
    for key in _TREE_PLANES:
        v = jnp.where(
            k == TreeOpKind.INSERT, ins[key],
            jnp.where(k == TreeOpKind.SET_VALUE, sv[key], s[key]))
        if structural:
            v = jnp.where(
                k == TreeOpKind.REMOVE, rem[key],
                jnp.where(k == TreeOpKind.MOVE, mov[key], v))
        out[key] = v
    out["overflow"] = jnp.where(
        (k == TreeOpKind.INSERT) & would_ovf, 1, c["overflow"])
    out["ok_ins"] = ok_ins
    out["ok_txn"] = ok_txn
    return out


def apply_tree_batch(state: TreeState, kind, node, parent, after, field,
                     value, type_, seq, meta) -> TreeState:
    """Apply a dense (D, O) batch of expanded tree records, per-doc in
    column order (the sequencer's total order); NOOP pads skip.

    Per record column the step dispatches one of three bodies via
    ``lax.cond``: all-NOOP columns (pow2 padding) are identity, columns
    with any remove/move run the full structural body, and everything
    else runs the light body (no subtree-mask while loops) — the batch
    only pays for the op classes it actually contains."""
    sd = {k: getattr(state, k) for k in _TREE_PLANES}
    sd["overflow"] = state.overflow
    sd["ok_ins"] = jnp.ones_like(state.overflow)
    sd["ok_txn"] = jnp.ones_like(state.overflow)

    def step(carry, op):
        k, nd, pa, af, fi, va, ty, sq, me = op
        solo = (k >= TreeOpKind.INSERT_SOLO) & (k <= TreeOpKind.SET_SOLO)
        base = jnp.where(solo, k - 4, k)
        heavy = jnp.any((base == TreeOpKind.REMOVE) |
                        (base == TreeOpKind.MOVE))
        any_op = jnp.any(k != TreeOpKind.NOOP)

        def run(structural):
            def go(c):
                return jax.vmap(functools.partial(
                    _one_record, structural=structural))(
                        c, base, solo, nd, pa, af, fi, va, ty, sq, me)
            return go

        out = jax.lax.cond(
            heavy, run(True),
            lambda c: jax.lax.cond(any_op, run(False), lambda c2: c2, c),
            carry)
        return out, None

    ops = tuple(x.T for x in (kind, node, parent, after, field, value,
                              type_, seq, meta))
    out, _ = jax.lax.scan(step, sd, ops)
    return TreeState(**{k: out[k] for k in _TREE_PLANES},
                     overflow=out["overflow"])


apply_tree_batch_jit = jax.jit(apply_tree_batch, donate_argnums=0)


def apply_tree_planes(state: TreeState, planes) -> TreeState:
    """Stacked-plane entry: ``planes`` is ONE (9, D, O) int32 buffer
    (kind, node, parent, after, field, value, type_, meta, seq) — a single
    contiguous host→device transfer per batch instead of nine."""
    return apply_tree_batch(
        state, planes[0], planes[1], planes[2], planes[3], planes[4],
        planes[5], planes[6], planes[8], planes[7])


apply_tree_planes_jit = jax.jit(apply_tree_planes, donate_argnums=0)


def apply_tree_wire(state: TreeState, cols, ids, vals, row, pos, base,
                    id_map, f_map, t_map, v_map, *, o: int) -> TreeState:
    """Compact-wire apply: width-coded record columns + batch-local table
    maps, expanded ON DEVICE (map gathers, dense scatter, per-record seq
    derivation). The host→device upload sits on the serving path (the
    PCIe link), so the wire ships ~a dozen bytes per record — the
    tree analog of the string path's width-coded wire profiles.

    - ``cols`` (R, 3) u8: kind | meta<<4 (meta bit 0 = nested, bit 1 =
      first-record-of-op), field_local, type_local
    - ``ids`` (R, 3) u16/u32: node/parent/after batch-local 1-based
      indices (u32 when the batch id table outgrows u16)
    - ``vals`` (R,) u16/u32: value batch-local index
    - ``row`` (R,) u16 / ``pos`` (R,) u8 or u16: dense scatter
      coordinates; ``pos == o`` (out of range) drops the record (R is
      pow2-padded)
    - ``base`` (D,) i32: each doc's FIRST op seq this batch (per-doc op
      seqs are consecutive within a batch, so per-record seq = base +
      running count of first-of-op bits − 1)
    - ``*_map`` i32: batch-local index → global interner handle
    """
    i32 = jnp.int32
    kind = (cols[:, 0] & 0xF).astype(i32)
    meta = (cols[:, 0] >> 4).astype(i32)
    field = f_map[cols[:, 1].astype(i32)]
    type_ = t_map[cols[:, 2].astype(i32)]
    node = id_map[ids[:, 0].astype(i32)]
    parent = id_map[ids[:, 1].astype(i32)]
    after = id_map[ids[:, 2].astype(i32)]
    value = v_map[vals.astype(i32)]
    d = state.node_id.shape[0]
    r, p = row.astype(i32), pos.astype(i32)
    stacked = jnp.stack([kind, node, parent, after, field, value, type_,
                         meta & 1], axis=0)              # (8, R)
    dense = jnp.zeros((8, d, o), i32).at[:, r, p].set(stacked,
                                                      mode="drop")
    first = jnp.zeros((d, o), i32).at[r, p].set((meta >> 1) & 1,
                                                mode="drop")
    seq = base[:, None] + jnp.cumsum(first, axis=1) - 1
    return apply_tree_batch(state, dense[0], dense[1], dense[2], dense[3],
                            dense[4], dense[5], dense[6], seq, dense[7])


apply_tree_wire_jit = jax.jit(apply_tree_wire, donate_argnums=0,
                              static_argnames=("o",))


@jax.jit
def gather_tree_rows_jit(state: TreeState, rows):
    """Fused device gather of selected doc rows (incremental summary)."""
    return tuple(getattr(state, k)[rows] for k in _TREE_PLANES) + \
        (state.overflow[rows],)


@functools.partial(jax.jit, donate_argnums=0)
def write_tree_rows_jit(state: TreeState, rows, *planes_and_overflow):
    """Overwrite selected doc rows (delta restore; duplicate padding
    rows scatter identical values — a no-op)."""
    updates = {k: getattr(state, k).at[rows].set(planes_and_overflow[i])
               for i, k in enumerate(_TREE_PLANES)}
    return TreeState(**updates,
                     overflow=state.overflow.at[rows].set(
                         planes_and_overflow[-1]))


def tree_state_digest(state: TreeState) -> jax.Array:
    """Per-doc structural digest, invariant to slot layout: mixes each live
    node's (id, parent, field, prev, value, type) — prev encodes sibling
    order, so equal digests mean equal trees."""
    live = state.node_id != 0
    mix = (state.node_id * 1000003 + state.parent * 8191 +
           state.field * 131071 + state.prev_sib * 524287 +
           state.value * 8209 + state.type_ * 127)
    return jnp.sum(jnp.where(live, mix, 0), axis=1) + \
        jnp.sum(live.astype(jnp.int32), axis=1)
