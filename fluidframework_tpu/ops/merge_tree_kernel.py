"""Batched MergeTree op-apply kernel — the north-star hot path on device.

Reference counterpart: ``@fluidframework/merge-tree`` ``MergeTree.
insertSegments`` / ``markRangeRemoved`` and the container-runtime ``processOp``
loop above them (SURVEY.md §2.1, §3.2). The reference walks a B-tree object
graph per op; here the *entire* merge — position resolution in the op's
(refSeq, client) perspective, concurrent-insert tie-break, segment splits,
tombstoning with overlapping removes — is (doc × op × segment) tensor math:
one ``lax.scan`` over the op axis (total order per doc is a hard data
dependency) with every document in the batch advanced in parallel per step.

Design invariants that make this tractable on a TPU:

- **Acked-only state.** The device holds sequenced state only; optimistic
  local ops, acks, and rebase live in the host client (``models``). With no
  pending segments, the reference's tie-break ("new segment goes after
  pending-local segments, before lower-seq acked ones") collapses to: *insert
  at the leftmost slot whose perspective-prefix equals the position* — every
  acked segment has seq < the incoming op's seq. Later-sequenced concurrent
  inserts therefore land left of earlier ones, exactly like the oracle.
- **Position-ordered dense slots.** Active segments occupy slots 0..n-1 in
  document order. An insert or split always shifts the tail of the slot
  arrays right by 1 or 2, so every plane update is a ``roll`` plus masked
  selects — pure vector passes, **no general gather/scatter** (dynamic
  gathers lower to scalar loops on TPU and measure ~1000× slower here).
  Scalar extractions (the containing slot's prefix) use one-hot masked
  reductions for the same reason; compaction sorts all planes together
  with a multi-operand ``lax.sort`` instead of argsort + gather.
- **Client indexes + remover bitmask.** Clients of a doc are interned to
  indexes 0..31 by the host; "removed by client c" (needed for perspectives
  whose refSeq predates the client's own removal) is one bit in an int32
  plane, supporting the reference's overlapping-remove client list.
- **Payload handles.** Text bytes never reach the device: segments carry
  (handle_op, handle_off, len); splits just offset the handle, and the host
  text table materializes strings on read. Markers are length-1 runs with a
  marker-table handle.

Capacity: S slots per doc. An op that would overflow S sets a sticky per-doc
overflow flag and leaves the doc unchanged; the host drains such docs through
the oracle and re-uploads after compaction (the gap-buffer escape hatch of
SURVEY.md §7 risk (b)).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.constants import NOT_REMOVED
from .schema import OpKind

MAX_CLIENTS = 32  # remover bitmask width (int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StringState:
    """Device-resident acked merge-tree state for D docs × S segment slots."""

    seq: jax.Array          # (D, S) int32 insert seq
    client: jax.Array       # (D, S) int32 inserting client index
    removed_seq: jax.Array  # (D, S) int32, NOT_REMOVED if live
    removers: jax.Array     # (D, S) int32 bitmask of removing client indexes
    length: jax.Array       # (D, S) int32 run length
    handle_op: jax.Array    # (D, S) int32 payload table id
    handle_off: jax.Array   # (D, S) int32 offset within the payload
    prop_val: jax.Array     # (D, S, K) int32 value handle per property key
    count: jax.Array        # (D,)  int32 active slot count
    overflow: jax.Array     # (D,)  int32 sticky overflow flag

    @staticmethod
    def create(n_docs: int, capacity: int, n_props: int = 4) -> "StringState":
        """n_props: K property-key planes for annotate (per-key LWW). Keys
        are host-interned to plane indexes; a store needing more distinct
        keys than K must be created wider (static shape)."""
        z = lambda fill=0: jnp.full((n_docs, capacity), fill, dtype=jnp.int32)
        return StringState(
            seq=z(), client=z(), removed_seq=z(NOT_REMOVED), removers=z(),
            length=z(), handle_op=z(), handle_off=z(),
            prop_val=jnp.zeros((n_docs, capacity, n_props), jnp.int32),
            count=jnp.zeros((n_docs,), jnp.int32),
            overflow=jnp.zeros((n_docs,), jnp.int32),
        )


# ----------------------------------------------------------- single-doc math
# All helpers below operate on ONE document (S-vectors) and are vmapped over
# the doc axis by the batch step.

def _iota(n):
    """(n,) int32 index vector built from a 2-D iota: usable both as a plain
    XLA constant and inside Pallas kernels (Mosaic rejects 1-D iota, and
    pallas_call rejects captured trace-time constants like jnp.arange)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)[0]


def _active(s, S):
    return _iota(S) < s["count"]


def _prop_keys(s):
    """The per-key property planes of a state dict, in key-plane order.

    Properties live as K separate 2-D (S,) / (D, S) planes named
    ``prop0..propK-1`` — NOT one (S, K) array: a tiny minor dim gets
    lane-padded to 128 in TPU vector layouts, which both bloats VMEM ~32×
    and blocks Mosaic's i1 reshapes. The XLA entry points split/restack
    the state's (D, S, K) ``prop_val`` at the boundary."""
    return tuple(f"prop{i}" for i in range(len(s))
                 if f"prop{i}" in s)


def _visible(s, ref_seq, client_idx):
    S = s["seq"].shape[0]
    ins = (s["seq"] <= ref_seq) | (s["client"] == client_idx)
    rem = (s["removed_seq"] <= ref_seq) | \
          (((s["removers"] >> jnp.clip(client_idx, 0, MAX_CLIENTS - 1)) & 1)
           .astype(bool) & (client_idx >= 0))
    return _active(s, S) & ins & ~rem


def _cumsum(x):
    """Hillis-Steele inclusive prefix sum along the last axis via static
    shifts. Equivalent to ``jnp.cumsum`` but built from roll/where/add so it
    also lowers inside Pallas kernels (Mosaic has no cumsum primitive)."""
    S = x.shape[-1]
    idx = _iota(S)
    step = 1
    while step < S:
        x = x + jnp.where(idx >= step, jnp.roll(x, step, axis=-1), 0)
        step *= 2
    return x


def _prefix(s, vis):
    pl = jnp.where(vis, s["length"], 0)
    cum = _cumsum(pl)
    return cum - pl, cum - pl + pl  # (exclusive prefix, inclusive end)


_PLANES = ("seq", "client", "removed_seq", "removers", "length",
           "handle_op", "handle_off")


def _insert_one(s, pos, length, handle, seq, client_idx, ref_seq,
                with_props=True):
    """Apply one insert to one doc (S-vector planes in dict s).

    Gather-free: the result is ``s`` below the cut slot, ``roll(s, 1)``
    (boundary insert) or ``roll(s, 2)`` (split) above it, with the new
    segment written at the cut and the split's right piece fixed up in
    place — ``roll(s, 2)`` already carries the containing slot's values
    to the right-piece position. Wrapped roll values only ever land on
    slots that are overwritten or beyond ``count``.
    """
    S = s["seq"].shape[0]
    i = _iota(S)
    vis = _visible(s, ref_seq, client_idx)
    pre, end = _prefix(s, vis)

    inside = vis & (pre < pos) & (pos < end)
    has_inside = jnp.any(inside)
    # first-true index (min over masked iota): Mosaic lowers min-reductions
    # but not argmax; S when absent, and every use is has_inside-guarded
    j = jnp.min(jnp.where(inside, i, S))        # containing slot (split case)
    off = pos - jnp.sum(jnp.where(inside, pre, 0))   # pre[j], one-hot sum

    bcand = _active(s, S) & (pre >= pos)
    # active slots have index < count, so the min picks the first candidate
    # when one exists and falls back to count (append) otherwise
    idx_b = jnp.min(jnp.where(bcand, i, s["count"]))

    shift = jnp.where(has_inside, 2, 1).astype(jnp.int32)
    new_count = s["count"] + shift
    would_overflow = new_count > S

    new_slot = jnp.where(has_inside, j + 1, idx_b)
    is_new = i == new_slot
    is_right = has_inside & (i == new_slot + 1)   # split right piece
    is_left = has_inside & (i == j)               # split left piece
    below = i < new_slot

    out = {}
    for k in _PLANES:
        shifted = jnp.where(has_inside, jnp.roll(s[k], 2), jnp.roll(s[k], 1))
        out[k] = jnp.where(below, s[k], shifted)

    # base values at is_right are the containing slot's (via roll-by-2)
    out["length"] = jnp.where(
        is_new, length,
        jnp.where(is_left, off,
                  jnp.where(is_right, out["length"] - off, out["length"])))
    out["handle_off"] = jnp.where(
        is_new, 0,
        jnp.where(is_right, out["handle_off"] + off, out["handle_off"]))
    out["handle_op"] = jnp.where(is_new, handle, out["handle_op"])
    out["seq"] = jnp.where(is_new, seq, out["seq"])
    out["client"] = jnp.where(is_new, client_idx, out["client"])
    out["removed_seq"] = jnp.where(is_new, NOT_REMOVED, out["removed_seq"])
    out["removers"] = jnp.where(is_new, 0, out["removers"])

    # property planes (one (S,) plane per key): same roll, split right
    # piece inherits the containing slot's props via roll-by-2; new
    # segments carry none (host inserts-with-props are expressed as insert
    # + annotate at one seq). with_props=False (host knows no annotate
    # ever touched this store): all-zero planes are permutation-invariant,
    # skip the movement — ~35% of the kernel's HBM traffic.
    data_keys = _PLANES
    if with_props:
        pkeys = _prop_keys(s)
        data_keys = _PLANES + pkeys
        for pk in pkeys:
            pshift = jnp.where(has_inside, jnp.roll(s[pk], 2),
                               jnp.roll(s[pk], 1))
            pv = jnp.where(below, s[pk], pshift)
            out[pk] = jnp.where(is_new, 0, pv)
        if "prop_val" in s:  # stacked (S, K) variant (megadoc XLA path)
            data_keys = data_keys + ("prop_val",)
            pshift3 = jnp.where(has_inside,
                                jnp.roll(s["prop_val"], 2, axis=0),
                                jnp.roll(s["prop_val"], 1, axis=0))
            pv3 = jnp.where(below[:, None], s["prop_val"], pshift3)
            out["prop_val"] = jnp.where(is_new[:, None], 0, pv3)
    elif "prop_val" in s:
        out["prop_val"] = s["prop_val"]
        data_keys = _PLANES + ("prop_val",)

    # overflow: leave the doc untouched, set the sticky flag
    res = {k: jnp.where(would_overflow, s[k], out[k]) for k in data_keys}
    res["count"] = jnp.where(would_overflow, s["count"], new_count)
    res["overflow"] = jnp.where(would_overflow, 1, s["overflow"])
    return res


def _split_at(s, p, ref_seq, client_idx, with_props=True):
    """Split the visible segment strictly containing perspective position p."""
    S = s["seq"].shape[0]
    i = _iota(S)
    vis = _visible(s, ref_seq, client_idx)
    pre, end = _prefix(s, vis)
    inside = vis & (pre < p) & (p < end)
    has_inside = jnp.any(inside)
    j = jnp.min(jnp.where(inside, i, S))             # first-true index
    off = p - jnp.sum(jnp.where(inside, pre, 0))     # pre[j], one-hot sum

    new_count = s["count"] + 1
    would_overflow = new_count > S
    do = has_inside & ~would_overflow

    # gather-free: roll(s, 1) already carries slot j's values to j+1
    is_left = i == j
    is_right = i == j + 1
    out = {}
    for k in _PLANES:
        out[k] = jnp.where(i <= j, s[k], jnp.roll(s[k], 1))
    out["length"] = jnp.where(
        is_left, off,
        jnp.where(is_right, out["length"] - off, out["length"]))
    out["handle_off"] = jnp.where(
        is_right, out["handle_off"] + off, out["handle_off"])
    data_keys = _PLANES
    if with_props:
        pkeys = _prop_keys(s)
        data_keys = _PLANES + pkeys
        for pk in pkeys:
            out[pk] = jnp.where(i <= j, s[pk], jnp.roll(s[pk], 1))
        if "prop_val" in s:  # stacked (S, K) variant (megadoc XLA path)
            data_keys = data_keys + ("prop_val",)
            out["prop_val"] = jnp.where(
                (i <= j)[:, None], s["prop_val"],
                jnp.roll(s["prop_val"], 1, axis=0))
    elif "prop_val" in s:
        out["prop_val"] = s["prop_val"]
        data_keys = _PLANES + ("prop_val",)

    res = {k: jnp.where(do, out[k], s[k]) for k in data_keys}
    res["count"] = jnp.where(do, new_count, s["count"])
    res["overflow"] = jnp.where(has_inside & would_overflow, 1, s["overflow"])
    return res


PROP_HANDLE_BITS = 20  # a2 for annotate = key plane index << 20 | value handle


def _range_one(s, kind, start, end_pos, packed, seq, client_idx, ref_seq,
               with_props=True):
    """Apply one remove OR annotate to one doc — both are "two splits at the
    perspective boundaries + mark the visible segments strictly inside", so
    they share the expensive split passes and differ only in the cheap mark.

    Remove: only segments visible to the remover are marked — concurrently
    inserted text inside the range survives, overlapping removes keep the
    earliest acked removal seq and accumulate remover bits.

    Annotate: per-key last-sequenced-writer-wins (reference: merge-tree
    annotate). ``packed`` = key plane index << PROP_HANDLE_BITS | value
    handle; handle 0 deletes the key. Scan order is seq order, so a plain
    overwrite of the key's plane on visible targets realises LWW."""
    s = _split_at(s, start, ref_seq, client_idx, with_props)
    s = _split_at(s, end_pos, ref_seq, client_idx, with_props)
    vis = _visible(s, ref_seq, client_idx)
    pre, endp = _prefix(s, vis)
    target = vis & (pre >= start) & (endp <= end_pos) & (s["length"] > 0)

    is_rem = kind == int(OpKind.STR_REMOVE)
    bit = jnp.where(client_idx >= 0,
                    (1 << jnp.clip(client_idx, 0, MAX_CLIENTS - 1)), 0)
    out = dict(s)
    out["removed_seq"] = jnp.where(
        target & is_rem, jnp.minimum(s["removed_seq"], seq),
        s["removed_seq"])
    out["removers"] = jnp.where(target & is_rem, s["removers"] | bit,
                                s["removers"])

    if with_props:
        key_idx = packed >> PROP_HANDLE_BITS
        handle = packed & ((1 << PROP_HANDLE_BITS) - 1)
        is_ann = target & (kind == int(OpKind.STR_ANNOTATE))
        for ki, pk in enumerate(_prop_keys(s)):
            out[pk] = jnp.where(is_ann & (key_idx == ki), handle, s[pk])
        if "prop_val" in s:  # stacked (S, K) variant (megadoc XLA path)
            K = s["prop_val"].shape[1]
            sel = is_ann[:, None] & (jnp.arange(K)[None, :] == key_idx)
            out["prop_val"] = jnp.where(sel, handle, s["prop_val"])
    return out




# ------------------------------------------------------------- batched apply

def _state_dict(state: StringState):
    return {
        "seq": state.seq, "client": state.client,
        "removed_seq": state.removed_seq, "removers": state.removers,
        "length": state.length, "handle_op": state.handle_op,
        "handle_off": state.handle_off, "prop_val": state.prop_val,
        "count": state.count, "overflow": state.overflow,
    }


def apply_string_batch(state: StringState, kind, a0, a1, a2, seq, client,
                       ref_seq, with_props: bool = True) -> StringState:
    """Apply a dense (D, O) batch of sequenced merge-tree ops.

    kind/a0/a1/a2/seq/client/ref_seq: (D, O) int32 planes. Per doc, ops apply
    in ascending op index (the sequencer's total order); NOOP pads.
    STR_INSERT: a0=pos, a1=len, a2=payload handle. STR_REMOVE: a0=start,
    a1=end. STR_ANNOTATE: a0=start, a1=end, a2=key plane << 20 | value
    handle.

    with_props=False (static): the host guarantees no annotate has ever
    touched this state, so the all-zero property planes are permutation-
    invariant and all prop movement is skipped (the planes thread through
    the scan untouched).
    """
    sd = _state_dict(state)
    K = state.prop_val.shape[2]
    if with_props:
        # split (D, S, K) into K 2-D planes for the helpers (see _prop_keys)
        pv = sd.pop("prop_val")
        for i in range(K):
            sd[f"prop{i}"] = pv[:, :, i]

    def step(carry, op):
        k, p0, p1, p2, sq, cl, rs = op

        ins = jax.vmap(functools.partial(_insert_one, with_props=with_props)
                       )(carry, p0, p1, p2, sq, cl, rs)
        rng = jax.vmap(functools.partial(_range_one, with_props=with_props)
                       )(carry, k, p0, p1, p2, sq, cl, rs)

        def pick(key):
            tail = (1,) * (carry[key].ndim - 1)
            is_ins = (k == OpKind.STR_INSERT).reshape((-1,) + tail)
            is_rng = ((k == OpKind.STR_REMOVE) |
                      (k == OpKind.STR_ANNOTATE)).reshape((-1,) + tail)
            return jnp.where(is_ins, ins[key],
                             jnp.where(is_rng, rng[key], carry[key]))

        return {key: pick(key) for key in carry}, None

    ops = (kind.T, a0.T, a1.T, a2.T, seq.T, client.T, ref_seq.T)  # (O, D)
    out, _ = jax.lax.scan(step, sd, ops)
    if with_props:
        out["prop_val"] = jnp.stack(
            [out.pop(f"prop{i}") for i in range(K)], axis=-1)
    return StringState(**out)


apply_string_batch_jit = jax.jit(apply_string_batch, donate_argnums=0,
                                 static_argnames=("with_props",))


def compact_string_state(state: StringState, min_seq,
                         with_props: bool = True) -> StringState:
    """Zamboni on device: drop tombstones whose removal is acked at or below
    minSeq (reference: merge-tree zamboni; SURVEY.md §7.4 "compaction kernel
    keyed on MSN"). Stable partition keeps document order. min_seq: (D,)."""
    sd = _state_dict(state)
    S = state.seq.shape[1]

    # Gather-free stable partition: sort every plane together on the
    # drop-key with one multi-operand lax.sort (TPU sort network), instead
    # of argsort + per-plane gather (which lowers to scalar loops).
    active = jnp.arange(S)[None, :] < state.count[:, None]
    keep = active & ~(state.removed_seq <= min_seq[:, None])
    key = (~keep).astype(jnp.int32)
    K = state.prop_val.shape[2] if with_props else 0
    planes = [sd[k] for k in _PLANES] + \
        [state.prop_val[:, :, i] for i in range(K)]
    sorted_ = jax.lax.sort([key] + planes, dimension=1, is_stable=True,
                           num_keys=1)
    out = dict(zip(_PLANES, sorted_[1:1 + len(_PLANES)]))
    out["prop_val"] = jnp.stack(sorted_[1 + len(_PLANES):], axis=2) \
        if with_props else state.prop_val  # all-zero: permutation-invariant
    out["count"] = jnp.sum(keep.astype(jnp.int32), axis=1)
    out["overflow"] = state.overflow
    return StringState(**out)


# jitted zamboni: an un-jitted call runs dozens of eager dispatches,
# each with its own launch overhead
compact_string_state_jit = jax.jit(compact_string_state, donate_argnums=0,
                                   static_argnames=("with_props",))


def string_state_digest(state: StringState) -> jax.Array:
    """Per-doc content digest, invariant to split boundaries: for a live run
    (handle_op, handle_off) at visible position pos, (handle_off - pos) is
    identical for every piece of the same insert, so the per-slot mix sums to
    the same value however the run is physically split."""
    S = state.seq.shape[1]
    active = jnp.arange(S)[None, :] < state.count[:, None]
    live = active & (state.removed_seq == NOT_REMOVED)
    pl = jnp.where(live, state.length, 0)
    pre = jnp.cumsum(pl, axis=1) - pl
    mix = (state.handle_op * 1000003 + (state.handle_off - pre) * 8191) * pl
    return jnp.sum(jnp.where(live, mix, 0), axis=1) + jnp.sum(pl, axis=1)
