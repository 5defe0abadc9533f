"""Faults planted under the timed path, for the controls and their tests:
each breaks one guarantee the configurations state, once, inside the
measured traffic, and a run with one planted has to come out not correct.
``run.py --control <name>`` plants one; the benchmark's own runs never do.
"""

import numpy as np

NOOP = 12           # ops/schema.OpKind.NOOP: a slot the merge skips
ANNOTATE = 2        # ops/schema.OpKind.STR_ANNOTATE
AFTER_CALLS = 5     # the fault strikes this many calls into the window


def _nth_call(armed, obj, attr, before=None, replace=None, n=AFTER_CALLS,
              onward=False):
    """Plant on ``obj.attr``: its n-th call after ``armed`` is set (the
    harness sets it as the measured window opens) is the faulty one, or,
    with ``onward``, that call and every later one."""
    fn = getattr(obj, attr)
    state = {"calls": 0}

    def planted(*a, **k):
        state["calls"] += armed.is_set()
        if state["calls"] == n or (onward and state["calls"] > n):
            if replace is not None:
                return replace(fn, *a, **k)
            before(*a, **k)
        return fn(*a, **k)

    setattr(obj, attr, planted)


def unapplied_window(door, engine, log, armed):
    """A window is sequenced, logged and acked, and never merged: the
    step returns its state unchanged."""
    def blank(w):
        w.kind_eff = np.full_like(w.kind_eff, NOOP)
    _nth_call(armed, engine, "_ingest_dispatch", before=blank)


def half_window(door, engine, log, armed):
    """Half of a window's rows are left out of the merge."""
    def blank(w):
        w.kind_eff = w.kind_eff.copy()
        w.kind_eff[: max(len(w.kind_eff) // 2, 1)] = NOOP
    _nth_call(armed, engine, "_ingest_dispatch", before=blank)


def skipped_append(door, engine, log, armed):
    """A window is acked without its durable append."""
    def skip(fn, *a, **k):
        engine._ingest_mark_logged()    # or the engine poisons itself
    _nth_call(armed, engine, "_append_columnar", replace=skip)


def altered_ack(door, engine, log, armed):
    """One window's acks carry a sequence number that was never given."""
    def bump(fn, w, seqs, marks=None):
        return fn(w, np.asarray(seqs) + 1, marks=marks)
    _nth_call(armed, door, "_fan_acks", replace=bump)


def dropped_annotates(door, engine, log, armed):
    """From one window on, annotates are sequenced, logged and acked, and
    the props path never merges them: text stays right, marks go missing."""
    def blank(w):
        w.kind_eff = np.where(w.kind_eff == ANNOTATE, NOOP, w.kind_eff)
    _nth_call(armed, engine, "_ingest_dispatch", before=blank, onward=True)


def unsharded_state(door, engine, log, armed):
    """As the window opens the store's planes are gathered onto one chip
    and its mesh is dropped: every later window merges there, every
    answer stays right, and the placement the configuration states is
    gone. A fault only a cell on several chips can have."""
    def gather(w):
        import jax
        store = engine.store
        store.state = jax.device_put(store.state, jax.devices()[0])
        store.mesh = None
    _nth_call(armed, engine, "_ingest_dispatch", before=gather, n=1)


PLANTS = {f.__name__: f for f in (unapplied_window, half_window,
                                  skipped_append, altered_ack,
                                  dropped_annotates, unsharded_state)}
