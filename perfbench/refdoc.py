"""The plain reference: one collaborative text document, one record per
character, sequenced ops applied in sequence order.

Independent of the program: it imports nothing of it and shares no table
with it. Semantics (Fluid's merge tree, as an observer replica sees it):

* an op made by ``client`` at reference sequence number ``ref`` speaks of
  the view in which a character is inserted if its insert was sequenced
  at or before ``ref`` or is the client's own, and removed if a remove of
  it was sequenced at or before ``ref`` or is the client's own;
* insert at ``pos``: the new run goes directly after the ``pos``-th
  character of that view (before anything the view cannot see there);
* remove / annotate ``[start, end)``: exactly the characters of that
  range of the view; text inserted concurrently inside it survives; of
  overlapping removes the earliest sequenced one counts; an annotate sets
  one key, a ``None`` value deletes it, last sequenced writer wins.
"""

INS, REM, ANN = 0, 1, 2


class RefDoc:
    __slots__ = ("chars", "seq", "live")

    def __init__(self, seq: int = 0):
        #: [ch, ins_seq, ins_client, removed_seq|None, removers|None, props|None]
        self.chars = []
        self.seq = seq          # last sequence number applied
        self.live = 0           # characters nobody has removed

    @staticmethod
    def _sees(c, ref, client):
        if not (c[1] <= ref or c[2] == client):
            return False
        if c[3] is None:
            return True
        return not (c[3] <= ref or client in c[4])

    def _index_after(self, pos, ref, client):
        """List index directly after the pos-th visible character."""
        if pos == 0:
            return 0
        seen = 0
        for i, c in enumerate(self.chars):
            if self._sees(c, ref, client):
                seen += 1
                if seen == pos:
                    return i + 1
        raise IndexError(f"position {pos} beyond the view's {seen} chars")

    def _range(self, start, end, ref, client):
        out, seen = [], 0
        for c in self.chars:
            if seen >= end:
                break
            if self._sees(c, ref, client):
                if seen >= start:
                    out.append(c)
                seen += 1
        if seen < end:
            raise IndexError(f"range [{start},{end}) beyond the view")
        return out

    def apply(self, seq, client, ref, kind, a0, a1, payload=None):
        """One sequenced op. ``payload``: the inserted text, or the
        annotate's single-key dict."""
        if seq != self.seq + 1:
            raise ValueError(f"op seq {seq} does not follow {self.seq}")
        ref = min(ref, seq - 1)
        if kind == INS:
            i = self._index_after(a0, ref, client)
            self.chars[i:i] = [[ch, seq, client, None, None, None]
                               for ch in payload]
            self.live += len(payload)
        elif kind == REM:
            for c in self._range(a0, a1, ref, client):
                if c[3] is None:
                    c[3], c[4] = seq, {client}
                    self.live -= 1
                else:
                    c[4].add(client)
        elif kind == ANN:
            (key, value), = payload.items()
            for c in self._range(a0, a1, ref, client):
                if value is None:
                    if c[5]:
                        c[5].pop(key, None)
                else:
                    if c[5] is None:
                        c[5] = {}
                    c[5][key] = value
        else:
            raise ValueError(f"op kind {kind}")
        self.seq = seq

    def text(self) -> str:
        return "".join(c[0] for c in self.chars if c[3] is None)

    def props(self):
        """Properties of each live character, in order."""
        return [dict(c[5]) if c[5] else {} for c in self.chars
                if c[3] is None]
