"""From raw readings to per-layer metrics: one small file per metric under
``metrics/``, found by the metric's name, each naming a reducer of this
fixed vocabulary and the readings it takes. A reader that finds nothing
to read returns nothing, and the harness leaves the metric out.

Readings (``raw``) are differences across the measured window unless
named otherwise: ``d.<counter>`` (door, executor and store counters,
``span.<name>.s/.n`` of the benchmark's spans), ``gen.<field>`` (the
generator's own clock), ``trace.<...>`` and ``roofline.<...>`` (the traced
part of the window), ``window_s`` and ``acked``.
"""

from .traffic import load_json


def _get(raw, key):
    v = raw.get(key)
    return None if v is None else float(v)


def read_metric(name: str, raw: dict):
    spec = load_json("metrics", name)
    how = spec["reduce"]
    scale = spec.get("scale", 1.0)
    if how == "value":
        v = _get(raw, spec["key"])
        return None if v is None else v * scale
    nums = [_get(raw, k) for k in spec["num"]]
    den = _get(raw, spec["den"])
    if den is None or den <= 0 or any(v is None for v in nums):
        return None
    if how == "ratio":              # per_op, per_window, share: sum / den
        return sum(nums) / den * scale
    if how == "max_ratio":          # the busiest of several
        return max(nums) / den * scale
    if how == "one_minus_ratio":    # an idle share
        return (1.0 - sum(nums) / den) * scale
    raise ValueError(f"metric {name}: unknown reducer {how!r}")
