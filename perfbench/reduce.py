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


def read_metric(name: str, raw: dict):
    spec = load_json("metrics", name)
    how = spec["reduce"]
    scale = spec.get("scale", 1.0)
    # ``module``: the names one kernel's jitted module goes by (one chip,
    # a mesh, a later name); ``{module}`` in a reading's name is the first
    # of them that the trace has. A run has one.
    mod = next((m for m in spec.get("module", ())
                if f"trace.module_n.{m}" in raw), "")

    def get(key):
        v = raw.get(key.replace("{module}", mod))
        return None if v is None else float(v)

    if how == "value":
        v = get(spec["key"])
        return None if v is None else v * scale
    nums = [get(k) for k in spec["num"]]
    den = get(spec["den"])
    if den is None or den <= 0 or any(v is None for v in nums):
        return None
    if how == "ratio":              # per_op, per_window, share: sum / den
        return sum(nums) / den * scale
    if how == "max_ratio":          # the busiest of several
        return max(nums) / den * scale
    if how == "one_minus_ratio":    # an idle share
        return (1.0 - sum(nums) / den) * scale
    if how == "ratio_of_means":     # sum / num_n over den / den_n
        n, d = get(spec["num_n"]), get(spec["den_n"])
        if not n or not d:
            return None
        return (sum(nums) / n) / (den / d) * scale
    raise ValueError(f"metric {name}: unknown reducer {how!r}")
