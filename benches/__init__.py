"""BASELINE.md benchmark configs #1–#5.

Each script is standalone (`python benches/configN_*.py`) and prints ONE
JSON line in the same shape as the headline `bench.py` (which implements
config #4, the north-star metric, and is what the driver runs). No
published reference numbers exist (BASELINE.md: reference mount was empty,
`published: {}`), so `vs_baseline` is null except where BASELINE.json set
an explicit target.

Measurement honesty: dispatch is asynchronous — timed sections end with a
device→host read (see bench.py).
"""
