"""The benchmark of fluid-tpu: door-to-ack cells driven from BENCHMARK.json.

Everything the yardstick needs lives here (traffic generation, the plain
reference, the trace reduction, the roofline's byte count, the peaks);
from the program it takes only the system under test and its counters.
"""
