"""The benchmark of the serving stack: the yardstick every later PR is held to.

Everything that decides a number lives here, where a PR that claims a
gain cannot edit it: traffic generation (traffic.py), the load generator
(loadgen.py), the arithmetic from client records, counters, spans and the
device trace to metrics (metrics.py, layer_metrics/, trace_reduce.py),
the table of peaks and the bytes and operations of a step (peaks.json,
roofline.py), the plain reference and the comparison that decides
``correct`` (reference.py for the mathematics and the tolerances; one
file per model family under architectures/ for the field mapping, the
reader of the engine's tree and the verdict). From the program it takes
only the system under test, its /metrics counters, its /admin/trace
spans and the names the profiler prints.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run.py is the parent and never imports JAX; serve_cell.py is the one
child that holds the chip.
"""
