"""The chip benchmark: one cell (a configuration under a traffic mix) per run.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives here, where a later PR cannot edit
it: traffic generation (``traffic.py``), the arithmetic from frame logs to
end-to-end metrics (``metrics.py``), the reduction from the profiler's
trace to per-layer metrics (``xplane.py``, ``reducers.py``), the table of
peaks and the kernels' operation and byte counts (``roofline.py``), the
plain reference forward (``reference/``) and the comparison that decides
``correct`` (``correctness.py``). From the program the benchmark takes
only the system under test — the app built by ``build_app`` — and its
spans, counters and kernel names.

Cells, configurations, traffic mixes and per-layer metrics are data:
``BENCHMARK.json`` names them and ``spec.py`` finds
``configs/<name>.json``, ``traffic/<name>.json`` and
``layer_metrics/<name>.json``.
"""
