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
``layer_metrics/<name>.json``. An ARCHITECTURE is files too (PR 28): its
plain reference ``reference/<module>.py``, named by its configuration's
file and holding its own kernel checks; its reducers and kernel costs, any
module under ``reducer_files/``; and in the configuration's file its
correctness sample, its cuts with their floors, its layer kinds and its
scopes (``spec.py`` lists who reads which key; PERF.md section 3 has the
table).
"""

# What the harness asks of the engine behind the local provider, beyond the
# HTTP surface: ``run.py`` warms and counts through these, so an engine for
# another architecture (a hybrid of layer kinds, a second kind of per-slot
# state) keeps them. Methods: ``prefill_groups(rows)`` (how admission
# groups rows into prefill calls), ``_exec_prefill(slots, starts, chunks)``
# (one compiled prefill call; returns the first tokens and the cache),
# ``_decode_burst(depth)`` and ``_flush_pending()`` (a lag-one decode burst
# and its landing), ``stats()`` (the counters, ``xla_compile_total`` and
# the ``sched_*_ms_total`` ledger among them), ``submit(request)``.
# Attributes: ``decode_burst`` and ``decode_burst_busy`` (the depths),
# ``flight`` (the flight ring, or None), ``params`` (the weight tree the
# reference reads), ``model_cfg``, ``cache`` and ``_d_dirty`` (set when the
# warm-up has run bursts behind the scheduler's back), ``tokenizer``
# (replaced by the harness's), and the geometry ``B``, ``S``,
# ``prefill_chunk``, ``kv_page``, ``kv_quant``, ``quant``,
# ``attention_impl``, ``mesh``. ``run.warm_programs`` asks for them by name.
ENGINE_INTERFACE = (
    "prefill_groups", "_exec_prefill", "_decode_burst", "_flush_pending",
    "stats", "submit", "decode_burst", "decode_burst_busy", "flight",
    "params", "model_cfg", "cache", "_d_dirty", "tokenizer", "B", "S",
    "prefill_chunk", "kv_page", "kv_quant", "quant", "attention_impl", "mesh")

