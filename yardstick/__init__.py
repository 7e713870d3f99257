"""The yardstick: the benchmark every PR is measured with.

``python -m yardstick.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once, on the TPU it
is started on, and prints one JSON line. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found by the name in ``BENCHMARK.json`` (see ``README.md``); from
the program the yardstick takes only the system under test, its spans,
its counters and its module names.
"""
