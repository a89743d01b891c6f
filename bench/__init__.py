"""On-chip benchmark of the Tarema scheduler's device paths.

One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  The cells, metrics and bounds are listed in
``BENCHMARK.json`` at the root of the checkout; everything that belongs to
one configuration (``configs/``), one traffic mix (``traffic/``) or one
per-layer metric (``metrics/``) is a file of its own, found by name.

Nothing in this package imports JAX at module level, so the references can
run in child processes that never touch the chip.
"""
