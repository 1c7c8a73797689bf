"""Per-layer metric readers, one file per metric named as in
BENCHMARK.json. Each defines ``read(ctx)``, returning the metric's value
or None where the run gives it nothing to read. ``ctx`` holds the
compact trace of the traced window (``bench.tracing``), the steps it
ran, and the run's counts (see ``bench.harness.per_layer``)."""
