"""Chip benchmark of the GradientFlow trainer (see BENCHMARK.json).

Everything that measures lives here: the traffic generator, the weights
generator, the plain reference and the comparison that decides
``correct``, the FLOP and byte counters, the table of device peaks and
the reduction from profiler traces to metrics. From the program under
``src/`` the benchmark takes only the trainer it runs and the names of
the operations it compiled.
"""
