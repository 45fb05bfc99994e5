"""The chip benchmark: cells of BENCHMARK.json served on a TPU.

``bench/run.py`` is the entry point.  Everything that belongs to one
configuration, traffic mix, cell or per-layer metric is a file of its
own, found by the name that BENCHMARK.json gives it:

    bench/configs/<config>.json    sizes, source and precision
    bench/traffic/<traffic>.json   the generator's parameters
    bench/limits/<cell>.json       the correctness limits and readings
    bench/metrics/<metric>.py      one reader per per-layer metric

The yardstick (traffic generation, weights, plain references, work
counts, peaks, trace reduction and the comparison) lives here too, and
takes nothing from the program under test but its serving engine.
"""
