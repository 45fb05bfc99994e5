"""Whole-step share of the chip's peak: the operations of the work the
window's engine steps outside the trace completed (bench/work) over
their summed host-clock time times the peak FLOP/s, in %."""
from bench import measure


def read(run):
    return measure.step_mfu(run)
