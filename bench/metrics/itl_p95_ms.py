"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of a request in the window."""
from bench import measure


def read(run):
    return measure.inter_output_ms(run, 95)
