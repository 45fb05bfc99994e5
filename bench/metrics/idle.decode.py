"""Share of the traced engine steps' time in which no operation ran on
the device, in %, from the profiler trace."""
from bench import measure


def read(run):
    return measure.idle_share(run)
