"""images_per_s: images classified in the window over its seconds."""
from bench import measure


def read(run):
    return measure.rate(run, lambda t: t.images)
