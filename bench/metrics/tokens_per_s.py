"""tokens_per_s: output tokens emitted in the window (first tokens
included) over its seconds."""
from bench import measure


def read(run):
    return measure.rate(run, lambda t: len(t.prefills) + len(t.decodes))
