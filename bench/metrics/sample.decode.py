"""sample.decode: the host's sampling of the next tokens from the
fetched logits (the engine's ``serving.sample`` phase) over its
``serving.step`` seconds, in the decode steps before the trace, in %."""
from bench import measure


def read(run):
    return measure.phase_share([t for t in run.window.ticks if t.decodes],
                               ["serving.sample"])
