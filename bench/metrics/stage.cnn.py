"""stage.cnn: the engine's batching, stacking, padding and transfer to
the device (its ``serving.stage`` phase) over its ``serving.step``
seconds, in the batches before the trace, in %."""
from bench import measure


def read(run):
    return measure.phase_share([t for t in run.window.ticks if t.images],
                               ["serving.stage"])
