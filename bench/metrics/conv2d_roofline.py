"""conv2d_roofline: the conv layers' least time at the chip's peaks
(bench/work/cnn.py) over the device time of the conv2d kernel's events,
for the images of the traced steps, in %."""
from bench import measure
from bench.work import cnn


def read(run):
    config = run.cell.config
    calls = [c for t in measure.traced_ticks(run) if t.images
             for c in cnn.conv_calls(config, t.images)]
    return measure.roofline(run, "conv2d", calls)
