"""decode_attention_roofline: each layer's decode attention over the
live slots' contexts, at its least time (bench/work/dense_lm.py), over
the device time of the decode_attention kernel's events in the traced
steps, in %."""
from bench import measure
from bench.work import dense_lm


def read(run):
    config = run.cell.config
    calls = [dense_lm.decode_attention_call(config, t.decodes)
             for t in measure.traced_ticks(run) if t.decodes]
    return measure.roofline(run, "decode_attention",
                            calls * config["num_hidden_layers"])
