"""occupancy.decode: mean share of the slots that advanced in each
decode step of the window, in % (the engine's live slots, read after
every step)."""


def read(run):
    ticks = [t for t in run.window.ticks if t.decodes]
    if not ticks:
        return None
    slots = run.cell.traffic["slots"]
    return 100.0 * sum(t.live for t in ticks) / (len(ticks) * slots)
