"""Step planning: tokens fed over rows computed by the prefill-width
program, summed over the window's prefill-width engine steps (the
engine's own step records), in percent. Each such step computes
batch x chunk rows whatever each slot feeds."""
from benchlib.phases import engine_steps


def read(run):
    steps = [r for r in engine_steps(run) if r.kind == "prefill"]
    rows = sum(r.rows for r in steps)
    if not rows:
        return None
    return 100.0 * sum(r.fed for r in steps) / rows
