"""Device inside a step: device idle time while the host waits on the
sampled tokens (under the engine's ``engine.sync`` span, the innermost
engine span there), per engine step traced, in ms."""
from benchlib.phases import SYNC, idle_phases


def read(run):
    p = idle_phases(run)
    return None if p is None else p.per_step_ms((SYNC,))
