"""Engine host phases: device idle time under the engine's admit, plan,
inputs, dispatch and advance spans (the innermost engine span over each
idle stretch of the traced window), per engine step traced, in ms."""
from benchlib.phases import HOST_PHASES, idle_phases


def read(run):
    p = idle_phases(run)
    return None if p is None else p.per_step_ms(HOST_PHASES)
