"""Engine step host path: host-clock time of an engine step outside its
wait for the sampled tokens (``engine.step`` less ``engine.sync``, from
the engine's own step records), mean over the window's steps that ran a
step program, in ms; steps the profiler covered are left out."""
from benchlib.phases import engine_steps


def read(run):
    steps = [r for r in engine_steps(run, traced=False) if r.kind != "idle"]
    if not steps:
        return None
    return 1e3 * sum(r.seconds - r.phase_s.get("sync", 0.0)
                     for r in steps) / len(steps)
