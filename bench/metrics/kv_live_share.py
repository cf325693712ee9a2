"""KV cache: KV positions the active slots hold (``min(fed, ring
length)`` each, as the step's program attends them) over the positions
the cache reserves, summed over the window's engine steps (the engine's
own step records), in percent."""
from benchlib.phases import engine_steps


def read(run):
    steps = engine_steps(run)
    room = sum(r.reserved_kv for r in steps)
    if not room:
        return None
    return 100.0 * sum(r.live_kv for r in steps) / room
