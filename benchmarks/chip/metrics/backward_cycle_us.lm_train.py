"""Device µs per LM train step under the ``backward`` cycle scope (the
transpose read), summed over the seven block projections of every layer
(``benchlib.scopes``: the traced calls' self time over their steps)."""

from benchlib import scopes


def read(readings):
    red, steps = readings.get("scopes"), readings.get("traced_steps")
    if not red or not steps:
        return None
    s = scopes.cycle_s(red["scope_s"], "backward")
    return None if s is None else 1e6 * s / steps
