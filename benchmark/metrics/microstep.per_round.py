"""microstep.per_round: microsteps per round over the traced segments,
from the program's own exact counters (stats.microsteps, stats.rounds)."""


def read(ctx):
    return ctx.microsteps / ctx.rounds if ctx.rounds else None
