"""collective.ms_per_round: device seconds of the chunk program's ops under
`shadow_collective` (every cross-chip collective of the round: the
all-reduces, the all-to-alls, the all-gather) per round of the traced
segments, in ms. One chip has none: nothing to read."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_collective", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
