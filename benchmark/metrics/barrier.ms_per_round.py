"""barrier.ms_per_round: device seconds of the chunk program's ops under
`shadow_window` (`_compute_window`: the barrier's global minimum, the
window end, the next lookahead) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_window", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
