"""round.other_ms_per_round: device seconds of the chunk program's ops
outside both scopes (the window and barrier, `_compute_window`, the
round's bookkeeping and copies) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.scopes["other"]
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
