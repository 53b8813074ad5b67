"""microstep.ms_per_round: device seconds of the chunk program's ops under
`shadow_microsteps` (queue pop and push, the model handler, the send
path) per round of the traced segments, in milliseconds."""


def read(ctx):
    s = ctx.reduction.scopes["microsteps"]
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
