"""handler.ms_per_round: device seconds of the chunk program's ops under
`shadow_handler` (the model's event handler, inside the microstep loop)
per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_handler", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
