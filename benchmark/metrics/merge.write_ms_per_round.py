"""merge.write_ms_per_round: device seconds of the chunk program's ops under
`shadow_merge_write` (the exchange merge's row select and gather and the
write into the queue slab) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_merge_write", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
