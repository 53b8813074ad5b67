"""merge.order_ms_per_round: device seconds of the chunk program's ops under
`shadow_merge_order` (the exchange merge's grouping sort, segment bounds
and urgency window) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_merge_order", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
