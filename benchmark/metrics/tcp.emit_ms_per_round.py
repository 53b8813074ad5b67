"""tcp.emit_ms_per_round: device seconds of the chunk program's ops under
`shadow_tcp_emit` (inside `shadow_handler`: the tgen-TCP handler's
emissions, push ports A and B, the send port, destinations and phase
stamps) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_tcp_emit", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
