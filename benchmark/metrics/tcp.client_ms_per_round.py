"""tcp.client_ms_per_round: device seconds of the chunk program's ops under
`shadow_tcp_client` (inside `shadow_handler`: the tgen-TCP handler's client
lane, SYN-ACK, ACK processing and recovery, FIN-ACK, TICK, TX continuation,
RTO, deadline maintenance) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.markers.get("shadow_tcp_client", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
