"""exchange.ms_per_round: device seconds of the chunk program's ops under
`shadow_exchange` (the merge of staged packets into the queues,
`shadow_merge` included) per round of the traced segments, in ms."""


def read(ctx):
    s = ctx.reduction.scopes["exchange"]
    return 1e3 * s / ctx.rounds if s > 0 and ctx.rounds else None
