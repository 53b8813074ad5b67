"""exchange.rows_per_round: packets the traced segments sent, each a row
the exchange merge routes to its destination's queue (`packets_sent` in
each segment's report), per round of those segments: the merge's real
load, beside its capacity-sized shapes."""


def read(ctx):
    if not ctx.rounds or not ctx.reports:
        return None
    return sum(r["packets_sent"] for r in ctx.reports) / ctx.rounds
