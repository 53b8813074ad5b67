"""tcp.segments_per_round: DATA segments the tgen-TCP clients sent in the
traced segments, first transmissions only (`data_segments_sent` of each
segment's `model_report`), per round of those segments: the TCP load the
handler carries."""


def read(ctx):
    sent = [r.get("model_report", {}).get("data_segments_sent") for r in ctx.reports]
    if not ctx.rounds or not sent or None in sent:
        return None
    return sum(sent) / ctx.rounds
