"""driver.host_ms_per_round: host seconds of the program's `shadow.run`
spans less the `shadow.sync` spans inside them (the time the driver loop
spends on the host, not waiting for the chip) per round of the traced
segments, in ms."""


def read(ctx):
    spans = ctx.reduction.host_spans
    if "shadow.run" not in spans or not ctx.rounds:
        return None
    run_s = spans["shadow.run"][0]
    sync_s = spans.get("shadow.sync", [0.0])[0]
    return 1e3 * (run_s - sync_s) / ctx.rounds
