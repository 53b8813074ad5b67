"""driver.ahead_share: the share of the traced segments' accepted chunks
whose successor was already queued on the device when the driver read
them (`chunk_pipeline` of each segment's report: 100 x ahead / chunks),
in %. None where the program reports no chunk pipeline."""


def read(ctx):
    chunks = ahead = 0
    for r in ctx.reports:
        pipe = r.get("chunk_pipeline")
        if pipe:
            chunks += pipe["chunks"]
            ahead += pipe["ahead"]
    return 100.0 * ahead / chunks if chunks else None
