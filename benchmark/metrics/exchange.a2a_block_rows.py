"""exchange.a2a_block_rows: the alltoall block width, in rows per
(source shard, destination shard) pair, that the traced segments' chunks
ran at, averaged over those chunks (`a2a_rungs.chunks_per_rung` of each
segment's report). The exchange's pack, wire and merge input are sized
by it. None where the program keeps the block fixed (no `a2a_rungs`)."""


def read(ctx):
    chunks = rows = 0
    for r in ctx.reports:
        for rung, n in r.get("a2a_rungs", {}).get("chunks_per_rung", {}).items():
            chunks += n
            rows += int(rung) * n
    return rows / chunks if chunks else None
