"""Share of the window spent appending ingest records to the WAL and
fsyncing it (``WriteAheadLog.append_ingest`` and ``sync`` spans)."""


def read(ctx):
    s = ctx.spans("wal.append_ingest", "wal.sync")
    return 100.0 * sum(t1 - t0 for _, t0, t1, _ in s) / ctx.seconds \
        if s else None
