"""Share of the window spent making the WAL durable (``wal.sync`` spans:
flush + fsync, once per tick that appended)."""
from bench import program_spans


def read(ctx):
    return program_spans.busy_pct(ctx, "wal.sync")
