"""Share of the window spent encoding and buffering ingest records for
the WAL (``wal.encode`` spans: ``tolist`` + ``json.dumps`` + write)."""
from bench import program_spans


def read(ctx):
    return program_spans.busy_pct(ctx, "wal.encode")
