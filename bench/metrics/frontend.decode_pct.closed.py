"""Share of the window the server spends decoding request lines
(``frontend.decode`` spans: ``json.loads`` in ``serve_socket``)."""
from bench import program_spans


def read(ctx):
    return program_spans.busy_pct(ctx, "frontend.decode")
