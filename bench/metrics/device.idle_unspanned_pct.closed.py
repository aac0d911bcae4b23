"""Share of the device's idle time in the traced window that no program
span covers (``program_spans.idle_by_span``): idle time the program's
spans cannot explain."""
from bench import program_spans


def read(ctx):
    gaps = program_spans.idle_by_span(ctx)
    if not gaps:
        return None
    idle = sum(v for _, v in gaps)
    return 100.0 * dict(gaps).get("no span", 0.0) / idle if idle else None
