"""Gather passes of the CountMin update's route probe: the mean static
trip count (``n_probe``) of ``kernels.ops.route_probe``'s loop that the
window's CountMin ``ingest.update`` spans carry. None on a program whose
spans carry no ``n_probe``."""
from bench import program_spans


def read(ctx):
    passes = [a["n_probe"] for _, _, _, a in program_spans.spans(
        ctx, "ingest.update") if a.get("kind") == "CountMin"
        and "n_probe" in a]
    return sum(passes) / len(passes) if passes else None
