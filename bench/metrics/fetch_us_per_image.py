"""Time the program spends making results numpy (its ``fetch`` spans,
the blocking ``wait.device`` inside them included) per image answered
in the traced window (us/image): what the result copies from the device
still cost where they are waited on.  A program that records no
``fetch`` span gives none.  Moves images_per_s."""
from bench import program_spans


def read(ctx):
    fetched = [s for s in program_spans.taken(ctx) if s.name == "fetch"]
    if not fetched or not ctx.window.images:
        return None
    return 1e-3 * sum(s.end - s.start for s in fetched) / ctx.window.images
