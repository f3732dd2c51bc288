"""Mean host time per burst of the BSE read: the program's
``bse.fetch_many`` span (two-dispatch: the gather of the users' tables,
cast to the wire dtype) or ``bse.serve_candidates`` span (fused: the
megakernel's gather, dequantisation and query), dispatch to return."""

NAMES = ("bse.fetch_many", "bse.serve_candidates")


def read(ctx):
    spans = [s for s in ctx.spans if s.name in NAMES]
    if not spans:
        return None
    return 1e3 * sum(s.duration_s for s in spans) / len(spans)
