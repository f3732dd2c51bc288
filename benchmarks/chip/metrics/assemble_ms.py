"""Mean host time per burst of the CTR server's request assembly: the
self time of the program's ``ctr.assemble`` span (padding, stacking and
uploading the burst's candidates, context and recent windows)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "ctr.assemble"]
    if not spans:
        return None
    self_s = []
    for s in spans:
        child = sum(c.duration_s for c in ctx.spans
                    if c.parent_id == s.span_id)
        self_s.append(s.duration_s - child)
    return 1e3 * sum(self_s) / len(self_s)
