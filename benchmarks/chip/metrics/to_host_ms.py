"""Mean host time per burst of the scores' return: the program's
``ctr.to_host`` span (the device-to-host copy of the burst's scores and
the per-request slices)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "ctr.to_host"]
    if not spans:
        return None
    return 1e3 * sum(s.duration_s for s in spans) / len(spans)
