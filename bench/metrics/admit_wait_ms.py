"""Batcher admission: median, over the window's requests, of (the
batcher's admission stamp - its submission stamp), ms: the time a
request waited in the batcher's queue for a slot, both stamps taken by
the program where the work happens (``GenRequest.submitted_wall`` in
``submit``, ``admitted_wall`` when ``admit`` gives it a slot).  A
program without the stamps gives nothing to read."""
import statistics


def read(ctx):
    waits = []
    for r in ctx.window_recs:
        sub = getattr(r.req, "submitted_wall", None)
        adm = getattr(r.req, "admitted_wall", None)
        if sub is not None and adm is not None:
            waits.append(adm - sub)
    return 1e3 * statistics.median(waits) if waits else None
