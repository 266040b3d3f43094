"""Whole tick: the long-prompt cell's p95 TTFT over the window's
requests (end of the tick that produced the first token - due time),
ms.  Its run-to-run spread there is more than half of the widest bound
an end-to-end metric may have, so it is read per layer in that cell
and stays end to end in the chat cells."""


def read(ctx):
    return ctx.res.get("metrics", {}).get("ttft_p95_ms")
