"""Whole tick: the long-prompt cell's p95 TPOT over the window's
requests ((end of the last token's tick - end of the first's) / (tokens
- 1)), ms; per layer there for the reason ``ttft_p95_ms.longprompt``
gives."""


def read(ctx):
    return ctx.res.get("metrics", {}).get("tpot_p95_ms")
