"""Tick host code: device-idle time inside the program's ``collm.step``
spans in the traced window, per tick, ms, in the long-prompt cell,
whose tails are read per layer.  The run's notes split it by the
innermost ``collm.*`` span and give the idle outside ``collm.step``."""
from harness import spans


def read(ctx):
    return spans.step_idle(ctx, "step_idle_ms.longprompt by span")
