"""Tick host code: device-idle time inside the program's ``collm.step``
spans in the traced window, per tick, ms: how long the chip waits on
the host while a tick runs, apart from waiting for arrivals between
ticks.  The run's notes split it by the innermost ``collm.*`` span and
give the idle outside ``collm.step``."""
from harness import spans


def read(ctx):
    return spans.step_idle(ctx, "step_idle_ms.serve by span")
