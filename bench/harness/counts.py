"""The roofline arithmetic every kernel metric shares.  What a kernel
or a tick needs in operations and bytes is counted by the model's
family (``bench/families/<family>.py``)."""
from __future__ import annotations

from typing import Any, Dict


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: Dict[str, Any]) -> Dict[str, Any]:
    """Least time for the work on this chip over the time it took, in
    percent, and which bound sets that least time."""
    t_ops = ops / float(peaks["bf16_flops_per_s"])
    t_mem = nbytes / float(peaks["hbm_bytes_per_s"])
    least = max(t_ops, t_mem)
    return {"share_pct": 100.0 * least / seconds,
            "bound": "compute" if t_ops >= t_mem else "memory"}
