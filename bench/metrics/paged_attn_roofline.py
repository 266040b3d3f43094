"""Kernel ``paged_decode_attention``: least time for the operations and
bytes its live rows need (each row's live keys and values read once,
4*H*hd operations per key, every layer) over the kernel's traced time,
in percent.  The bound that sets the least time is printed by the run
(``roofline_bound``); at decode it is memory."""
from harness import counts, spec

KERNEL = r"paged_kernel|paged_decode_attention"


def read(ctx):
    if not ctx.traced:
        return None
    secs, calls = ctx.ops(KERNEL)
    contexts = ctx.decode_contexts()
    if calls == 0 or secs <= 0 or not contexts:
        return None
    need = spec.family(ctx.cfg).paged_attention(ctx.cfg, contexts)
    share = counts.roofline_share(need["ops"], need["bytes"], secs,
                                  ctx.peaks)
    ctx.res.setdefault("notes", {})["paged_attn_roofline_bound"] = \
        share["bound"]
    return share["share_pct"]
