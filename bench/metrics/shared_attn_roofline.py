"""shared_attn_roofline: the shared block's causal attention core against
its roofline, in %.

The least time its algorithmic work needs over a train step
(``workcount_hybrid.shared_attn_work`` for each invocation, forward and
backward; each the larger of operations over the bf16 peak and bytes over
HBM bandwidth), over its device time a step (region ``shared.attn``, every
phase, the recompute included).  At the benchmark's shapes operations set
the bound.
"""
import regions
import workcount
import workcount_hybrid


def read(ctx):
    spent = regions.region_ms(ctx, "shared.attn")
    if not spent:
        return None
    t, cfg = ctx["traffic"], ctx["cfg"]
    work = workcount_hybrid.shared_attn_work(cfg, t["batch"], t["seq"])
    least = sum(workcount.least_time(*w, ctx["peak"])[0]
                for w in work.values())
    return 100.0 * workcount_hybrid.invocations(cfg) * 1e3 * least / spent
