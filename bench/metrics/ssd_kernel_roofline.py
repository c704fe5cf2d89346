"""ssd_kernel_roofline: the Pallas SSD calls' share of their roofline, in %.

For each call, the least time its algorithmic work needs
(``workcount.ssd_kernel_work``: B and C at their group count; the larger of
operations over the bf16 peak and bytes over HBM bandwidth) summed over the
calls, over their summed device time in the trace.  At the benchmark's
shapes bytes set the bound, forward and backward.
"""
import ssdcalls
import workcount


def read(ctx):
    calls = ssdcalls.calls(ctx)
    t = ctx["traffic"]
    work = workcount.ssd_kernel_work(ctx["cfg"], t["batch"], t["seq"])
    least = sum(n * workcount.least_time(*work[kind], ctx["peak"])[0]
                for kind, (_, n) in calls.items())
    spent = sum(s for s, _ in calls.values())
    if spent <= 0:
        return None
    return 100.0 * least / spent
