"""ssd_kernel_ms: device time of the Pallas SSD kernels per train step, in ms
(forward, its recomputation and backward, summed from the device trace)."""
import ssdcalls


def read(ctx):
    calls = ssdcalls.calls(ctx)
    if not any(n for _, n in calls.values()):
        return None
    return 1e3 * sum(s for s, _ in calls.values()) / ctx["steps"]
