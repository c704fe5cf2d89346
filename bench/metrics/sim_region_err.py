"""sim_region_err: the simulator's error region by region on the chip's own
step, in %: the sum over the regions and ``other`` of |simulated -
measured| over the measured sum (``regions.py``).  Errors of opposite sign
in two regions add here, where ``sim_est_err`` lets them cancel."""
import regions


def read(ctx):
    return regions.sim_region_err(ctx)
