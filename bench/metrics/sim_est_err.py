"""sim_est_err: the simulator's error on the chip's own step, in %.

``simulate()`` with the ``TPU_V5E`` parameter file prices the compiled step
the traced window ran; the error is |t_est / measured step - 1|, with the
measured step the traced window over its steps.  The simulator prices
Pallas calls as data movement, so a kernel gain it cannot see widens this.
"""


def read(ctx):
    t_est = ctx.get("sim_t_est")
    if not t_est:
        return None
    step = (ctx["hi"] - ctx["lo"]) / ctx["steps"]
    return 100.0 * abs(t_est / step - 1.0)
