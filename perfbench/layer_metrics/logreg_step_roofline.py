"""The packed Nesterov step kernel's share of its roofline: the least time
the chip could take for the fit's gradient steps (training rows only), over
the kernel's summed device time in the traced search."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"
KERNEL = r"packed_nesterov_step"  # the kernel's HLO instruction name


def read(ctx):
    return ctx["trace_reduce"].kernel_roofline_pct(ctx, KERNEL)
