"""The fused MLP epoch kernel's share of its roofline: the least time
the chip could take for the fit's minibatch epochs (training rows only), over
the kernel's summed device time in the traced search."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"
KERNEL = r"tpu_custom_call"  # the only Mosaic kernel on this path; it carries no name of its own yet


def read(ctx):
    return ctx["trace_reduce"].kernel_roofline_pct(ctx, KERNEL)
