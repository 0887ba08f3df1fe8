"""Least time of the window's required `quant_matmul` work (the linears
of a "quant" plan) at the chip's int8 peak or HBM bandwidth, whichever
binds, over the device time of the events named quant_matmul."""
import work


def read(ctx):
    return work.roofline(ctx, "quant_matmul")
