"""Least time of the window's required attention work at the chip's bf16
peak or HBM bandwidth, whichever binds, over the device time of the
events named paged_attention."""
import work


def read(ctx):
    return work.roofline(ctx, "paged_attention")
