"""Least time of the window's required `lowrank_qmm` work at the chip's
int8 peak or HBM bandwidth, whichever binds, over the device time of the
events named lowrank_qmm."""
import work


def read(ctx):
    return work.roofline(ctx, "lowrank_qmm")
