"""Least time of the window's required routed-expert work (`lowrank_gmm`:
the factors of each expert a step routes a row to, and every routed
pair's two integer products) at the chip's int8 peak or HBM bandwidth,
whichever binds, over the device time of the events named
lowrank_gmm."""
import work


def read(ctx):
    return work.roofline(ctx, "lowrank_gmm")
