"""Least time of the window's required `lowrank_qmm` work at the chip's
int8 peak or HBM bandwidth, whichever binds, over the device time of the
events named lowrank_qmm."""
import work


def read(ctx):
    t = ctx["trace"].kernel_s("lowrank_qmm")
    if not t:
        return None
    pk = ctx["peaks"]
    lin = work.lowrank(ctx["cfg"], ctx["trace_tokens"],
                       ctx["trace"].step_count)
    least = work.least_seconds(lin["ops"], lin["bytes"], pk["int8_ops"],
                               pk["hbm_bytes_per_s"])
    return 100.0 * least / t
