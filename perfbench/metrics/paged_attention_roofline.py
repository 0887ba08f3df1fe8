"""Least time of the window's required attention work at the chip's bf16
peak or HBM bandwidth, whichever binds, over the device time of the
events named paged_attention."""
import work


def read(ctx):
    t = ctx["trace"].kernel_s("paged_attention")
    if not t:
        return None
    pk = ctx["peaks"]
    att = work.attention(ctx["cfg"], ctx["trace_tokens"])
    least = work.least_seconds(att["ops"], att["bytes"], pk["bf16_flops"],
                               pk["hbm_bytes_per_s"])
    return 100.0 * least / t
