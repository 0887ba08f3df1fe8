"""The whole serving step's share of the chip's peak: the least time the
traced window's required work takes at peak (int8 linear operations at
the int8 peak, attention's float operations at the bf16 peak) over the
device time of the serving-step program."""
import work


def read(ctx):
    tr = ctx["trace"]
    if not tr.step_device_s:
        return None
    tok, cfg, pk = ctx["trace_tokens"], ctx["cfg"], ctx["peaks"]
    lin = work.lowrank(cfg, tok, tr.step_count)
    att = work.attention(cfg, tok)
    least = lin["ops"] / pk["int8_ops"] + att["ops"] / pk["bf16_flops"]
    return 100.0 * least / tr.step_device_s
