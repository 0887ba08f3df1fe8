"""The whole serving step's share of the chip's peak: the least time the
traced window's required work takes at peak (each kernel the family's
step runs, at the peak its work names: int8 linear operations at the
int8 peak, attention's float operations at the bf16 peak) over the
device time of the serving-step program."""
import work


def read(ctx):
    tr = ctx["trace"]
    if not tr.step_device_s:
        return None
    pk = ctx["peaks"]
    least = sum(w["ops"] / pk[w["peak"]] for w in work.required(ctx).values())
    return 100.0 * least / tr.step_device_s
