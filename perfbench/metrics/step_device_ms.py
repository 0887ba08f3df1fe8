"""Device time of the serving-step program per step, from the trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.step_count:
        return None
    return 1000.0 * tr.step_device_s / tr.step_count
