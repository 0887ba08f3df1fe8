"""Valid tokens (prompt rows prefilled and served tokens fed back as
decode inputs) per serving step, over the traced part of the window."""


def read(ctx):
    steps = ctx["trace"].step_count
    if not steps:
        return None
    return ctx["trace_tokens"].rows / steps
