"""Process start to the first timed request: weights made on the device,
engine built, every span width compiled or loaded from the cache and run
once."""


def read(ctx):
    return ctx["setup_s"]
