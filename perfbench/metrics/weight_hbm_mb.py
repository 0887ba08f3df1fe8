"""Bytes the served parameter arrays occupy on the device
(`InferenceEngine.weight_hbm_bytes`), in MB."""


def read(ctx):
    return ctx["weight_hbm_bytes"] / 1e6
