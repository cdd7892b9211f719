"""Set-up seconds: process start to the window (loading, weights, compiling or loading programs, warm-up, filling the slots)."""


def read(rec):
    return rec.setup_s
