"""Share of the traced window in which no operation ran on the device."""
from bench import window


def read(ctx):
    return window.idle_share(ctx)
