"""Device time of the decode-block executables per decode step."""
from bench import window


def read(ctx):
    return window.decode_step_ms(ctx)
