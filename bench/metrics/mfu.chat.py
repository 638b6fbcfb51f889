"""Model operations of the prompt and output tokens processed in the
traced window over window x chips x bf16 peak."""
from bench import window


def read(ctx):
    return window.mfu(ctx)
