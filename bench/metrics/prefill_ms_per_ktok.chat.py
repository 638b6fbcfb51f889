"""Device time of the admission (prefill) executables per 1000 prompt
tokens prefilled in the window."""
from bench import window


def read(ctx):
    t = ctx["trace"].time_of(window.ADMIT)
    tokens = sum(window.prefilled(ctx["run"]))
    return None if t is None or not tokens else 1e3 * t / (tokens / 1e3)
