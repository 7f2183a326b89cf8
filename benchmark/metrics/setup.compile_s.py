"""Set-up layer (__main__, core/config.py, the compile cache): seconds
JAX spent tracing, lowering and compiling before the window opened,
from its monitoring events.  Moves setup_s."""


def read(ctx):
    return ctx["setup"]["compile_s"]
