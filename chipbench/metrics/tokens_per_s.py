"""Generated tokens stamped inside the window, over the window's seconds."""
from harness import loop


def read(run):
    return loop.tokens_in(run.win) / run.win.seconds
