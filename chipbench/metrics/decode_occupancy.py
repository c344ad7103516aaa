"""Tokens from decode steps over decode steps times slots, in the window:
how full the engine keeps its decode batch."""


def read(run):
    calls = run.in_window(run.win.decode_calls)
    if not calls:
        return None
    return 100.0 * sum(len(pos) for _, pos in calls) \
        / (len(calls) * run.n_slots)
