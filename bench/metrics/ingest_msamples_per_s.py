"""Request samples (not padding) of every request completed in the
window, over the window, in millions per second."""


def read(run):
    done = run.completed
    if not done or run.window_s <= 0:
        return None
    return sum(r.samples for r in done) / run.window_s / 1e6
