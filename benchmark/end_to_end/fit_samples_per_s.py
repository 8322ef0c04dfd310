"""Every sample trained in the window over all the time of the window: the
steps finished between the window's opening and its close, both on a
finished step, times the batch."""


def read(run):
    win = run["window"]
    return win["samples"] / win["seconds"]
