"""Process start to the window's opening: imports, context, rows and
weights from the seed, build, compile or cache load, the three compared
steps, and the steps of the measured call that fill the pipeline."""


def read(run):
    return run["window"]["opened_after_s"]
