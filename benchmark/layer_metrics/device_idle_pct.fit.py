"""Device: share of the traced window in which no operation ran, averaged
over the chips used."""


def read(run):
    red = run["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
