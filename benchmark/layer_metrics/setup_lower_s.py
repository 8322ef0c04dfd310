"""Program build: the seconds this run's process spent lowering programs, jaxpr
to StableHLO (where Pallas kernels become Mosaic), from the registry's
``jit_build_seconds{phase="lower"}``. A gauge, which a window returns whole,
and no build happens in the window: the value is set-up's. Self time, so the
four phases add up to no more than ``window.opened_after_s``. ``None`` where
the program books no builds."""

SERIES = 'jit_build_seconds{phase="lower"}'


def read(run):
    return run["window"]["registry"]["gauges"].get(SERIES)
