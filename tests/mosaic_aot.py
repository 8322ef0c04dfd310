"""Compile for a TPU v5e from the CPU tier.

The installed ``libtpu`` carries the whole TPU compiler, Mosaic included,
and ``jax.experimental.topologies`` can describe a v5e host without one
being attached — so a test can ask, with no chip, whether Mosaic ACCEPTS a
kernel at a shape (it cannot run it: parity stays with ``interpret=True``
here and with ``chip_smoke.py`` on the chip).  The dispatch suites use this
to pin each ``shapes_ok`` rule to the compiler's own answer on both sides.
"""

import functools

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from analytics_zoo_tpu.observe.metrics import METRICS, render_series
from analytics_zoo_tpu.ops import dispatch


@functools.lru_cache(maxsize=1)
def _topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu").devices
    except Exception as e:  # no libtpu in this environment
        return repr(e)


def tpu_devices():
    """The four devices of a described (not attached) v5e 2x2 host."""
    devs = _topology()
    if isinstance(devs, str):
        pytest.skip(f"no TPU compiler to describe a v5e topology: {devs}")
    return list(devs)


def spec(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=sharding or SingleDeviceSharding(tpu_devices()[0]))


def tpu_compile(fn, *specs):
    """Lower ``fn`` for the described v5e and run the TPU compiler on it;
    raises what the compiler raises."""
    return jax.jit(fn).lower(*specs).compile()


def selected_series(monkeypatch, fn, *args):
    """Trace ``fn`` with ``dispatch.on_tpu`` patched to true and return
    the one ``ops_kernel_selected_total`` series the trace counted."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mark = METRICS.snapshot()
    jax.eval_shape(fn, *args)
    got = [k for k in METRICS.delta(mark)["counters"]
           if k.startswith("ops_kernel_selected_total")]
    assert len(got) == 1, got
    return got[0]


def series(kernel, path):
    return render_series("ops_kernel_selected_total",
                         (("kernel", kernel), ("path", path)))
