"""The build listener (``observe/builds.py``): JAX's trace, lowering,
compile and cache-load events booked at their self time in
``jit_build_seconds{phase}`` and counted in
``jit_builds_total{phase,program}``."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.observe import METRICS, install_build_listener
from analytics_zoo_tpu.observe import builds
from analytics_zoo_tpu.observe.builds import PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def listening():
    install_build_listener()


def _fresh(name):
    """A function no persistent cache holds: its constant is new to the
    process, so its program is too."""
    c = 1.0 + int.from_bytes(os.urandom(3), "little") / 2 ** 24

    def f(x):
        return jnp.sin(x) * c

    f.__name__ = f.__qualname__ = name
    return f


def _phases(delta):
    return {p: delta["gauges"][f'jit_build_seconds{{phase="{p}"}}']
            for p in PHASES}


def _builds(delta, program):
    return {k: v for k, v in delta["counters"].items()
            if k.startswith("jit_builds_total")
            and f'program="{program}"' in k}


def _series(phase, program):
    return f'jit_builds_total{{phase="{phase}",program="{program}"}}'


def _books_three_phases(name):
    f = jax.jit(_fresh(name))
    before = _phases(METRICS.delta(None))
    mark = METRICS.snapshot()
    f(jnp.ones(4)).block_until_ready()
    delta = METRICS.delta(mark)
    after = _phases(delta)
    for phase in ("trace", "lower", "compile"):
        assert after[phase] > before[phase], phase
    assert after["load"] == before["load"]
    assert _builds(delta, name) == {_series("trace", name): 1}
    assert _builds(delta, f"jit({name})") == {
        _series("lower", f"jit({name})"): 1,
        _series("compile", f"jit({name})"): 1}


def test_a_new_jit_books_its_trace_lowering_and_compile():
    _books_three_phases("books_three_phases")


def test_the_same_shapes_again_book_nothing():
    f = jax.jit(_fresh("called_twice"))
    f(jnp.ones(4)).block_until_ready()
    mark = METRICS.snapshot()
    f(jnp.zeros(4)).block_until_ready()
    delta = METRICS.delta(mark)
    assert _phases(delta) == _phases(METRICS.delta(None))
    assert not [k for k in delta["counters"]
                if k.startswith("jit_builds_total")]


def test_a_jit_traced_inside_another_is_booked_once():
    """Tracing ``outer`` traces ``inner`` and each primitive it meets,
    each step its own event inside ``outer``'s: every second is booked
    to the innermost, so the phases add up to the outermost spans, under
    the wall time around the call, where the events' own sum is more."""
    inner = jax.jit(_fresh("inner_program"))
    c = 1.0 + int.from_bytes(os.urandom(3), "little") / 2 ** 24

    @jax.jit
    def outer_program(x):
        return jnp.cos(inner(x)).sum() * c

    spans = []

    def record(event, start, end, **kw):
        if event.startswith("/jax/core/compile/"):
            spans.append((start, end))

    jax.monitoring.register_event_time_span_listener(record)
    try:
        before = _phases(METRICS.delta(None))
        t0 = time.time()
        outer_program(jnp.ones(8)).block_until_ready()
        wall = time.time() - t0
        after = _phases(METRICS.delta(None))
    finally:
        jax.monitoring.unregister_event_time_span_listener(record)
    booked = sum(after[p] - before[p] for p in PHASES)
    outermost = sum(e - s for s, e in spans
                    if not any(s2 <= s and e <= e2 and (s2, e2) != (s, e)
                               for s2, e2 in spans))
    assert booked == pytest.approx(outermost, rel=1e-6, abs=1e-6)
    assert booked <= wall
    assert sum(e - s for s, e in spans) > booked
    delta = METRICS.delta(None)
    assert _series("trace", "inner_program") in delta["counters"]


def test_a_build_that_raises_leaves_the_account_sound():
    def broken(x):
        raise ValueError("refused while tracing")

    mark = METRICS.snapshot()
    with pytest.raises(ValueError, match="refused while tracing"):
        jax.jit(broken)(jnp.ones(2))
    # JAX ends the refused step's span too: it is booked, and is the last
    # step this thread ended
    assert _builds(METRICS.delta(mark), "broken") == {
        _series("trace", "broken"): 1}
    start, end = builds._ended()[-1]
    assert start <= end <= time.time()
    _books_three_phases("built_after_a_refusal")


def test_a_step_books_its_span_less_the_steps_that_started_inside_it():
    """Spans as JAX sends them, each as it ends: two children and a
    grandchild, then their parent, then a step after it on the same
    thread.  Each second goes to the innermost step."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    spans = [(1.0, 2.0), (3.5, 3.75), (3.0, 4.0), (0.0, 5.0), (6.0, 7.0)]
    booked = []

    def send():
        for start, end in spans:
            before = _phases(METRICS.delta(None))["trace"]
            builds._on_span(trace, start, end, fun_name="synthetic_step")
            booked.append(_phases(METRICS.delta(None))["trace"] - before)
        booked.append(list(builds._ended()))

    t = threading.Thread(target=send)
    t.start()
    t.join()
    assert booked[:-1] == pytest.approx([1.0, 0.25, 0.75, 3.0, 1.0])
    assert booked[-1] == [(0.0, 5.0), (6.0, 7.0)]


def test_threads_building_at_once_lose_no_second():
    """More threads than cores, each sending its own spans, with the
    interpreter switching threads as often as it can: every span is
    booked and counted once."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    threads, spans = (os.cpu_count() or 1) + 2, 300

    def send():
        for i in range(spans):
            builds._on_span(trace, 2.0 * i, 2.0 * i + 0.5,
                            fun_name="built_at_once")

    before = _phases(METRICS.delta(None))["trace"]
    mark = METRICS.snapshot()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=send) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert _phases(METRICS.delta(None))["trace"] - before == pytest.approx(
        0.5 * threads * spans)
    assert _builds(METRICS.delta(mark), "built_at_once") == {
        _series("trace", "built_at_once"): threads * spans}


def test_a_build_on_another_thread_is_booked():
    f = jax.jit(_fresh("built_on_a_thread"))
    mark = METRICS.snapshot()
    here = list(builds._ended())
    t = threading.Thread(target=lambda: f(jnp.ones(3)).block_until_ready())
    t.start()
    t.join()
    assert _builds(METRICS.delta(mark), "jit(built_on_a_thread)") == {
        _series("lower", "jit(built_on_a_thread)"): 1,
        _series("compile", "jit(built_on_a_thread)"): 1}
    # the other thread's spans were kept on that thread
    assert list(builds._ended()) == here


def test_a_window_after_set_up_reads_set_up_whole():
    """The gauge comes back whole from a window opened after the build,
    and the window's counters name no program built before it."""
    f = jax.jit(_fresh("built_before_the_window"))
    f(jnp.ones(5)).block_until_ready()
    spent = _phases(METRICS.delta(None))
    mark = METRICS.snapshot()
    f(jnp.ones(5)).block_until_ready()
    window = METRICS.delta(mark)
    assert _phases(window) == spent and spent["compile"] > 0
    assert _builds(window, "jit(built_before_the_window)") == {}


def test_a_second_init_zoo_context_installs_nothing_twice():
    from analytics_zoo_tpu.core import context

    prev = context._GLOBAL_CONTEXT
    try:
        context.init_zoo_context()
        context.init_zoo_context()
    finally:
        context.set_zoo_context(prev)
    assert install_build_listener() is False
    f = jax.jit(_fresh("counted_once"))
    mark = METRICS.snapshot()
    f(jnp.ones(2)).block_until_ready()
    assert METRICS.delta(mark)["counters"][
        _series("compile", "jit(counted_once)")] == 1


def _python(code, tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_four_gauges_read_zero_right_after_install(tmp_path):
    got = _python(
        "import json\n"
        "from analytics_zoo_tpu.observe import METRICS, "
        "install_build_listener\n"
        "first = install_build_listener()\n"
        "print(json.dumps([first, install_build_listener(), "
        "METRICS.delta(None)['gauges']]))\n", tmp_path)
    assert got == [True, False, {f'jit_build_seconds{{phase="{p}"}}': 0.0
                                 for p in PHASES}]


def test_a_persistent_cache_hit_books_load_not_compile(tmp_path):
    """A process of its own, since JAX binds the cache once a process:
    the program compiled, then, its in-memory caches cleared, built
    again from the cache's entry."""
    got = _python(
        "import json, jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', "
        "0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', "
        "-1)\n"
        "from analytics_zoo_tpu.observe import METRICS, "
        "install_build_listener\n"
        "install_build_listener()\n"
        "def cached(x):\n"
        "    return jnp.tanh(x) * 3\n"
        "reads = []\n"
        "for _ in range(2):\n"
        "    jax.clear_caches()\n"
        "    jax.jit(cached)(jnp.ones(16)).block_until_ready()\n"
        "    reads.append(METRICS.delta(None))\n"
        "print(json.dumps(reads))\n", tmp_path)
    first, second = ({k.split('"')[1]: v for k, v in r["gauges"].items()}
                     for r in got)
    assert first["compile"] > 0 and first["load"] == 0
    assert second["compile"] == first["compile"]
    assert second["load"] > 0
    assert got[1]["counters"][_series("compile", "jit(cached)")] == 1
    assert got[1]["counters"][_series("load", "jit(cached)")] == 1
