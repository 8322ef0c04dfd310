"""Test configuration: force an 8-device virtual CPU mesh.

All tests exercise the SPMD code paths on a virtual 8-device CPU topology
(mirrors the reference's strategy of running distributed specs on
``local[4]`` Spark — SURVEY.md §4.4) so sharding/collective logic is tested
without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Attach the robustness event counters (``robust/*`` — NaN guard
    trips, checkpoint quarantines, retries, preempt flushes) to every
    FAILED test report: when a tier-1 run goes red the fault-layer
    activity around the failure is in the log, not lost."""
    outcome = yield
    report = outcome.get_result()
    if report.when != "call" or not report.failed:
        return
    try:
        from analytics_zoo_tpu.core.profiling import TIMERS

        counters = {k: v for k, v in TIMERS.counts().items()
                    if k.startswith("robust/")}
        if counters:
            report.sections.append(
                ("robustness counters",
                 "\n".join(f"{k} = {v}"
                           for k, v in sorted(counters.items()))))
    except Exception:
        pass    # reporting must never mask the real failure


def pytest_sessionfinish(session, exitstatus):
    """When ``ZOO_TEST_OBSERVE_DIR`` is set (the CI tier-1 job sets it
    and uploads the directory as a workflow artifact), dump what the
    run's instrumentation saw: the completed-span ring as a JSONL event
    log, the labeled-metric registry as a Prometheus text file, and the
    legacy flat counters — a red CI run ships its own telemetry."""
    out_dir = os.environ.get("ZOO_TEST_OBSERVE_DIR")
    if not out_dir:
        return
    try:
        import json

        from analytics_zoo_tpu.core.profiling import TIMERS
        from analytics_zoo_tpu.observe import metrics as obs
        from analytics_zoo_tpu.observe.export import (JsonlEventLog,
                                                      to_prometheus)
        from analytics_zoo_tpu.observe.trace import TRACER

        os.makedirs(out_dir, exist_ok=True)
        log = JsonlEventLog(os.path.join(out_dir, "events.jsonl"))
        log.emit("session", exitstatus=int(exitstatus),
                 spans_completed=TRACER.completed_count(),
                 spans_active=TRACER.active_count(),
                 metric_series=obs.METRICS.series_count())
        for d in TRACER.snapshot():
            log.emit("span", span=d)
        log.metrics_dump(obs.METRICS)
        log.close()
        with open(os.path.join(out_dir, "metrics.prom"), "w",
                  encoding="utf-8") as f:
            f.write(to_prometheus(obs.METRICS))
        with open(os.path.join(out_dir, "timers.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"counters": TIMERS.counts(),
                       "gauges": TIMERS.gauges()}, f, indent=2,
                      sort_keys=True)
    except Exception:
        pass    # telemetry export must never change the exit status


@pytest.fixture(autouse=True)
def _transfer_guard(request):
    """Opt-in runtime complement to zoolint's JG-TRANSFER-HOT: tests
    marked ``@pytest.mark.transfer_guard`` run under
    ``jax.transfer_guard("disallow")``, so any IMPLICIT host<->device
    transfer (a numpy op on a device array, ``float()`` on a traced
    result...) raises at the offending line.  Explicit transfers
    (``jax.device_put`` / ``jax.device_get``) stay allowed — the point
    is that every transfer on a hot path must be *visible in the
    code*, which is exactly what the static rule enforces."""
    if request.node.get_closest_marker("transfer_guard") is None:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture(scope="session")
def zoo_ctx():
    from analytics_zoo_tpu import init_zoo_context

    return init_zoo_context()


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)
