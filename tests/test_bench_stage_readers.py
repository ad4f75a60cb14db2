"""The benchmark's readers of the stage waits and collector pauses
(benchmark/metrics/<name>.py): each reads its counter from a context
shaped like the harness's `Run._counters()`, and gives None where the
program under test has no such counter."""

import importlib.util
import os

import pytest

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "metrics")

# a traced run's counters, as drive.py passes them on
CTX = {
    "phase_us_per_pod": {"encode": 21.5, "flush": 17.25, "bind": 9.5,
                         "queue_wait": 61000.0, "in_flight": 172500.0,
                         "gc_pause": 812.75},
    "stage_busy_frac": {"dispatch": 0.41, "settle": 0.52, "commit": 0.25,
                        "apply": 0.3, "barrier_wait": 0.1875,
                        "apply_wait": 0.0625},
    "scheduled": 9000, "batches": 10,
}

CASES = {
    "barrier_wait_frac.sat": ("stage_busy_frac", "barrier_wait", 0.1875),
    "barrier_wait_frac.steady": ("stage_busy_frac", "barrier_wait", 0.1875),
    "apply_wait_frac": ("stage_busy_frac", "apply_wait", 0.0625),
    "queue_wait_ms.steady": ("phase_us_per_pod", "queue_wait", 61.0),
    "in_flight_ms.steady": ("phase_us_per_pod", "in_flight", 172.5),
    "gc_pause_us_per_pod.sat": ("phase_us_per_pod", "gc_pause", 812.75),
    "gc_pause_us_per_pod.steady": ("phase_us_per_pod", "gc_pause", 812.75),
    "flush_us_per_pod": ("phase_us_per_pod", "flush", 17.25),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("absent", [False, True],
                         ids=["present", "absent"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_stage_reader(name, absent):
    group, key, want = CASES[name]
    ctx = {k: dict(v) if isinstance(v, dict) else v for k, v in CTX.items()}
    if absent:
        # a program without the counter (the parent of this metric)
        del ctx[group][key]
        assert _reader(name)(ctx) is None
    else:
        assert _reader(name)(ctx) == pytest.approx(want, rel=1e-12)
