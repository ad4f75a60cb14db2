"""Observability stack: the obs registry (Prometheus text exposition
0.0.4), the shared /metrics-/healthz-/readyz handler, workqueue/audit
instrumentation, and the acceptance fleet scrape — every component
(apiserver, kubelet, controller-manager obs mux, extender, scheduler)
serves all three endpoints, and the scheduler's per-phase histograms
match the driver's own phase accounting."""

import asyncio
import io
import json
import re
import sys
import threading
import types
import urllib.request

import pytest

from kubernetes_tpu.obs import REGISTRY, Registry, exponential_buckets
from kubernetes_tpu.obs.http import (
    METRICS_CONTENT_TYPE,
    ObsServer,
    obs_response,
)

from tests.http_util import http_store
from tests.test_http_apiserver import mk_node, mk_pod_dict


def fetch(url, timeout=5):
    """(status, body text, content-type) — tolerates non-2xx statuses."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode(), r.headers.get("Content-Type")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get("Content-Type")


async def afetch(url):
    return await asyncio.get_running_loop().run_in_executor(
        None, fetch, url)


# ---- registry / exposition format ----


def test_counter_and_gauge_render():
    r = Registry()
    c = r.counter("requests_total", "requests served")
    g = r.gauge("in_flight", "current in-flight")
    c.inc()
    c.inc(2)
    g.set(5)
    g.dec(2.5)
    text = r.render()
    assert "# HELP requests_total requests served" in text
    assert "# TYPE requests_total counter" in text
    # integral values render bare (no trailing .0) like client_golang
    assert "requests_total 3" in text
    assert "in_flight 2.5" in text
    with pytest.raises(ValueError):
        c.inc(-1)


def test_labels_and_escaping():
    r = Registry()
    fam = r.counter("api_requests_total", "by verb/resource",
                    ("verb", "resource"))
    fam.labels("GET", "pods").inc()
    fam.labels("GET", "pods").inc()
    fam.labels("POST", 'we"ird\\na\nme').inc()
    text = r.render()
    assert 'api_requests_total{verb="GET",resource="pods"} 2' in text
    # exposition-format escaping: backslash, quote, newline
    assert ('api_requests_total{verb="POST",'
            'resource="we\\"ird\\\\na\\nme"} 1') in text
    # same family object on re-registration; mismatch is an error
    assert r.counter("api_requests_total", "again",
                     ("verb", "resource")) is fam
    with pytest.raises(ValueError):
        r.gauge("api_requests_total", "wrong kind", ("verb", "resource"))
    with pytest.raises(ValueError):
        r.counter("api_requests_total", "wrong labels", ("verb",))


def test_histogram_bucket_invariants():
    r = Registry()
    h = r.histogram("latency_seconds", "op latency",
                    buckets=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.labels().count == 5
    assert abs(h.labels().sum - 5.605) < 1e-9
    text = r.render()
    # buckets are cumulative and +Inf equals the observation count
    assert 'latency_seconds_bucket{le="0.01"} 1' in text
    assert 'latency_seconds_bucket{le="0.1"} 3' in text
    assert 'latency_seconds_bucket{le="1.0"} 4' in text or \
        'latency_seconds_bucket{le="1"} 4' in text
    assert 'latency_seconds_bucket{le="+Inf"} 5' in text
    assert "latency_seconds_count 5" in text
    m = re.search(r"latency_seconds_sum (\S+)", text)
    assert m and abs(float(m.group(1)) - 5.605) < 1e-9
    # quantiles interpolate within buckets and clamp at the last bound
    assert 0.0 < h.quantile(0.5) <= 0.1
    assert h.quantile(0.99) == 1.0  # in the +Inf bucket -> last finite

    ladder = exponential_buckets(1000.0, 2.0, 15)
    assert len(ladder) == 15
    assert ladder[0] == 1000.0 and ladder[1] == 2000.0


def test_registry_concurrency():
    """Writers on many threads + renders interleaved: totals stay exact
    and rendering never throws mid-mutation (the asyncio servers scrape
    the global registry while loops mutate it)."""
    r = Registry()
    c = r.counter("ops_total", "ops", ("worker",))
    h = r.histogram("dur_seconds", "dur", buckets=[0.5, 1.0])
    stop = threading.Event()
    renders = []

    def scrape():
        while not stop.is_set():
            renders.append(r.render())

    def work(i):
        for _ in range(2000):
            c.labels(f"w{i}").inc()
            h.observe(0.25)

    scraper = threading.Thread(target=scrape)
    scraper.start()
    workers = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    stop.set()
    scraper.join()
    assert h.labels().count == 8 * 2000
    text = r.render()
    for i in range(8):
        assert f'ops_total{{worker="w{i}"}} 2000' in text
    assert renders  # scraped while hot


def test_metrics_hammer_contended_children():
    """The hard case test_registry_concurrency leaves out: every thread
    hammers the SAME child. Counter.inc totals stay exact under
    contention, Gauge inc/dec pairs net to zero, Histogram per-bucket
    counts partition the observation count exactly, and racing
    `labels()` calls on one unseen key converge on a single child (the
    double-checked create in Family.labels)."""
    r = Registry()
    c = r.counter("hammer_ops_total", "ops")
    g = r.gauge("hammer_inflight", "inflight")
    h = r.histogram("hammer_dur_seconds", "dur", buckets=[0.1, 1.0])
    lab = r.counter("hammer_labeled_total", "ops", ("k",))
    n_threads, n_ops = 8, 5000
    barrier = threading.Barrier(n_threads)
    children = [None] * n_threads

    def work(i):
        barrier.wait()  # maximize interleaving at the racy first get
        children[i] = lab.labels("same-key")
        for j in range(n_ops):
            c.inc()
            g.inc(2.0)
            g.dec()
            g.dec()
            # alternate buckets so each finite bound gets an exact share
            h.observe(0.05 if j % 2 == 0 else 0.5)
            children[i].inc()

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_ops
    assert c.labels().value == total
    assert g.labels().value == 0.0
    hist = h.labels()
    assert hist.count == total
    assert hist.counts[0] == total // 2   # le=0.1
    assert hist.counts[1] == total // 2   # le=1.0
    assert hist.counts[2] == 0            # +Inf
    assert hist.sum == pytest.approx(total // 2 * 0.05
                                     + total // 2 * 0.5)
    # the race on first labels(): exactly one child object won
    assert len({id(ch) for ch in children}) == 1
    assert children[0].value == total


# ---- shared handler helper ----


def test_obs_response_shapes():
    r = Registry()
    r.counter("x_total", "x").inc()
    status, body, ctype = obs_response("GET", "/metrics", registry=r)
    assert status == 200 and b"x_total 1" in body
    assert ctype == METRICS_CONTENT_TYPE
    status, body, _ = obs_response("GET", "/healthz")
    assert (status, body) == (200, b"ok")
    assert obs_response("GET", "/livez")[0] == 200
    # readyz aggregates its checks; failures name the failing check
    status, body, _ = obs_response(
        "GET", "/readyz",
        ready_checks={"synced": lambda: False, "up": lambda: True})
    assert status == 503 and b"synced" in body
    status, body, _ = obs_response(
        "GET", "/healthz", health_checks={"boom": lambda: 1 / 0})
    assert status == 503
    # non-obs paths are not ours; non-GET on obs paths is a 405
    assert obs_response("GET", "/api/v1/pods") is None
    assert obs_response("POST", "/metrics", registry=r)[0] == 405


def test_obs_server_scrape():
    async def run():
        ready = {"flag": False}
        srv = ObsServer(ready_checks={"flag": lambda: ready["flag"]})
        await srv.start()
        try:
            status, _, _ = await afetch(srv.url + "/healthz")
            assert status == 200
            status, body, _ = await afetch(srv.url + "/readyz")
            assert status == 503 and "flag" in body
            ready["flag"] = True
            status, body, _ = await afetch(srv.url + "/readyz")
            assert (status, body) == (200, "ok")
            status, _, ctype = await afetch(srv.url + "/metrics")
            assert status == 200 and "0.0.4" in ctype
            status, _, _ = await afetch(srv.url + "/nope")
            assert status == 404
        finally:
            await srv.stop()

    asyncio.run(run())


# ---- instrumented layers ----


def test_workqueue_metrics():
    async def run():
        from kubernetes_tpu.client.workqueue import BackoffQueue

        q = BackoffQueue(name="test-wq")
        q.add("a")
        q.add("b")
        batch = await asyncio.wait_for(q.get_batch(max_items=10), 5)
        assert sorted(batch) == ["a", "b"]
        for item in batch:
            q.done(item)
        q.add_after("a", 0.01)  # a retry
        await asyncio.sleep(0.05)
        await asyncio.wait_for(q.get_batch(max_items=10), 5)
        q.done("a")

    asyncio.run(run())
    text = REGISTRY.render()
    # 2 direct adds + 1 re-add when the add_after delay fired
    assert 'workqueue_adds_total{name="test-wq"} 3' in text
    assert 'workqueue_retries_total{name="test-wq"} 1' in text
    assert 'workqueue_depth{name="test-wq"} 0' in text
    for fam in ("workqueue_queue_duration_seconds",
                "workqueue_work_duration_seconds"):
        m = re.search(rf'{fam}_count{{name="test-wq"}} (\d+)', text)
        assert m and int(m.group(1)) >= 2


def test_audit_log_latency_and_size(tmp_path):
    """Satellite: audit records carry latencyMs + responseBytes."""
    audit = tmp_path / "audit.jsonl"
    with http_store(audit_path=str(audit)) as (client, _store):
        client.create(mk_node("n0"))
        client.list("Node")
    lines = [json.loads(x) for x in audit.read_text().splitlines()]
    assert len(lines) == 2
    for ln in lines:
        assert ln["latencyMs"] >= 0
        assert ln["responseBytes"] > 0


def test_kubectl_get_raw():
    """Satellite: `kubectl get --raw /metrics` (and /healthz) against a
    live apiserver."""
    from kubernetes_tpu.cli.kubectl import main

    with http_store() as (client, _store):
        server = f"http://{client.host}:{client.port}"

        def run_cli(*argv):
            out = io.StringIO()
            old = sys.stdout
            sys.stdout = out
            try:
                rc = main(["--server", server, *argv])
            finally:
                sys.stdout = old
            return rc, out.getvalue()

        rc, out = run_cli("get", "--raw", "/healthz")
        assert rc == 0 and out.strip() == "ok"
        rc, out = run_cli("get", "--raw", "/metrics")
        assert rc == 0 and "apiserver_request_count" in out
        rc, _ = run_cli("get", "--raw", "/definitely-not-here")
        assert rc == 1
        rc, _ = run_cli("get")  # no resource and no --raw
        assert rc == 1


def test_apiserver_request_metrics():
    with http_store() as (client, _store):
        client.create(mk_node("n0"))
        client.list("Node")
        status, text, _ = fetch(
            f"http://{client.host}:{client.port}/metrics")
        assert status == 200
    assert re.search(
        r'apiserver_request_count{verb="POST",resource="nodes",'
        r'code="201"} \d+', text)
    assert re.search(
        r'apiserver_request_count{verb="GET",resource="nodes",'
        r'code="200"} \d+', text)
    assert "apiserver_request_latencies_microseconds_bucket" in text
    assert "apiserver_current_inflight_requests" in text


def test_trace_steptimer_exports():
    from kubernetes_tpu.utils.trace import StepTimer, set_trace_sink

    records = []
    set_trace_sink(records.append)
    try:
        timer = StepTimer("unit-test batch")
        timer.step("encode")
        timer.step("solve")
        timer.export()
    finally:
        set_trace_sink(None)
    assert len(records) == 1
    rec = records[0]
    assert rec["name"] == "unit-test batch"
    assert [s["step"] for s in rec["steps"]] == ["encode", "solve"]
    # the steps partition the total: each mark ends one step
    assert all(s["ms"] >= 0.0 for s in rec["steps"])
    assert sum(s["ms"] for s in rec["steps"]) <= rec["total_ms"] + 1e-3
    assert isinstance(rec["ts"], float)


# ---- the acceptance test: boot the fleet, scrape all five ----


def test_fleet_obs_endpoints():
    """Every component serves /metrics + /healthz + /readyz; the
    scheduler's per-phase histograms agree with its own phase totals."""

    async def run():
        from kubernetes_tpu.agent.server import KubeletServer
        from kubernetes_tpu.apiserver import ObjectStore
        from kubernetes_tpu.apiserver.http import APIServer
        from kubernetes_tpu.extender.server import (
            ExtenderServer,
            ExtenderService,
        )
        from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.scheduler.server import SchedulerServer
        from kubernetes_tpu.state import Capacities

        store = ObjectStore()
        for n in make_nodes(4):
            store.create(n)

        api = APIServer(store)
        await api.start()

        sched = Scheduler(store, caps=Capacities(num_nodes=8, batch_pods=8))
        await sched.start()
        for p in make_pods(8):
            store.create(p)
        await asyncio.sleep(0)

        async def drain():
            done = 0
            while done < 8:
                done += await sched.schedule_pending(wait=0.2)

        await asyncio.wait_for(drain(), 30)
        sched_srv = SchedulerServer(sched)
        await sched_srv.start()

        kubelet_srv = KubeletServer(types.SimpleNamespace(running=True))
        await kubelet_srv.start()

        ext_service = ExtenderService()
        ext_service.warmup = lambda: None  # skip the compile; obs only
        ext_srv = ExtenderServer(ext_service)
        await ext_srv.start()

        cm_obs = ObsServer(ready_checks={"informers-synced": lambda: True})
        await cm_obs.start()

        fleet = {
            "apiserver": f"http://{api.host}:{api.port}",
            "scheduler": sched_srv.url,
            "kubelet": f"http://{kubelet_srv.host}:{kubelet_srv.port}",
            "extender": ext_srv.url,
            "controller-manager": cm_obs.url,
        }
        try:
            for component, base in fleet.items():
                for path in ("/metrics", "/healthz", "/readyz"):
                    status, body, ctype = await afetch(base + path)
                    assert status == 200, \
                        f"{component}{path} -> {status}: {body[:200]}"
                    if path == "/metrics":
                        assert "0.0.4" in ctype, f"{component}{path}"
                        assert "# TYPE" in body, f"{component}{path}"

            # scheduling-phase histograms appear in the scheduler's
            # /metrics and match the driver's phase accounting
            _, text, _ = await afetch(fleet["scheduler"] + "/metrics")
            assert "scheduler_pods_scheduled_total 8" in text
            for phase in ("encode", "flush", "dispatch", "solve",
                          "bind", "commit"):
                total = sched.metrics.phase_s.get(phase, 0.0)
                assert total > 0.0, f"driver never recorded {phase}"
                m = re.search(
                    rf'scheduler_phase_duration_seconds_sum'
                    rf'{{phase="{phase}"}} (\S+)', text)
                assert m, f"phase {phase} missing from /metrics"
                assert abs(float(m.group(1)) - total) <= \
                    max(1e-6, 0.01 * total), phase
                m = re.search(
                    rf'scheduler_phase_duration_seconds_bucket'
                    rf'{{phase="{phase}",le="\+Inf"}} (\d+)', text)
                c = re.search(
                    rf'scheduler_phase_duration_seconds_count'
                    rf'{{phase="{phase}"}} (\d+)', text)
                assert m and c and m.group(1) == c.group(1)
            # the bench snapshot reads the same accounting
            hist = sched.metrics.phase_histograms()
            for phase in ("encode", "solve", "bind", "commit"):
                assert hist[phase]["count"] >= 1
                assert abs(hist[phase]["sum_ms"] / 1000.0 -
                           sched.metrics.phase_s[phase]) <= \
                    max(1e-6, 0.01 * sched.metrics.phase_s[phase])

            # the Monitor scrapes the whole fleet: every component lands
            # in the TSDB with up=1 and its series are queryable
            from kubernetes_tpu.obs.monitor import Monitor

            # one profiling sample so the plane's families carry a
            # child (families render no series until first touched)
            from kubernetes_tpu.obs.profiling import PROFILER

            PROFILER.sampler.sample_once()

            mon = Monitor(store=None, interval=1.0)
            for job, base in fleet.items():
                mon.add_static_target(job, base)
            await mon.scrape_once()
            for job in fleet:
                vec = mon.query(f'up{{job="{job}"}}')
                assert vec and vec[0][1] == 1.0, f"up missing for {job}"
            assert len(mon.query("up")) == len(fleet)
            # a cross-component instant query over scraped series
            vec = mon.query('scheduler_pods_scheduled_total'
                            '{job="scheduler"}')
            assert vec and vec[0][1] == 8.0
            assert mon.query(
                'sum by (phase) '
                '(scheduler_phase_duration_seconds_count)')

            # profiling plane families land in the TSDB off the same
            # scrape: the sampler ring counter, the CPU-fallback StateDB
            # blob accounting (refreshed by the scheduler's /metrics
            # render), and the staged pipeline's busy-fraction export
            assert mon.query('profiling_samples_total'
                             '{job="scheduler"}') != []
            vec = mon.query('device_memory_statedb_bytes'
                            '{job="scheduler"}')
            assert vec and sum(v for _, v in vec) > 0
            if sched._staged is not None:
                assert mon.query('scheduler_pipeline_stage_busy_frac'
                                 '{job="scheduler",stage="settle"}') != []
            # DeviceMemoryHigh can never fire on the CPU fallback: no
            # device_memory_bytes_limit series means the highwater_frac
            # recording rule joins an empty vector
            assert mon.query('device_memory_bytes_limit') == []
            mon.evaluate_rules()
            assert mon.query('device_memory_highwater_frac') == []
            assert not any(
                s["state"] == "firing"
                for s in mon._alert_state.get(
                    "DeviceMemoryHigh", {}).values())
        finally:
            await cm_obs.stop()
            await ext_srv.stop()
            await kubelet_srv.stop()
            await sched_srv.stop()
            sched.stop()
            await api.stop()

    asyncio.run(run())
