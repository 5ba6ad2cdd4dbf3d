"""What held a thread of ``serve`` (obs/waits.py): the collector's pauses
on /metrics in every serve, and with --telemetry the waits for
cluster.lock, each also an annotation on the profiler's clock; and the
``cpu_us`` stat of every ``stage:*`` annotation."""

import asyncio
import gc
import threading
import time

import jax
import pytest

from kubernetes_tpu import cli, metrics
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.obs import ObsConfig, build_telemetry, waits
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.server.extender import ExtenderCore, make_app
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.utils.clock import FakeClock

RLOCK = type(threading.RLock())


class Counting:
    """Stands where TraceAnnotation stood: builds the real one, and keeps
    the name of each it built."""

    def __init__(self, real=jax.profiler.TraceAnnotation):
        self.real, self.names = real, []

    def __call__(self, name, **kw):
        self.names.append(name)
        return self.real(name, **kw)

    def built(self, prefix):
        return [n for n in self.names if n.startswith(prefix)]


@pytest.fixture
def collector():
    """The collector's callback as serve installs it, taken out after."""
    waits.unwatch_collector()
    yield waits.watch_collector
    waits.unwatch_collector()


def scrape() -> dict:
    from prometheus_client.parser import text_string_to_metric_families

    out = {}
    for fam in text_string_to_metric_families(metrics.render().decode()):
        for s in fam.samples:
            out[(s.name, tuple(sorted(s.labels.items())))] = s.value
    return out


def sample(name, **labels):
    return scrape().get((name, tuple(sorted(labels.items()))), 0.0)


def test_a_full_collection_is_a_pause_of_generation_2(collector):
    collector()
    before = sample("scheduler_gc_pause_seconds_count", generation="2")
    sum_before = sample("scheduler_gc_pause_seconds_sum", generation="2")
    gc.collect(2)
    after = scrape()
    assert after[("scheduler_gc_pause_seconds_count", (("generation", "2"),))] >= before + 1
    assert after[("scheduler_gc_pause_seconds_sum", (("generation", "2"),))] > sum_before
    # every generation is exported from the start, and the buckets reach 4 s
    for g in "012":
        assert ("scheduler_gc_pause_seconds_count", (("generation", g),)) in after
    assert ("scheduler_gc_pause_seconds_bucket", (("generation", "2"), ("le", "4.0"))) in after


def observed(generation="2"):
    child = metrics.gc_pause_seconds.labels(generation)
    return child._sum.get(), sum(b.get() for b in child._buckets)


def test_the_callback_queues_and_the_scrape_observes(collector, monkeypatch):
    """A collection may start inside a metric's own lock: the callback
    takes none, and the histogram moves at the next render."""
    monkeypatch.setattr(waits, "FLUSH_EVERY_S", 3600.0)
    pauses = collector()
    pauses.flush()
    before = observed()
    gc.collect(2)
    assert observed() == before
    metrics.render()
    assert observed()[1] >= before[1] + 1


def test_the_queue_is_flushed_without_a_scrape(collector, monkeypatch):
    """A process nobody scrapes does not grow the queue for ever."""
    monkeypatch.setattr(waits, "FLUSH_EVERY_S", 0.05)
    pauses = collector()
    before = observed()
    gc.collect(2)
    deadline = time.monotonic() + 30
    while observed()[1] < before[1] + 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert observed()[1] >= before[1] + 1 and not pauses._pending


def test_collections_are_annotated_only_when_asked(collector):
    ann = Counting()
    collector()
    gc.collect(2)
    collector(ann)
    gc.collect(2)
    gc.collect(0)
    assert ann.built("gc:") == ["gc:gen2", "gc:gen0"]


def cluster(nodes=4):
    cs = ClusterState()
    for i in range(nodes):
        cs.create_node(
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"}).obj()
        )
    return cs


def wait_totals():
    return {
        t: (
            sample("scheduler_cluster_lock_wait_seconds_total", thread=t),
            sample("scheduler_cluster_lock_contended_total", thread=t),
        )
        for t in waits.THREADS
    }


def hold_while(lock, target, seconds=0.05):
    """Hold ``lock`` while ``target`` runs on a thread of its own, for
    ``seconds`` after it started (it then waits for the lock)."""
    lock.acquire()
    t = threading.Thread(target=target)
    t.start()
    time.sleep(seconds)
    lock.release()
    t.join(timeout=60)
    assert not t.is_alive()


def test_uncontended_and_reentrant_acquires_book_nothing():
    ann = Counting()
    lock = waits.TimedRLock(threading.RLock(), ann, None)
    before = wait_totals()
    with lock:
        with lock:
            assert lock.acquire(blocking=False)
            lock.release()
    assert wait_totals() == before and ann.names == []
    # a thread that cannot have it and does not wait books nothing either
    lock.acquire()
    got = []
    t = threading.Thread(target=lambda: got.append(lock.acquire(blocking=False)))
    t.start()
    t.join()
    lock.release()
    assert got == [False] and wait_totals() == before and ann.names == []


def test_a_contended_acquire_books_its_wait_under_its_thread():
    ann = Counting()

    def fake_loop():
        with lock:
            pass

    lock = waits.TimedRLock(threading.RLock(), ann, fake_loop.__code__)
    before = wait_totals()

    def other():
        with lock:
            pass

    async def handler():
        with lock:
            pass

    hold_while(lock, other)
    hold_while(lock, lambda: asyncio.run(handler()))
    hold_while(lock, fake_loop)
    after = wait_totals()
    for t in waits.THREADS:
        waited, contended = after[t][0] - before[t][0], after[t][1] - before[t][1]
        assert contended == 1, t
        assert 0.03 < waited < 30, t
    assert ann.names == [waits.LOCK_WAIT] * 3


def test_the_timed_lock_serves_the_loop_and_held_run(collector):
    """The scheduler's own loop, waiting for cluster.lock held here, books
    under ``loop``; held runs and their re-entries still work."""
    cs = cluster()
    waits.instrument_serve(cs, telemetry=True)
    assert isinstance(cs.lock, waits.TimedRLock)
    sched = Scheduler(cs, SchedulerConfig(obs=ObsConfig(profile=True)))
    pods = [MakePod().name(f"p{i}").req({"cpu": "1"}).obj() for i in range(8)]
    with sched.held_run():  # the ingest path: one hold, re-entered per pod
        cs.create_pods(pods)
    assert len(cs.list_pods()) == 8
    before = wait_totals()
    hold_while(cs.lock, sched.run_pipelined)
    after = wait_totals()
    assert after["loop"][1] - before["loop"][1] >= 1
    assert after["loop"][0] - before["loop"][0] > 0.03
    assert after["other"] == before["other"] and after["ingest"] == before["ingest"]
    assert all(p.node_name for p in cs.list_pods())


@pytest.mark.parametrize("telemetry", [False, True])
def test_serve_wires_the_lock_and_the_collector(monkeypatch, collector, telemetry):
    """cmd_serve, up to the server: without --telemetry the lock is the
    plain RLock and no gc: / wait: annotation is ever built; the
    collector's histogram is on either way."""
    from kubernetes_tpu.server import extender
    from kubernetes_tpu.utils import compile_cache
    from kubernetes_tpu.utils import logging as structured_logging

    ann = Counting()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    monkeypatch.setattr(structured_logging, "setup", lambda *a, **k: None)
    monkeypatch.setattr(compile_cache, "key_on_op_names", lambda: None)
    served = []
    monkeypatch.setattr(extender, "run_server", lambda cs, **kw: served.append(cs))
    argv = ["serve", "--mode", "scheduler"] + (["--telemetry"] if telemetry else [])
    assert cli.main(argv) == 0
    (cs,) = served
    assert (type(cs.lock) is waits.TimedRLock) is telemetry
    assert (type(cs.lock) is RLOCK) is not telemetry
    hold_while(cs.lock, lambda: cs.list_pods())
    count0 = sample("scheduler_gc_pause_seconds_count", generation="2")
    gc.collect(2)
    assert sample("scheduler_gc_pause_seconds_count", generation="2") >= count0 + 1
    if telemetry:
        assert waits.LOCK_WAIT in ann.names and "gc:gen2" in ann.names
    else:
        assert ann.built("gc:") == [] and ann.built("wait:") == []


def test_stage_annotation_carries_the_threads_cpu_time(tmp_path):
    """``cpu_us``: the thread's CPU time inside the block; a block that
    sleeps spends its wall off the CPU. The seconds the profiler books
    are the clock's, as before."""
    from benchmarks.lib import span_attrib, trace_reduce

    clock = FakeClock(5.0)
    tel = build_telemetry(ObsConfig(profile=True), clock)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tel.stage("dispatch", step=1, pods=2):
            time.sleep(0.1)
            clock.advance(0.5)
        with tel.stage("bind", step=1, pods=2):
            t_end = time.thread_time() + 0.05
            while time.thread_time() < t_end:
                pass
    finally:
        jax.profiler.stop_trace()
    stages = tel.profiler.observe_batch(step=1, pods=2)["stages"]
    assert (stages["dispatch"], stages["bind"]) == (0.5, 0.0)
    capture = span_attrib.load(trace_reduce.find_xplane(str(tmp_path)))
    events = {e[0]: e for th in capture["threads"] for e in th}
    _, _, sleep_ns, sleep_stats = events["stage:dispatch"]
    _, _, busy_ns, busy_stats = events["stage:bind"]
    assert set(sleep_stats) == set(busy_stats) == {"step", "pods", "cpu_us"}
    # within one 10 ms tick, where the host's thread clock counts ticks
    assert sleep_ns > 0.095e9 and sleep_stats["cpu_us"] < 0.25 * sleep_ns / 1e3
    assert busy_stats["cpu_us"] >= 50_000
    assert busy_stats["cpu_us"] <= busy_ns / 1e3 + 10_000


def post(app, body):
    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(app)) as client:
            resp = await client.post("/api/pods", json=body)
            return resp.status

    return asyncio.run(go())


class Recording:
    """An annotation that records its metadata and whether it closed."""

    def __init__(self, log, name, **kw):
        self.log, self.entry = log, {"name": name, **kw, "closed": False}
        log.append(self.entry)

    def set_metadata(self, **kw):
        self.entry.update(kw)

    def __exit__(self, *exc):
        self.entry["closed"] = True


@pytest.mark.parametrize("fails", [False, True])
def test_ingest_annotation_closes_whether_the_body_applies_or_raises(fails):
    cs = cluster()
    sched = Scheduler(cs, SchedulerConfig(obs=ObsConfig(profile=True)))
    log = []
    sched.telemetry.annotation = lambda name, **kw: Recording(log, name, **kw)
    if fails:
        def refuse(pods):
            raise RuntimeError("store refused the body")

        cs.create_pods = refuse
    app = make_app(ExtenderCore(cs, backend="oracle"), scheduler=sched)
    body = {"items": [MakePod().name(f"p{i}").req({"cpu": "1"}).obj().to_dict()
                      for i in range(4)]}
    assert post(app, body) == (500 if fails else 200)
    (entry,) = [e for e in log if e["name"] == "stage:ingest"]
    assert entry["closed"] and entry["pods"] == (0 if fails else 4)
    assert entry["cpu_us"] >= 0
