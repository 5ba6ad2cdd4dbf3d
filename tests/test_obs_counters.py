"""Counters that put a number on what was only seen from outside
(PR 25): the POST /api/pods handler's own seconds and pods, and the
decision journal's own seconds beside its record count."""

import asyncio

import pytest

from kubernetes_tpu import metrics
from kubernetes_tpu.api.wrappers import MakeNode, MakePod
from kubernetes_tpu.obs import ObsConfig
from kubernetes_tpu.obs.journal import PodDecisionJournal
from kubernetes_tpu.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.server.extender import ExtenderCore, make_app
from kubernetes_tpu.state.cluster import ClusterState
from kubernetes_tpu.utils.clock import FakeClock


def cell(counter) -> float:
    return counter._value.get()


def journal_records() -> float:
    return sum(
        c._value.get() for c in metrics.journal_records_total._metrics.values()
    )


class TickClock(FakeClock):
    """Every perf() read is a millisecond later than the one before."""

    def perf(self) -> float:
        self.advance(0.001)
        return self._now


def cluster(nodes=4):
    cs = ClusterState()
    for i in range(nodes):
        cs.create_node(
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"}).obj()
        )
    return cs


@pytest.mark.parametrize("telemetry", [False, True])
def test_ingest_counters_count_the_handler(telemetry):
    cs = cluster()
    sched = Scheduler(
        cs, SchedulerConfig(obs=ObsConfig(profile=True) if telemetry else None)
    )
    annotations = []
    if telemetry:
        real = sched.telemetry.annotation

        def recording(name, **kw):
            annotations.append(name)
            return real(name, **kw)

        sched.telemetry.annotation = recording
    app = make_app(ExtenderCore(cs, backend="oracle"), scheduler=sched)
    s0, p0 = cell(metrics.ingest_seconds_total), cell(metrics.ingest_pods_total)

    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(app)) as client:
            for lo in (0, 8):
                pods = {"items": [
                    MakePod().name(f"p{i}").req({"cpu": "1"}).obj().to_dict()
                    for i in range(lo, lo + 8)
                ]}
                resp = await client.post("/api/pods", json=pods)
                assert (resp.status, await resp.json()) == (200, {"applied": 8})
            one = MakePod().name("single").req({"cpu": "1"}).obj().to_dict()
            resp = await client.post("/api/pods", json=one)
            assert await resp.json() == {"applied": 1}

    asyncio.run(go())
    assert cell(metrics.ingest_pods_total) - p0 == 17
    spent = cell(metrics.ingest_seconds_total) - s0
    assert 0.0 < spent < 5.0
    assert len(cs.list_pods()) == 17
    assert [a for a in annotations if a == "stage:ingest"] == (
        ["stage:ingest"] * 3 if telemetry else []
    )


def test_journal_seconds_move_with_the_record_count():
    journal = PodDecisionJournal(clock=TickClock(), sink=lambda rec: None)
    pod = MakePod().name("p").obj()
    journal.lines  # nothing pending: no flush, no tick
    r0, s0 = journal_records(), cell(metrics.journal_seconds_total)
    for i in range(4095):
        journal.record(1, i, pod, "bound", node="n0")
    # both counters wait for the flush at 4,096 pending records
    assert journal_records() - r0 == 0
    assert cell(metrics.journal_seconds_total) - s0 == 0.0
    journal.record(1, 4095, pod, "bound", node="n0")
    assert journal_records() - r0 == 4096
    # a tick a record (its two reads) and one for the flush's two
    assert cell(metrics.journal_seconds_total) - s0 == pytest.approx(4.097)
    journal.record(1, 4096, pod, "unschedulable")
    assert len(journal.lines) == 4097  # a read flushes the rest
    assert journal_records() - r0 == 4097
    assert cell(metrics.journal_seconds_total) - s0 == pytest.approx(4.099)


def test_journal_seconds_on_a_scheduler_run_are_part_of_the_bind_stage(tmp_path):
    cs = cluster()
    sched = Scheduler(
        cs,
        SchedulerConfig(
            batch_size=8,
            obs=ObsConfig(
                journal=True, profile=True,
                journal_path=str(tmp_path / "journal.jsonl"),
            ),
        ),
    )
    for i in range(24):
        cs.create_pod(MakePod().name(f"p{i}").req({"cpu": "1"}).obj())
    r0, s0 = journal_records(), cell(metrics.journal_seconds_total)
    sched.run_pipelined()
    assert len(sched.journal.lines) == 24  # flushes the counters too
    assert journal_records() - r0 == 24
    spent = cell(metrics.journal_seconds_total) - s0
    bind = sched.telemetry.profiler.snapshot()["stage_seconds"]["bind"]
    assert 0.0 < spent < bind
    assert len((tmp_path / "journal.jsonl").read_text().splitlines()) == 24
