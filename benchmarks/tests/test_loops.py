"""The load loop against a fake server (no program, no chip)."""

import json
import time

from benchmarks.lib import files, gen, loops


class FakeServe:
    def __init__(self, hold_s=0.0):
        self.hold_s = hold_s
        self.bodies = []

    def post_pods(self, body):
        time.sleep(self.hold_s)
        items = json.loads(body)["items"]
        self.bodies.append(len(items))
        return len(items)

    def require_alive(self):
        pass


class FakeTail:
    """Binds everything posted, one poll later."""

    def __init__(self, offer):
        self.offer = offer
        self.n_bound = 0

    def poll(self):
        self.n_bound = self.offer.n_posted
        return self.n_bound


def cfg():
    return files.load_config("sched-perf-spread-5000n")


def test_backlog_keeps_the_queue_at_depth_and_stops_at_the_cap():
    serve = FakeServe()
    offer = loops.Offer(cfg(), serve)

    class NeverBinds(FakeTail):
        def poll(self):
            return 0

    loops.backlog(
        offer, NeverBinds(offer), gen.RolloutStream(cfg(), 5), depth=300,
        chunk=100, stop_at=time.monotonic() + 0.2,
    )
    assert offer.n_posted == 400  # depth + one chunk, then it waits
    try:
        loops.backlog(
            offer, FakeTail(offer), gen.RolloutStream(cfg(), 6, wave_base=900),
            depth=300, chunk=100, stop_at=time.monotonic() + 5.0, max_offered=1000,
        )
    except RuntimeError as e:
        assert "validWhile.maxPodsOffered" in str(e)
    else:
        raise AssertionError("the cap did not stop the loop")
