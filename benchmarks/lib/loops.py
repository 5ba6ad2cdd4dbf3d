"""The load loop, one process, one thread: it drives ``POST /api/pods``
on the child and tails the journal the child streams.

``backlog`` is closed on backlog depth: it posts chunks and keeps
posted - bound >= depth, so the scheduler always finds a full queue.
"""

from __future__ import annotations

import time

from . import gen


class Offer:
    """Everything offered to the child in this run, and the generator's
    own spans around each POST."""

    def __init__(self, cfg: dict, serve) -> None:
        self.cfg = cfg
        self.serve = serve
        self.specs: dict[str, gen.PodSpec] = {}  # pod key -> spec
        self.order: list[str] = []  # pod keys in posting order
        # (t_send, t_done, pods): the generator's spans
        self.posts: list[tuple] = []

    @property
    def n_posted(self) -> int:
        return len(self.order)

    def post(self, specs: list) -> None:
        body = gen.pods_body(self.cfg, specs)
        t_send = time.monotonic()
        applied = self.serve.post_pods(body)
        t_done = time.monotonic()
        if applied != len(specs):
            raise RuntimeError(f"/api/pods applied {applied} of {len(specs)}")
        self.posts.append((t_send, t_done, len(specs)))
        for s in specs:
            self.specs[s.key] = s
            self.order.append(s.key)


def wait_bound(serve, tail, target: int, timeout: float) -> None:
    """Until ``target`` pods are bound in the journal."""
    deadline = time.monotonic() + timeout
    while tail.poll() < target:
        serve.require_alive()
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"{tail.n_bound}/{target} pods bound after {timeout}s; "
                f"other decisions: {tail.other[:3]}"
            )
        time.sleep(0.005)


def backlog(
    offer: Offer, tail, stream, depth: int, chunk: int,
    stop_at: float | None = None, until_bound: int | None = None,
    until=None, max_offered: int | None = None, timeout: float = 1500.0,
) -> None:
    """Keep posted - bound >= depth until ``stop_at`` (monotonic), until
    ``until_bound`` pods are bound, or until ``until()`` holds."""
    deadline = time.monotonic() + timeout
    while True:
        bound = tail.poll()
        now = time.monotonic()
        if stop_at is not None and now >= stop_at:
            return
        if until_bound is not None and bound >= until_bound:
            return
        if until is not None and until():
            return
        if now > deadline:
            raise TimeoutError(f"backlog loop: {bound} bound after {timeout}s")
        if offer.n_posted - bound < depth + chunk:
            if max_offered is not None and offer.n_posted + chunk > max_offered:
                raise RuntimeError(
                    f"{offer.n_posted} pods offered: one more chunk passes "
                    f"the configuration's validWhile.maxPodsOffered "
                    f"({max_offered}); the cluster would fill inside the run"
                )
            offer.post(stream.take(chunk))
        else:
            offer.serve.require_alive()
            time.sleep(0.002)
