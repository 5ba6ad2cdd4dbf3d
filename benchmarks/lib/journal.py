"""Reader of the decision journal the child streams (``serve
--obs-journal``): one JSON object per line, flushed per record, each
``dec`` record stamped ``t = time.monotonic()`` at the bind commit and
``step``, the id of the popped batch it came from. CLOCK_MONOTONIC is
one clock for every process on the machine, so the generator's own
``time.monotonic()`` stamps subtract from it."""

from __future__ import annotations

import json
import os


class JournalTail:
    """Incremental reader; records are kept in file (= commit) order."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._pos = 0
        self._rest = b""
        self.keys: list[str] = []  # pod key of each bound record
        self.nodes: list[str] = []
        self.times: list[float] = []
        self.steps: list[int] = []  # the batch each bound record came from
        self.other: list[dict] = []  # every decision that is not "bound"

    @property
    def n_bound(self) -> int:
        return len(self.keys)

    def poll(self) -> int:
        """Read what was appended; returns the bound count."""
        if not os.path.exists(self.path):
            return 0
        with open(self.path, "rb") as f:
            f.seek(self._pos)
            data = f.read()
        if not data:
            return len(self.keys)
        self._pos += len(data)
        lines = (self._rest + data).split(b"\n")
        self._rest = lines.pop()  # an unfinished line waits for its end
        for line in lines:
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("k") != "dec":
                continue
            if "step" not in rec:
                raise ValueError(f"{self.path}: a decision without its batch's step: {rec}")
            if rec["outcome"] == "bound":
                self.keys.append(rec["pod"])
                self.nodes.append(rec["node"])
                self.times.append(rec["t"])
                self.steps.append(rec["step"])
            else:
                self.other.append(rec)
        return len(self.keys)


def batch_done_after(times: list, steps: list, t: float) -> bool:
    """Whether the batch of the first bound record stamped at or after
    ``t`` is complete: a record of a later batch follows it (the program
    commits its batches in order). Looks back from the newest record."""
    first = None
    for i in range(len(times) - 1, -1, -1):
        if times[i] < t:
            break
        first = i
    return first is not None and any(s > steps[first] for s in steps[first + 1:])
