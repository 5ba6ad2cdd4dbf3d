"""The child that holds the chip: ``python -m kubernetes_tpu <argv>`` on
the main thread, unchanged, plus one side thread that answers the
benchmark's requests on stdin. Only the process that holds the chip can
read its memory statistics or trace it, and the program has neither a
memory gauge nor a bounded profiler capture; this wrapper adds both
from the outside and edits nothing of the program.

    python benchmarks/lib/serve_child.py <checkout root> <argv of kubernetes_tpu.cli.main ...>

Requests, one a line, each answered by a JSON file written atomically
at ``<ack path>``:

    mem <ack path>                 peak_bytes_in_use of each local device
    trace_start <dir> <ack path>   jax.profiler.start_trace(dir), Python tracer off
    trace_stop <ack path>          jax.profiler.stop_trace()
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


ANCHOR = "bench_anchor"


def _ack(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def _serve_requests() -> None:
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            import jax

            if words[0] == "mem":
                stats = [d.memory_stats() or {} for d in jax.local_devices()]
                _ack(
                    words[1],
                    {
                        "peak_bytes_in_use": [
                            s.get("peak_bytes_in_use") for s in stats
                        ],
                        "bytes_limit": [s.get("bytes_limit") for s in stats],
                    },
                )
            elif words[0] == "trace_start":
                # the Python tracer is off: at one event per call it
                # halves the server's speed and fills the trace
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                t_ask = time.monotonic()
                jax.profiler.start_trace(words[1], profiler_options=opts)
                # an event of our own ties the trace's clock to the
                # host's monotonic clock (lib/trace_reduce.py)
                t_anchor = time.monotonic()
                with jax.profiler.TraceAnnotation(ANCHOR):
                    pass
                _ack(
                    words[2],
                    {"t_ask": t_ask, "t_anchor": t_anchor, "t_on": time.monotonic()},
                )
            elif words[0] == "trace_stop":
                t_ask = time.monotonic()
                jax.profiler.stop_trace()
                _ack(words[1], {"t_ask": t_ask, "t_off": time.monotonic()})
            else:
                raise ValueError(f"unknown request {words[0]!r}")
        except Exception as e:  # the server must keep running; say why
            _ack(words[-1], {"error": f"{type(e).__name__}: {e}"})


def main() -> int:
    root, argv = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, root)
    from kubernetes_tpu.cli import main as cli_main

    threading.Thread(target=_serve_requests, daemon=True).start()
    return cli_main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
