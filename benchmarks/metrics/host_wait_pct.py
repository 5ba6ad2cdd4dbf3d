"""What held the host's threads, as a share: device-idle time under a
collection or under the loop's wait for cluster.lock, % of the traced
span, or the loop's stage time spent off the CPU, % of that time
(lib/host_waits.py). None on a capture of a program without those
instruments."""

from benchmarks.lib import host_waits


def read(ctx, reading):
    return host_waits.pct(ctx, reading)
