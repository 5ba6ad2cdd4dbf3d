"""Bound: bytes. One constraint family's kernel against its roofline:
the family's least bytes over HBM bandwidth, over the device self time
under the family's scope (``jax.named_scope``, ``span_attrib``) in the
traced span.

The bytes are counted from the cell's shapes and NOT from the program's
buffers, so they read the same work whatever implements the filter:
lib/solve_work.py's part for one family (``FAMILY_BYTES``), that is
once a solve one occupancy word per node, and per pod placed that
node's word written:

    FAMILY_BYTES x (solves x nodes + pods)

None where the run was not traced, nothing was bound or solved in the
span, or no device time was under the scope (a cell without the family,
or a capture without scopes)."""

from benchmarks.lib import span_attrib
from benchmarks.lib.solve_work import FAMILY_BYTES


def read(ctx, scope):
    traced = ctx.get("traced")
    if not traced or not traced["pods"] or not traced["solves"]:
        return None
    got = span_attrib.for_cell(ctx)
    busy = got and got["scope_s"] and got["scope_s"].get(scope, 0.0)
    if not busy:
        return None
    nodes = int(ctx["config"]["nodes"]["count"])
    least_bytes = FAMILY_BYTES * (traced["solves"] * nodes + traced["pods"])
    least = least_bytes / float(ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / busy
