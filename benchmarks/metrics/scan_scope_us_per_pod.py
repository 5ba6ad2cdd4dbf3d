"""Device self time under the named jax.named_scope names, per pod
bound in the traced span. None where no operation of the capture
carries any scope (a program without them, or another program's
executables out of a warm compile cache)."""

from benchmarks.lib import span_attrib


def read(ctx, scopes):
    got = span_attrib.for_cell(ctx)
    traced = ctx["traced"]
    if not got or got["scope_s"] is None or not traced or not traced["pods"]:
        return None
    return sum(got["scope_s"].get(s, 0.0) for s in scopes) / traced["pods"] * 1e6
