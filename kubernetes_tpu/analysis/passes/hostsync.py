"""TPU001 — host-sync-in-hot-path.

The batched solve only wins while the hot path stays on-device: one
accidental ``np.asarray``/``int()`` on a traced or device value inside
the solve loop blocks the host on the device and re-serializes every
batch (the dispatch loop can no longer run ahead of the solve).

Scope (see callgraph.ModuleGraph): functions wrapped by ``jax.jit`` and
everything reachable from them intra-module (*traced scope*), plus
functions registered hot via ``# ktpu: hot`` and their reachable set
(*hot scope*). The two sanctioned deferred-read points in
registry.SANCTIONED_SYNC_POINTS are exempt and stop propagation.

Flagged primitives:

- ``np.asarray`` / ``np.array`` / ``numpy.*`` (both scopes) — a forced
  device->host transfer when the argument is a device value; in traced
  code it is a trace-time failure or a silently baked constant.
- ``.block_until_ready()`` and ``.tolist()`` (both scopes) — explicit
  sync points.
- ``float()`` / ``int()`` / ``bool()`` on non-literal arguments (traced
  scope only) — tracer coercions. Host-side hot code coerces numpy
  scalars legitimately, so hot scope skips this sub-rule; device reads
  there must still route through the sanctioned points.
"""

from __future__ import annotations

import ast

from ..callgraph import own_nodes, scoped_graph
from ..core import Finding, Pass

_NP_BASES = {"np", "numpy", "onp"}
_NP_FUNCS = {"asarray", "array"}
_SYNC_METHODS = {"block_until_ready", "tolist"}
_COERCIONS = {"float", "int", "bool"}


def _is_np_transfer(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr in _NP_FUNCS
        and isinstance(f.value, ast.Name)
        and f.value.id in _NP_BASES
    )


def _is_sync_method(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS:
        return f.attr
    return None


def _is_coercion(call: ast.Call) -> str | None:
    f = call.func
    if (
        isinstance(f, ast.Name)
        and f.id in _COERCIONS
        and call.args
        and not all(isinstance(a, ast.Constant) for a in call.args)
    ):
        return f.id
    return None


class HostSyncPass(Pass):
    rule = "TPU001"
    title = "host sync in hot path"

    def run(self, module, ctx):
        graph, traced, hot = scoped_graph(module, ctx)
        findings: list[Finding] = []
        for qual in sorted(traced | hot):
            info = graph.functions.get(qual)
            if info is None:
                continue
            in_traced = qual in traced
            where = "jit-traced" if in_traced else "hot-path"
            for node in own_nodes(info.node):
                if not isinstance(node, ast.Call):
                    continue
                if _is_np_transfer(node):
                    findings.append(
                        Finding(
                            self.rule, module.path, node.lineno,
                            f"numpy transfer ({ast.unparse(node.func)}) in "
                            f"{where} function '{qual}' forces a "
                            "device->host sync",
                            hint="keep the value on-device (jnp), or read "
                            "it through a sanctioned deferred-read point",
                        )
                    )
                    continue
                meth = _is_sync_method(node)
                if meth is not None:
                    findings.append(
                        Finding(
                            self.rule, module.path, node.lineno,
                            f".{meth}() in {where} function '{qual}' "
                            "blocks on the device",
                            hint="defer the read past the overlapped host "
                            "work, or move it off the hot path",
                        )
                    )
                    continue
                if in_traced:
                    co = _is_coercion(node)
                    if co is not None:
                        findings.append(
                            Finding(
                                self.rule, module.path, node.lineno,
                                f"{co}() coercion in jit-traced function "
                                f"'{qual}' concretizes a traced value",
                                hint="use jnp ops on the tracer; coerce "
                                "only static (Python) arguments",
                            )
                        )
        return findings
