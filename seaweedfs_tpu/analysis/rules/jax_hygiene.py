"""JAX hygiene in jitted code and the pipelined feed path.

Two contracts:

1. No host-sync primitive — ``.item()``, ``block_until_ready``,
   ``jax.device_get``, ``np.asarray``/``np.array`` of a traced value —
   inside a ``@jax.jit``-decorated function. Under trace these either
   raise ``ConcretizationTypeError`` at runtime or, worse, silently
   constant-fold a value that should be data-dependent.

2. In the pipelined feed modules (ops/codec_pallas.py, ops/feed.py,
   ops/codec_mesh.py, models/ec_pipeline.py, ec/probe.py) the
   double-buffered overlap is the whole point: a stray
   ``block_until_ready``/``device_get`` on the submit path
   re-serialises upload and compute and the measured H2D/kernel
   overlap collapses. Sync primitives are allowed only in
   the named drain-site functions below (the upload/drain workers and
   host readbacks, where blocking IS the contract).
"""
from __future__ import annotations

import ast

from ..engine import PKG_PREFIX, Rule, register

FEED_MODULES = (
    "ops/codec_pallas.py",
    "ops/feed.py",
    "ops/codec_mesh.py",
    "models/ec_pipeline.py",
    "ec/probe.py",
)

# drain sites: functions whose contract is "block here" — the staged
# feed's upload/drain workers and the host readback helpers
ALLOWED_SYNC_FUNCS = {"upload", "drain", "finish", "up", "down",
                      "_readback", "_collect"}


def _is_jitted(func: ast.AST) -> bool:
    for dec in getattr(func, "decorator_list", ()):
        for node in ast.walk(dec):
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("jit", "pjit"):
                return True
            if isinstance(node, ast.Name) and node.id in ("jit", "pjit"):
                return True
    return False


def _sync_reason(node: ast.Call) -> str | None:
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    if f.attr == "block_until_ready":
        return "block_until_ready"
    if f.attr == "device_get" and isinstance(f.value, ast.Name) and \
            f.value.id == "jax":
        return "jax.device_get"
    if f.attr == "item" and not node.args and not node.keywords:
        return ".item()"
    return None


@register
class JaxHygieneRule(Rule):
    name = "jax-hygiene"
    description = ("no host-sync primitives inside jitted functions or "
                   "on the pipelined feed's submit path (allowlisted "
                   "drain sites only)")

    def wants(self, rel: str) -> bool:
        return rel.startswith(PKG_PREFIX) and rel.endswith(".py")

    def visit_Call(self, ctx, node: ast.Call) -> None:
        reason = _sync_reason(node)
        in_feed = (ctx.in_pkg() or "") in FEED_MODULES
        jitted = [fn for fn in ctx.func_stack if _is_jitted(fn)]
        if jitted:
            f = node.func
            np_conv = (isinstance(f, ast.Attribute)
                       and f.attr in ("asarray", "array")
                       and isinstance(f.value, ast.Name)
                       and f.value.id == "np")
            if reason or np_conv:
                self.report(ctx, node,
                            f"{reason or 'np.' + f.attr} inside jitted "
                            f"function {jitted[-1].name!r} — "
                            "concretizes a traced value")
            return
        if not in_feed or reason is None:
            return
        ctx.run.stats["feed_sync_sites"] = \
            ctx.run.stats.get("feed_sync_sites", 0) + 1
        fn_names = {getattr(fn, "name", "") for fn in ctx.func_stack}
        if not fn_names & ALLOWED_SYNC_FUNCS:
            self.report(ctx, node,
                        f"{reason} on the feed path outside the "
                        "allowlisted drain sites "
                        f"({', '.join(sorted(ALLOWED_SYNC_FUNCS))}) — "
                        "re-serialises the upload/compute overlap")
