"""No bare device synchronization in serving code (the lint formerly
in test_lint_device_sync.py).

Serving packages (server/, filer/, s3/, mount/) must never touch the
accelerator directly: a bare ``jax.device_get``/``.block_until_ready``
stalls a request thread behind the host<->device link for the
whole transfer, and an argless ``device_put(x)`` uploads to an
UNCOMMITTED default device — XLA is then free to re-copy the array per
executable. All device traffic belongs in the staged pipeline
(ops/codec_pallas.py, ops/feed.py) behind the measured router
(ec/backend.py).
"""
from __future__ import annotations

import ast

from ..engine import PKG_PREFIX, Rule, register

SERVING_DIRS = ("server/", "filer/", "s3/", "mount/")


@register
class DeviceSyncRule(Rule):
    name = "device-sync"
    description = ("no jax.device_get / .block_until_ready / "
                   "uncommitted device_put in serving code")

    def wants(self, rel: str) -> bool:
        if not rel.startswith(PKG_PREFIX) or not rel.endswith(".py"):
            return False
        return rel[len(PKG_PREFIX):].startswith(SERVING_DIRS)

    def visit_Call(self, ctx, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "device_get" and \
                isinstance(f.value, ast.Name) and f.value.id == "jax":
            self.report(ctx, node, "jax.device_get — synchronous D2H "
                        "in a request thread")
        elif isinstance(f, ast.Attribute) and \
                f.attr == "block_until_ready":
            self.report(ctx, node, ".block_until_ready() — blocks the "
                        "request thread on the device")
        elif ((isinstance(f, ast.Name) and f.id == "device_put")
              or (isinstance(f, ast.Attribute)
                  and f.attr == "device_put")):
            if len(node.args) + len(node.keywords) < 2:
                self.report(ctx, node, "device_put with no placement — "
                            "uncommitted upload, XLA may re-copy per "
                            "executable")
