"""Source `harness`: what the harness reads itself, on its own clock (a
query's wall, the oracle's, set-up by part: `run.parts`) or from the device
and JAX's compile-cache events.  spec["read"]: {"key": k}
"""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    return ctx["harness"].get(spec["read"]["key"])
