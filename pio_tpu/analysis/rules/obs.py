"""Observability rules: outbound HTTP must ride the shared client.

``utils/httpclient.py`` is the ONE outbound HTTP implementation in
pio_tpu/ — it injects the ``traceparent`` header (pio_tpu/obs/), honors
the ambient Deadline conventions, and passes through the chaos
injection point, so every cross-process hop joins the caller's trace
and every drill can reach it. A raw ``urllib.request.urlopen`` /
``http.client.HTTPConnection`` / ``requests.*`` call elsewhere silently
DROPS all three: the hop disappears from span trees, outlives its
request budget, and is invisible to chaos drills.

  * `raw-http` — a raw outbound HTTP call in ``pio_tpu/`` outside the
    sanctioned client. The client implementation itself suppresses with
    a justification (the one place the urllib call may live), as does
    genuinely non-RPC byte fetching (template gallery downloads).

Scope: ``pio_tpu/`` only. Tests, the benchmark's drivers and eval/
scripts drive servers from OUTSIDE the traced topology, where raw
clients are the point (e.g. measuring without client-side
instrumentation).
"""

from __future__ import annotations

import ast
from typing import Iterator

from pio_tpu.analysis.engine import ModuleContext
from pio_tpu.analysis.findings import Finding, Severity

# canonical call names that perform an outbound HTTP request
_RAW_HTTP_CALLS = frozenset({
    "urllib.request.urlopen",
    "urllib.request.urlretrieve",
    "http.client.HTTPConnection",
    "http.client.HTTPSConnection",
    "requests.get", "requests.post", "requests.put", "requests.delete",
    "requests.head", "requests.patch", "requests.request",
    "requests.Session",
})


class ObsRule:
    id = "obs"
    ids = ("raw-http",)

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if "pio_tpu/" not in path:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.imports.canonical(node.func)
            if name not in _RAW_HTTP_CALLS:
                continue
            yield Finding(
                "raw-http", Severity.WARNING, ctx.path, node.lineno,
                node.col_offset,
                f"raw outbound HTTP via {name}(): bypasses "
                "pio_tpu.utils.httpclient.JsonHttpClient, silently "
                "dropping trace-context propagation (traceparent), "
                "deadline conventions, and the chaos injection point — "
                "the hop vanishes from `pio trace` trees and outlives "
                "its request budget; use JsonHttpClient (or suppress "
                "with justification where raw bytes, not JSON RPC, are "
                "genuinely required)")
