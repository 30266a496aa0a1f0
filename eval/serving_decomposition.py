"""Serving-latency decomposition: device round trip vs host compute vs
HTTP vs batching — one p50 with no split says nothing about where a
query's time goes.

Method (all medians; this box swings 10x on scheduler hiccups):
 1. device_roundtrip: tiny jitted op, dispatch + scalar readback — the
    floor every device-touching predict pays: microseconds (CPU) to
    ~0.2 ms (PCIe TPU host); over a remote link it is that link's
    round trip plus the dispatch floor.
 2. direct_query: QueryServer.query() in-process, no HTTP — supplement
    + predict (device dispatch + topk) + serve, via the production code
    path. The tracer's span histograms give the internal split.
 3. http_query: POST /queries.json over loopback — (3)-(2) isolates
    HTTP parse/encode + socket cost.
 4. batched: query_batch at depth B — per-query device amortization.
 5. Projection: co-located p50 = http_query_p50 - (device_roundtrip -
    assumed co-located roundtrip). The assumption is a PARAMETER
    (default 0.2 ms, the typical PCIe-attached-TPU dispatch floor;
    0.0 reproduces the raw subtraction) and is recorded in the
    artifact — this is a stated-methodology projection, not a
    measurement.

Writes eval/SERVING_DECOMP.{json,md}.
Usage: python eval/serving_decomposition.py [--cpu] [--colocated-ms 0.2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pcts(lat_s: list[float]) -> dict:
    ms = sorted(x * 1e3 for x in lat_s)

    def pct(p):
        return ms[min(len(ms) - 1, int(p / 100 * len(ms)))]

    return {"p50_ms": round(pct(50), 3), "p90_ms": round(pct(90), 3),
            "p99_ms": round(pct(99), 3), "n": len(ms)}


def build(n_users=5000, n_items=1500, n_events=100_000):
    import numpy as np

    from pio_tpu.controller import EngineParams
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import Storage
    from pio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.serve import (
        QueryServer, ServingConfig, create_query_server,
    )
    from pio_tpu.workflow.train import run_train

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_metadata_apps().insert(App(0, "decompapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(0)
    uu = rng.integers(0, n_users, n_events)
    ii = rng.integers(0, n_items, n_events)
    events = [
        Event(event="rate", entity_type="user", entity_id=f"u{uu[m]}",
              target_entity_type="item", target_entity_id=f"i{ii[m]}",
              properties=DataMap({"rating": int(rng.integers(1, 6))}))
        for m in range(n_events)
    ]
    ev.insert_batch(events, app_id)
    engine = RecommendationEngine.apply()
    ep = EngineParams(
        datasource=("", DataSourceParams(app_name="decompapp")),
        algorithms=[("als", ALSAlgorithmParams(
            rank=32, num_iterations=5, lambda_=0.05, chunk=8192))],
    )
    ctx = create_workflow_context(storage, use_mesh=False)
    run_train(engine, ep, storage, engine_id="decomp", ctx=ctx)
    config = ServingConfig(
        ip="127.0.0.1", port=0, engine_id="decomp",
        warm_query={"user": "u1", "num": 10}, backend="async",
    )
    http, qs = create_query_server(engine, ep, storage, config, ctx=ctx)
    http.start()
    return http, qs, n_users


def measure_device_roundtrip(reps=25) -> float:
    import jax
    import jax.numpy as jnp

    one = jnp.ones(())
    add = jax.jit(lambda x: x + 1)
    jax.block_until_ready(add(one))
    rtts = []
    for _ in range(reps):
        t0 = time.monotonic()
        jax.block_until_ready(add(one))
        rtts.append(time.monotonic() - t0)
    return statistics.median(rtts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--colocated-ms", type=float, default=0.2,
                    help="assumed co-located device roundtrip for the "
                         "projection (PCIe TPU host typical)")
    ap.add_argument("--n", type=int, default=300)
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    device_kind = jax.devices()[0].device_kind
    http, qs, n_users = build()
    out: dict = {"device_kind": device_kind,
                 "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    try:
        # 1. raw device roundtrip (the link's floor)
        rtt_s = measure_device_roundtrip()
        out["device_roundtrip_ms"] = round(rtt_s * 1e3, 3)

        # 2. direct in-process query (production path, no HTTP)
        direct = []
        for r in range(args.n + 20):
            q = {"user": f"u{r % n_users}", "num": 10}
            t0 = time.monotonic()
            qs.query(q, record=r >= 20)
            if r >= 20:
                direct.append(time.monotonic() - t0)
        out["direct_query"] = pcts(direct)
        # tracer split of the same calls (supplement/predict/serve spans);
        # histogram values are seconds — report ms
        spans = {}
        for name, h in qs.tracer.snapshot().items():
            if h.get("count"):
                spans[name] = {k: round(v * 1e3, 3) for k, v in h.items()
                               if k.startswith("p")}
        out["span_split"] = spans

        # 3. loopback HTTP
        import http.client as hc

        conn = hc.HTTPConnection("127.0.0.1", http.port, timeout=30)
        hlat = []
        for r in range(args.n + 20):
            q = json.dumps({"user": f"u{r % n_users}", "num": 10})
            t0 = time.monotonic()
            conn.request("POST", "/queries.json", body=q.encode())
            conn.getresponse().read()
            if r >= 20:
                hlat.append(time.monotonic() - t0)
        conn.close()
        out["http_query"] = pcts(hlat)

        # 4. batched device amortization
        for depth in (8, 32):
            qlist = [{"user": f"u{i % n_users}", "num": 10}
                     for i in range(depth)]
            qs.query_batch(qlist, record=False)   # warm the bucket
            bl = []
            for _ in range(max(args.n // depth, 10)):
                t0 = time.monotonic()
                qs.query_batch(qlist, record=False)
                bl.append((time.monotonic() - t0) / depth)
            out[f"batched_per_query_ms_depth{depth}"] = round(
                statistics.median(bl) * 1e3, 3)

        # decomposition + projection
        d50 = out["direct_query"]["p50_ms"]
        h50 = out["http_query"]["p50_ms"]
        rtt = out["device_roundtrip_ms"]
        out["decomposition"] = {
            "device_roundtrip_ms": rtt,
            "host_compute_ms": round(max(d50 - rtt, 0.0), 3),
            "http_overhead_ms": round(max(h50 - d50, 0.0), 3),
        }
        # NOTE no clamp: when the measured roundtrip is CHEAPER than the
        # assumed co-located one (CPU run), the projection goes UP — a
        # co-located TPU dispatch costs more than a local CPU dispatch,
        # and the artifact must match its stated method exactly
        delta = rtt - args.colocated_ms
        out["projection"] = {
            "assumed_colocated_roundtrip_ms": args.colocated_ms,
            "method": "http_p50 - (device_roundtrip - assumed); valid "
                      "because a predict pays exactly one device "
                      "dispatch (span_split.predict covers it)",
            "colocated_p50_ms": round(h50 - delta, 3),
            "colocated_p99_ms": round(
                out["http_query"]["p99_ms"] - delta, 3),
        }
    finally:
        http.stop()
        qs.close()

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "SERVING_DECOMP.json"), "w") as f:
        json.dump(out, f, indent=1)
    dec = out["decomposition"]
    proj = out["projection"]
    with open(os.path.join(here, "SERVING_DECOMP.md"), "w") as f:
        f.write(f"""# Serving latency decomposition ({device_kind})

Generated {out['ts']} by eval/serving_decomposition.py.

| component | ms |
|---|---|
| device roundtrip (link floor) | {dec['device_roundtrip_ms']} |
| host compute (supplement+topk+serve) | {dec['host_compute_ms']} |
| HTTP parse/encode/socket | {dec['http_overhead_ms']} |
| **measured loopback p50** | **{out['http_query']['p50_ms']}** |

Batched per-query device cost: depth 8 = {out.get('batched_per_query_ms_depth8')} ms,
depth 32 = {out.get('batched_per_query_ms_depth32')} ms.

Co-located projection (assumed roundtrip
{proj['assumed_colocated_roundtrip_ms']} ms): p50 ≈
**{proj['colocated_p50_ms']} ms**, p99 ≈ {proj['colocated_p99_ms']} ms.
Method: {proj['method']}.

Span split (tracer quantiles, ms): {json.dumps(out['span_split'])}
""")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
