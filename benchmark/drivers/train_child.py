"""The child that holds the chip(s) for a train cell.

    python -m benchmark.drivers.train_child <spec.json>

Set-up: the device report, the seeded ratings, one whole `run_train`
(which compiles, or loads from the persistent cache, every program the
window will use). Window: `run_train` back to back until the time is up;
the job in flight then is finished and counted whole. After the window:
the last job's model is loaded by the path `pio deploy` uses and checked
(benchmark/harness/check_train.py). Every job carries its `train spans:`
record (benchmark/harness/program.py). With `trace`, the window runs
under the jax profiler and the trace is reduced here, where jax is.
"""

from __future__ import annotations

import json
import logging
import os
import re
import sys
import time

import numpy as np

_STAGES = re.compile(r"train stages: read ([\d.]+)s, prepare ([\d.]+)s, "
                     r"algorithms ([\d.]+)s")
_TIMING = re.compile(r"train timing: engine\.train ([\d.]+)s, of which "
                     r"compile ([\d.]+)s over (\d+) programs \((\d+) "
                     r"persistent-cache hits\); persist ([\d.]+)s")


class JobLog(logging.Handler):
    """The two INFO records `run_train` writes for each job."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.job: dict = {}

    def emit(self, record: logging.LogRecord) -> None:
        text = record.getMessage()
        if m := _STAGES.search(text):
            self.job.update(read_s=float(m[1]), prepare_s=float(m[2]),
                            algorithms_s=float(m[3]))
        elif m := _TIMING.search(text):
            self.job.update(train_s=float(m[1]), compile_s=float(m[2]),
                            programs=int(m[3]), cache_hits=int(m[4]),
                            persist_s=float(m[5]))


def device_report(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak(jax) -> tuple[int, list]:
    """Peak bytes on the fullest local device, and every device's whole
    report. `peak_bytes_in_use` counts live arrays only; what a loaded
    program reserves for its temporaries is `peak_bytes_reserved`, a
    separate part of the device's memory (on the v5e the two and the
    largest free block add up to the limit), so the peak is their sum."""
    stats = [dev.memory_stats() or {} for dev in jax.local_devices()]
    return max((s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0) for s in stats),
               default=0), stats


def insert_events(storage, app_name, user_idx, item_idx, values) -> None:
    """`source: "events"`: one `rate` event per rating, with explicit
    ascending event times, written through the store's own batch insert."""
    from datetime import datetime, timedelta, timezone

    from pio_tpu.data.dao import App
    from pio_tpu.data.event import Event

    app_id = storage.get_metadata_apps().insert(App(0, app_name))
    events = storage.get_events()
    events.init(app_id)
    t0 = datetime(2020, 1, 1, tzinfo=timezone.utc)
    batch = 20_000
    for lo in range(0, len(values), batch):
        hi = min(len(values), lo + batch)
        events.insert_batch([
            Event("rate", "user", f"u{user_idx[n]}", "item",
                  f"i{item_idx[n]}", {"rating": float(values[n])},
                  event_time=t0 + timedelta(seconds=n))
            for n in range(lo, hi)], app_id)


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    import jax

    device = device_report(jax)
    want = "cpu" if spec["rehearse"] else "tpu"
    if device["platform"] != want or device["count"] < spec["chips"]:
        with open(spec["out"], "w") as f:
            json.dump({"device": device}, f)
        return 0          # the parent says why there is no result

    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.data.bimap import EntityIdIndex
    from pio_tpu.data.eventstore import Interactions
    from pio_tpu.data.storage import get_storage
    from pio_tpu.models.recommendation import RecommendationEngine
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import load_models, run_train

    from benchmark.engines import seeded_engine
    from benchmark.harness import check_train, data, program

    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    shape = config["data"]
    log = logging.getLogger("benchmark")
    user_idx, item_idx, values = data.make_interactions(shape, seed)
    log.info("made %d ratings", len(values))
    storage = get_storage()
    if traffic["source"] == "events":
        insert_events(storage, "bench", user_idx, item_idx, values)
        engine = RecommendationEngine.apply()
        source_params = {"app_name": "bench", "event_names": ["rate"]}
    else:
        engine = seeded_engine(Interactions(
            user_idx, item_idx, values,
            EntityIdIndex(data.entity_ids("u", shape["n_users"])),
            EntityIdIndex(data.entity_ids("i", shape["n_items"]))))
        source_params = None
    ep = EngineParams(datasource=("", source_params),
                      algorithms=[("als", dict(config["algorithm"]))])
    # one chip: the single-device trainer, whatever the machine holds;
    # more: the mesh over all of them, as `pio train` makes it
    ctx = create_workflow_context(storage, use_mesh=spec["chips"] > 1)
    job_log, span_log = JobLog(), program.SpanLog()
    logging.getLogger("pio_tpu.workflow").addHandler(job_log)
    logging.getLogger("pio_tpu.workflow").addHandler(span_log)

    def job() -> dict:
        job_log.job, span_log.rows = {}, []
        t_a = time.monotonic()
        instance = run_train(engine, ep, storage, engine_id="bench", ctx=ctx)
        t_b = time.monotonic()
        return dict(job_log.job, spans=span_log.rows, instance=instance,
                    start=t_a, end=t_b, wall_s=t_b - t_a)

    warm = job()
    log.info("warm job %.2fs", warm["wall_s"])
    tracing = spec["trace"]
    trace_dir = os.path.join(os.path.dirname(spec["out"]), "trace")
    if tracing:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # ours are TraceAnnotations
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    jobs = []
    t_open = time.monotonic()
    with jax.profiler.TraceAnnotation("bench:window"):
        while True:
            jobs.append(job())
            if time.monotonic() - t_open >= spec["seconds"]:
                break
    t_close = time.monotonic()
    if tracing:
        jax.profiler.stop_trace()
    peak, memory = memory_peak(jax)
    log.info("window closed: %d jobs", len(jobs))

    # -- after the window: the check, on what the last job persisted
    model = load_models(storage, engine, ep, jobs[-1]["instance"], ctx)[0]
    users = np.asarray(model.factors.user_factors)
    items = np.asarray(model.factors.item_factors)
    u_rows = np.array([int(s[1:]) for s in model.users.ids()])
    i_rows = np.array([int(s[1:]) for s in model.items.ids()])
    numbers: dict = {}
    shape_ok = (users.shape == (shape["n_users"], config["algorithm"]["rank"])
                and items.shape == (shape["n_items"],
                                    config["algorithm"]["rank"])
                and users.dtype == np.float32 and items.dtype == np.float32
                and bool(np.isfinite(users).all())
                and bool(np.isfinite(items).all())
                and len(set(u_rows.tolist())) == shape["n_users"]
                and len(set(i_rows.tolist())) == shape["n_items"])
    compared = [f"tables {users.dtype}{users.shape} {items.dtype}"
                f"{items.shape}, finite, every id once: "
                f"{'ok' if shape_ok else 'FAILED'}"]
    correct = shape_ok
    if shape_ok:
        by_id_u = np.empty_like(users)
        by_id_u[u_rows] = users
        by_id_i = np.empty_like(items)
        by_id_i[i_rows] = items
        del model
        log.info("model loaded")
        t_c = time.monotonic()
        verdict = check_train.check(
            by_id_u, by_id_i, user_idx, item_idx, values,
            config["algorithm"], config["check"]["limits"], seed,
            config["check"]["sample_rows"])
        numbers = dict(verdict["numbers"],
                       check_seconds=time.monotonic() - t_c)
        compared += verdict["compared"]
        correct = verdict["correct"]
        if spec.get("explore"):
            numbers["explore"] = explore(
                by_id_u, by_id_i, user_idx, item_idx, values,
                config, seed)
    compiles = sum(j.get("programs", 0) for j in jobs)
    compared.append(f"programs compiled inside the window {compiles} <= 0: "
                    f"{'ok' if compiles == 0 else 'FAILED'}")
    out = {
        "device": dict(device, memory_peak_bytes=peak),
        "memory_stats": memory,
        "warm_job": warm, "jobs": jobs,
        "window": {"open": t_open, "close": t_close},
        "correct": bool(correct and compiles == 0),
        "numbers": numbers, "compared": compared,
        "nnz": int(len(values)),
    }
    log.info("checked: %s", compared)
    if tracing:
        out["trace"] = program.reduce_trace(trace_dir, len(jobs), log)
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    return 0


def explore(users, items, user_idx, item_idx, values, config, seed) -> dict:
    """The item side's distances for the program's tables and for the
    lower-precision control in its place for the last half-sweep
    (reference at precision="bfloat16", tables rounded to bfloat16):
    the two lists a limit is set between."""
    from benchmark.harness.check_train import gaps, sample_of
    from benchmark.reference import als as ref

    alg = config["algorithm"]
    alpha, reg = alg["alpha"], alg["lambda_"]
    rng = np.random.default_rng([seed, 0xC0FFEE])      # the check's sample
    sample = sample_of(rng, len(items), config["check"]["sample_rows"])
    grouped = ref.rows_of(item_idx, user_idx, values, sample)
    low = ref.bf16(users)
    control, _ = ref.solve_rows(low, grouped, alpha, reg, "bfloat16")
    return {"program": gaps(items[sample], users, grouped, alpha, reg),
            "control_bfloat16": gaps(control, low, grouped, alpha, reg)}


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
