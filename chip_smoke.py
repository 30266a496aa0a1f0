#!/usr/bin/env python3
"""chip_smoke.py — does the main path still start on the chip?

Drives the path a user runs, once, through the normal entry points, at
the full width of the recommendation engine (MovieLens-20M catalog:
138,493 users x 26,744 items, rank 64, implicit ALS):

    pio app new -> pio eventserver + POST /batch/events.json
    -> pio train -> pio deploy -> POST /queries.json

and checks what comes out: factor tables of exactly the catalog's shape,
every acknowledged event read back by the trainer, served answers equal
(to a tolerance) to a NumPy top-k over the persisted factors, and the
chip-trained factors agreeing with a second, explicitly labelled CPU
reference train (pure-XLA accumulation path) on sampled predictions — so
a kernel that compiles but computes garbage cannot pass.

Rules it keeps:

 * This process never imports jax. A chip belongs to one process at a
   time, so the children that need it (`pio status`, `pio train`,
   `pio deploy`) run strictly one after another, each has exited before
   the next starts, and every other child is held to JAX_PLATFORMS=cpu.
 * Data is made from --seed; no network, no git. Storage lives in a temp
   dir; only the compile cache has a fixed place (JAX_COMPILATION_CACHE_DIR
   or .jax_cache in the checkout — pio_tpu/utils/compilecache.py).
 * It fails loudly: no TPU, a child that exits non-zero, a failed check
   or a swallowed warm-up failure in the deploy log is a non-zero exit
   and no result line.

Everything it prints before the last line is an OBSERVATION of one run,
not a benchmark result. The last line of stdout is one JSON object,
{"ok": true, "device": {...}} with the device as jax reports it.

    python chip_smoke.py [--events N] [--seed S] [--log-dir DIR]
    python chip_smoke.py --cpu-dry-run     # tiny size, CPU, NOT a chip result
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# MovieLens-20M catalog; ALS_PARAMS below are the parameters of the cell
# ml20m-r64.train-coo: rank 64, lambda 0.05, alpha 10, implicit
N_USERS, N_ITEMS, RANK = 138_493, 26_744, 64
FULL_EVENTS = 20_000_000
DRY_USERS, DRY_ITEMS, DRY_EVENTS = 2_000, 500, 20_000   # --cpu-dry-run
FULL_SWEEPS = 10          # the recommendation template's default
SWEEPS = 3                # "a few steps"
ALS_PARAMS = {"rank": RANK, "num_iterations": SWEEPS, "lambda_": 0.05,
              "alpha": 10.0, "implicit_prefs": True, "chunk": 8192}
BATCH_EVENTS = 50         # the JSON batch route's contract
INGEST_THREADS = 8
# served scores come from a default-precision (bf16-pass) MXU matmul, the
# NumPy reference is f32: equal to a tolerance, never bit-equal. Seen on
# a v5e (PR 21, eight runs): 1.0e-3 to 3.0e-3 absolute at scores of order 1.
SCORE_TOL = 2e-2
# chip-trained vs CPU-trained predictions, relative RMS — the bound
# __graft_entry__.py uses for sharded-vs-single. Seen on a v5e (PR 21,
# eight runs): 6e-4 to 8.4e-3 (which duplicate rating is "last" varies
# with ingest timing, so runs differ); the broken sharded solve gave 10.
REF_TOL = 5e-2

APP = "chipsmoke"
ENGINE_ID = "chipsmoke"
REF_ENGINE_ID = "chipsmoke_cpuref"


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

_children: list[subprocess.Popen] = []


def _kill_children() -> None:
    for p in _children:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait()


def _tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


class Run:
    """Paths, environment and the clock of one smoke run."""

    def __init__(self, args, work: str, log_dir: str):
        self.args = args
        self.work = work
        self.log_dir = log_dir
        self.deadline = time.monotonic() + args.time_limit
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env.update({
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(work, "eventlog"),
            "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(work, "pio.db"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
            "PIO_TPU_HOME": os.path.join(work, "home"),
        })
        env.pop("PIO_TPU_PLATFORM", None)
        # children that must not take the chip
        self.env_cpu = dict(env, JAX_PLATFORMS="cpu")
        # children that need it: whatever jax finds by default — or, in
        # the dry run, the CPU, said out loud
        self.env_chip = self.env_cpu if args.cpu_dry_run else env

    def remaining(self) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise SmokeFailure(
                f"time limit of {self.args.time_limit:.0f}s spent")
        return left

    def log_path(self, name: str) -> str:
        return os.path.join(self.log_dir, f"{name}.log")

    def spawn(self, name: str, argv: list[str], env: dict) -> subprocess.Popen:
        logf = open(self.log_path(name), "w")
        p = subprocess.Popen(
            argv, env=env, cwd=self.work, stdout=logf,
            stderr=subprocess.STDOUT, start_new_session=True)
        logf.close()
        _children.append(p)
        return p

    def pio(self, name: str, *verb: str, env: dict | None = None,
            timeout: float = 900.0) -> str:
        """Run one `pio` verb to its end; -> its output."""
        return self.to_end(
            name, [sys.executable, "-m", "pio_tpu.tools.cli", *verb],
            env or self.env_cpu, timeout)

    def to_end(self, name: str, argv: list[str], env: dict,
               timeout: float) -> str:
        """Run one child to its end; -> its output. Non-zero exit or a
        timeout is a failure of the smoke."""
        t0 = time.monotonic()
        p = self.spawn(name, argv, env)
        try:
            rc = p.wait(timeout=min(timeout, self.remaining()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name}: no end after {time.monotonic() - t0:.0f}s\n"
                + _tail(self.log_path(name))) from None
        if rc != 0:
            raise SmokeFailure(
                f"{name}: exit code {rc}\n" + _tail(self.log_path(name)))
        with open(self.log_path(name), errors="replace") as f:
            return f.read()

    def stop(self, name: str, p: subprocess.Popen) -> None:
        """SIGINT (the servers' clean shutdown), then the hard way."""
        if p.poll() is None:
            p.send_signal(signal.SIGINT)
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                os.killpg(p.pid, signal.SIGKILL)
                p.wait()
                raise SmokeFailure(
                    f"{name}: did not stop on SIGINT within 30s") from None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(run: Run, name: str, p: subprocess.Popen, port: int,
               timeout: float) -> float:
    """Poll GET /readyz until 200; -> seconds waited."""
    t0 = time.monotonic()
    limit = min(timeout, run.remaining())
    while time.monotonic() - t0 < limit:
        if p.poll() is not None:
            raise SmokeFailure(
                f"{name}: exited with code {p.returncode} before it was "
                "ready\n" + _tail(run.log_path(name)))
        try:
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            c.request("GET", "/readyz")
            ok = c.getresponse().status == 200
            c.close()
            if ok:
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(0.25)
    raise SmokeFailure(f"{name}: not ready after {limit:.0f}s\n"
                       + _tail(run.log_path(name)))


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def make_events(n_events: int, n_users: int, n_items: int, seed: int):
    """-> (user ids, item ids, ratings) as int arrays. Every user and
    every item appears at least once (so the factor tables come out at
    exactly the catalog's shape), the rest is Zipf-ish as bench.synth."""
    if n_events < max(n_users, n_items):
        raise SmokeFailure(
            f"--events {n_events} cannot cover {n_users} users")
    rng = np.random.default_rng(seed)
    rest = n_events - n_users
    cover_u = np.arange(n_users, dtype=np.int64)
    cover_i = cover_u % n_items            # n_users >= n_items: all items
    users = np.concatenate([cover_u, rng.zipf(1.2, rest) % n_users])
    items = np.concatenate([cover_i, rng.zipf(1.2, rest) % n_items])
    order = rng.permutation(n_events)
    return (users[order], items[order],
            rng.integers(1, 6, n_events))


def ingest(run: Run, port: int, key: str, users, items, ratings) -> dict:
    """POST every event through /batch/events.json (50 a request, a few
    keep-alive clients); every slot must come back 201."""
    n = len(users)
    starts = list(range(0, n, BATCH_EVENTS))
    path = f"/batch/events.json?accessKey={key}"
    errors: list[str] = []
    accepted = [0] * INGEST_THREADS
    row = ('{"event":"rate","entityType":"user","entityId":"u%d",'
           '"targetEntityType":"item","targetEntityId":"i%d",'
           '"properties":{"rating":%d}}')

    def client(w: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            for lo in starts[w::INGEST_THREADS]:
                if errors or time.monotonic() > run.deadline:
                    return
                hi = min(n, lo + BATCH_EVENTS)
                body = "[" + ",".join(
                    row % t for t in zip(users[lo:hi].tolist(),
                                         items[lo:hi].tolist(),
                                         ratings[lo:hi].tolist())) + "]"
                for attempt in range(5):
                    conn.request("POST", path, body=body.encode(),
                                 headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    if resp.status != 503:   # 503 = load shedder: retry
                        break
                    time.sleep(0.2 * (attempt + 1))
                if resp.status != 200:
                    errors.append(f"HTTP {resp.status}: {payload[:200]!r}")
                    return
                ok = sum(1 for r in json.loads(payload)
                         if r.get("status") == 201)
                if ok != hi - lo:
                    errors.append(f"{hi - lo - ok} of {hi - lo} events "
                                  f"refused: {payload[:300]!r}")
                    return
                accepted[w] += ok
        except Exception as e:  # noqa: BLE001 - reported by the parent
            errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(w,))
               for w in range(INGEST_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    if errors:
        raise SmokeFailure("ingest: " + errors[0])
    if sum(accepted) != n:
        raise SmokeFailure(f"ingest: {sum(accepted)} of {n} acknowledged "
                           "(time limit?)")
    return {"events": n, "seconds": round(dt, 2),
            "events_per_sec": round(n / dt, 1)}


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def parse_devices(text: str, what: str) -> dict:
    """The one-line device report every chip-holding process prints
    (pio_tpu.parallel.mesh.describe_devices)."""
    m = re.search(r"devices: (\d+) x (\S+) \((.*?)\), jax (\S+)", text)
    if not m:
        raise SmokeFailure(f"{what}: no device line in its output")
    return {"platform": m.group(2), "kind": m.group(3),
            "count": int(m.group(1)), "jax": m.group(4)}


def require_tpu(dev: dict, what: str, dry_run: bool) -> None:
    want = "cpu" if dry_run else "tpu"
    if dev["platform"] != want:
        raise SmokeFailure(
            f"{what} ran on platform {dev['platform']!r}, not {want!r}: "
            "no accelerator, no result (--cpu-dry-run debugs the script "
            "at a tiny size and says so)")


def check_deploy_log(text: str) -> None:
    """The server swallows warm-up exceptions by design (a failed warm
    means traffic pays the compile); here a Mosaic or XLA error in one
    must not end in exit 0."""
    for bad in ("warm query failed", "warm batch failed",
                "background bucket warm failed"):
        if bad in text:
            raise SmokeFailure(f"deploy log says {bad!r}:\n"
                               + "".join(text.splitlines(True)[-40:]))


def grep1(pattern: str, text: str, what: str) -> re.Match:
    m = re.search(pattern, text)
    if not m:
        raise SmokeFailure(f"{what}: no line matching {pattern!r}")
    return m


def check_answer(answer: dict, uvec, item_factors, item_ids, item_row,
                 num: int, black: set, what: str) -> float:
    """One served answer against the NumPy top-k over the persisted
    factors; -> largest |served - reference| score seen."""
    got = answer.get("itemScores")
    if not isinstance(got, list) or len(got) != num:
        raise SmokeFailure(f"{what}: expected {num} itemScores, got "
                           f"{answer!r:.300}")
    ref = item_factors @ uvec                       # f32 (n_items,)
    for b in black:
        ref[item_row[b]] = -np.inf
    top = np.argsort(-ref)[:num]
    scale = max(1.0, float(np.abs(ref[top]).max()))
    tol = SCORE_TOL * scale
    scores = [s["score"] for s in got]
    if not all(np.isfinite(scores)):
        raise SmokeFailure(f"{what}: non-finite score in {scores}")
    if any(a < b for a, b in zip(scores, scores[1:])):
        raise SmokeFailure(f"{what}: scores not descending: {scores}")
    names = [s["item"] for s in got]
    if len(set(names)) != num or black & set(names):
        raise SmokeFailure(f"{what}: duplicate or blacklisted item in "
                           f"{names}")
    worst = 0.0
    for name, score in zip(names, scores):
        dev = abs(score - float(ref[item_row[name]]))
        worst = max(worst, dev)
        if dev > tol:
            raise SmokeFailure(
                f"{what}: item {name} served {score}, reference "
                f"{float(ref[item_row[name]])} (tolerance {tol:.4g})")
    # nothing clearly better was left out: a reference top-k item is
    # either served or within tolerance of the weakest served one
    floor = min(float(ref[item_row[x]]) for x in names)
    served = set(names)
    for j in top:
        if item_ids[j] not in served and float(ref[j]) > floor + 2 * tol:
            raise SmokeFailure(
                f"{what}: item {item_ids[j]} (reference {float(ref[j])}) "
                f"missing; weakest served {floor}")
    return worst


def post_json(port: int, path: str, body) -> tuple[int, object, float]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        t0 = time.monotonic()
        conn.request("POST", path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        dt = time.monotonic() - t0
    finally:
        conn.close()
    try:
        return resp.status, json.loads(payload), dt
    except ValueError:
        return resp.status, payload[:300], dt


# ---------------------------------------------------------------------------
# the export child (JAX_PLATFORMS=cpu): unpickling a model imports jax,
# which the parent must not do
# ---------------------------------------------------------------------------

def export_child(out_dir: str, engine_ids: list[str]) -> int:
    from pio_tpu.data.storage import get_storage
    from pio_tpu.workflow.checkpoint import models_from_bytes

    storage = get_storage()
    for engine_id in engine_ids:
        inst = storage.get_metadata_engine_instances().get_latest_completed(
            engine_id, "1", "default")
        if inst is None:
            print(f"no COMPLETED instance of engine {engine_id}")
            return 1
        model = models_from_bytes(
            storage.get_model_data_models().get(inst.id).models)[0]
        np.save(os.path.join(out_dir, f"{engine_id}.users.npy"),
                np.asarray(model.factors.user_factors))
        np.save(os.path.join(out_dir, f"{engine_id}.items.npy"),
                np.asarray(model.factors.item_factors))
        with open(os.path.join(out_dir, f"{engine_id}.json"), "w") as f:
            json.dump({"instance": inst.id, "status": inst.status,
                       "users": model.users.ids(),
                       "items": model.items.ids()}, f)
    return 0


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def write_engine(run: Run, engine_id: str) -> str:
    d = os.path.join(run.work, engine_id)
    os.makedirs(d)
    with open(os.path.join(d, "engine.json"), "w") as f:
        json.dump({
            "id": engine_id,
            "engineFactory":
                "pio_tpu.models.recommendation.RecommendationEngine",
            "datasource": {"params": {"app_name": APP,
                                      "event_names": ["rate"]}},
            "algorithms": [{"name": "als", "params": ALS_PARAMS}],
        }, f)
    return d


def ingest_events(run: Run, obs: dict, n_events: int, n_users: int,
                  n_items: int):
    """Events in, through the event server; -> (users, items, number of
    distinct (user, item) pairs acknowledged)."""
    out = run.pio("app_new", "app", "new", APP)
    key = grep1(r"Access key: (\S+)", out, "pio app new").group(1)
    users, items, ratings = make_events(n_events, n_users, n_items,
                                        run.args.seed)
    pairs = int(np.unique(users * n_items + items).size)
    port = free_port()
    es = run.spawn("eventserver", [
        sys.executable, "-m", "pio_tpu.tools.cli", "eventserver",
        "--ip", "127.0.0.1", "--port", str(port)], run.env_cpu)
    wait_ready(run, "eventserver", es, port, 120)
    obs["ingest"] = ingest(run, port, key, users, items, ratings)
    run.stop("eventserver", es)
    # the event log's C++ side is built with g++ on first use
    build = os.path.join(HERE, "pio_tpu", "native", "_build")
    obs["ingest"]["native_eventlog_built"] = os.path.isdir(build) and any(
        f.endswith(".so") for f in os.listdir(build))
    obs["shapes"] = {"users": n_users, "items": n_items, "rank": RANK,
                     "events": n_events, "distinct_pairs": pairs,
                     "sweeps": SWEEPS}
    if not run.args.cpu_dry_run and (
            n_events < FULL_EVENTS or SWEEPS < FULL_SWEEPS):
        obs["reduced"] = {"events": f"{n_events} of {FULL_EVENTS}",
                          "sweeps": f"{SWEEPS} of {FULL_SWEEPS}"}
    return users, items, pairs


def train_on_chip(run: Run, obs: dict, pairs: int) -> str:
    """`pio train` on the chip; -> the engine dir it trained."""
    engine_dir = write_engine(run, ENGINE_ID)
    t0 = time.monotonic()
    log = run.pio("train", "train", "--engine-dir", engine_dir,
                  env=run.env_chip, timeout=1800)
    train_wall = time.monotonic() - t0
    train_dev = parse_devices(log, "pio train")
    require_tpu(train_dev, "pio train", run.args.cpu_dry_run)
    m = grep1(r"ALS train: (\d+) users x (\d+) items, (\d+) ratings, "
              r"rank (\d+), (\d+) sweeps, (.*?); accum=(\S+)",
              log, "pio train")
    if int(m.group(3)) != pairs:
        raise SmokeFailure(
            f"the trainer read {m.group(3)} ratings; {pairs} distinct "
            "(user, item) pairs were acknowledged by the event server")
    stages = grep1(r"train stages: read ([\d.]+)s, prepare ([\d.]+)s, "
                   r"algorithms ([\d.]+)s", log, "pio train")
    timing = grep1(r"train timing: engine\.train ([\d.]+)s, of which "
                   r"compile ([\d.]+)s over (\d+) programs \((\d+) "
                   r"persistent-cache hits\); persist ([\d.]+)s",
                   log, "pio train")
    obs["train"] = {
        "device": train_dev,
        "layout": m.group(6),
        "accum": m.group(7),
        "wall_seconds_process": round(train_wall, 2),
        "read_seconds": float(stages.group(1)),
        "prepare_seconds": float(stages.group(2)),
        "algorithms_seconds": float(stages.group(3)),
        "compile_seconds": float(timing.group(2)),
        "programs": int(timing.group(3)),
        "persistent_cache_hits": int(timing.group(4)),
        # transfer + layout + sweeps: the algorithm stage minus set-up
        "sweeps_seconds": round(
            float(stages.group(3)) - float(timing.group(2)), 2),
        "persist_seconds": float(timing.group(5)),
        "device_memory": grep1(r"train device memory: (.*)", log,
                               "pio train").group(1),
    }
    if train_dev["count"] > 1:
        sh = grep1(r"sharded ALS over (\d+) devices: rating blocks on "
                   r"devices (\[.*?\]), factor blocks on devices "
                   r"(\[.*?\])", log, "pio train")
        want = list(range(train_dev["count"]))
        if json.loads(sh.group(2)) != want or json.loads(sh.group(3)) != want:
            raise SmokeFailure(
                f"sharded train did not spread over {want}: {sh.group(0)}")
        obs["train"]["sharded"] = sh.group(0)
    return engine_dir


def train_cpu_reference(run: Run, obs: dict) -> None:
    """The same train on the CPU: a REFERENCE run, not a fallback."""
    ref_dir = write_engine(run, REF_ENGINE_ID)
    t0 = time.monotonic()
    ref_log = run.pio("train_cpu_reference", "train", "--engine-dir",
                      ref_dir,
                      env=dict(run.env_cpu, PIO_TPU_PLATFORM="cpu"),
                      timeout=1800)
    if parse_devices(ref_log, "reference train")["platform"] != "cpu":
        raise SmokeFailure("the CPU reference train did not run on the CPU")
    obs["cpu_reference_train"] = {
        "what": "reference run on the CPU backend; not a fallback",
        "accum": grep1(r"accum=(\S+)", ref_log, "reference train").group(1),
        "wall_seconds_process": round(time.monotonic() - t0, 2)}


class Persisted:
    """One engine's persisted model, as the export child wrote it."""

    def __init__(self, out_dir: str, engine_id: str):
        with open(os.path.join(out_dir, f"{engine_id}.json")) as f:
            self.meta = json.load(f)
        self.users = np.load(os.path.join(out_dir, f"{engine_id}.users.npy"))
        self.items = np.load(os.path.join(out_dir, f"{engine_id}.items.npy"))
        self.user_row = {u: r for r, u in enumerate(self.meta["users"])}
        self.item_row = {i: r for r, i in enumerate(self.meta["items"])}

    def predict(self, user_ids, item_ids):
        """Scores of (user, item) pairs given as the generator's ints
        (rows are matched by entity id: each train indexes for itself)."""
        u = np.array([self.user_row[f"u{x}"] for x in user_ids])
        i = np.array([self.item_row[f"i{x}"] for x in item_ids])
        return np.einsum("nk,nk->n", self.users[u], self.items[i])


def check_model(run: Run, obs: dict, users, items, n_users: int,
                n_items: int, rng) -> Persisted:
    """What was persisted: shapes, and the chip's factors against the
    CPU reference's on sampled pairs; -> the chip-trained model."""
    exp = os.path.join(run.work, "export")
    os.makedirs(exp)
    run.to_end("export", [sys.executable, os.path.abspath(__file__),
                          "--export-child", exp, ENGINE_ID, REF_ENGINE_ID],
               run.env_cpu, 600)
    chip, ref = Persisted(exp, ENGINE_ID), Persisted(exp, REF_ENGINE_ID)
    meta = chip.meta
    if meta["status"] != "COMPLETED":
        raise SmokeFailure(f"instance {meta['instance']} is "
                           f"{meta['status']}, not COMPLETED")
    for name, arr, rows in (("user", chip.users, n_users),
                            ("item", chip.items, n_items)):
        if arr.shape != (rows, RANK) or arr.dtype != np.float32:
            raise SmokeFailure(f"{name} factors are {arr.dtype}"
                               f"{arr.shape}, expected f32({rows}, {RANK})")
        if not np.isfinite(arr).all():
            raise SmokeFailure(f"{name} factors are not finite")
    if len(chip.user_row) != n_users or len(chip.item_row) != n_items:
        raise SmokeFailure("id indexes do not cover the catalog")
    obs["model"] = {"instance": meta["instance"], "status": meta["status"],
                    "user_factors": list(chip.users.shape),
                    "item_factors": list(chip.items.shape)}

    n_s = 4096      # half seen pairs, half uniform ones
    seen = rng.integers(0, len(users), n_s // 2)
    su = np.concatenate([users[seen], rng.integers(0, n_users, n_s // 2)])
    si = np.concatenate([items[seen], rng.integers(0, n_items, n_s // 2)])
    p_chip, p_ref = chip.predict(su, si), ref.predict(su, si)
    rel = float(np.linalg.norm(p_chip - p_ref)
                / max(np.linalg.norm(p_ref), 1e-12))
    obs["chip_vs_cpu_reference"] = {
        "pairs": n_s, "relative_rms": rel, "bound": REF_TOL,
        "max_abs_diff": float(np.abs(p_chip - p_ref).max()),
        "rms_prediction": float(np.sqrt(np.mean(p_ref ** 2)))}
    if not rel <= REF_TOL:
        raise SmokeFailure(
            f"chip-trained and CPU-trained predictions differ by "
            f"{rel:.4g} relative RMS (bound {REF_TOL})")
    return chip


def deploy_and_query(run: Run, obs: dict, engine_dir: str, model: Persisted,
                     q_users: list[str]) -> None:
    """`pio deploy` on the chip; 8 sequential queries, one batch of 16,
    one blackList query, each checked against NumPy over `model`."""
    port = free_port()
    dep = run.spawn("deploy", [
        sys.executable, "-m", "pio_tpu.tools.cli", "deploy",
        "--engine-dir", engine_dir, "--ip", "127.0.0.1", "--port",
        str(port), "--warm-query",
        json.dumps({"user": q_users[0], "num": 10})], run.env_chip)
    ready_s = wait_ready(run, "deploy", dep, port, 900)
    worst = 0.0
    lat_ms = []

    def served(what, status, answer, user, num, black=()):
        nonlocal worst
        if status != 200:
            raise SmokeFailure(f"{what}: HTTP {status}: {answer!r:.300}")
        worst = max(worst, check_answer(
            answer, model.users[model.user_row[user]], model.items,
            model.meta["items"], model.item_row, num, set(black), what))

    first = None
    for user in q_users[:8]:
        status, answer, dt = post_json(
            port, "/queries.json", {"user": user, "num": 10})
        served(f"query {user}", status, answer, user, 10)
        lat_ms.append(round(dt * 1e3, 2))
        first = first or answer
    batch = [{"user": u, "num": 10} for u in q_users[8:24]]
    status, answers, batch_dt = post_json(port, "/batch/queries.json", batch)
    if status != 200 or not isinstance(answers, list) \
            or len(answers) != len(batch):
        raise SmokeFailure(f"batch query: HTTP {status}: {answers!r:.300}")
    for q, answer in zip(batch, answers):
        served(f"batch query {q['user']}", 200, answer, q["user"], 10)
    black = [s["item"] for s in first["itemScores"][:3]]
    status, answer, black_dt = post_json(
        port, "/queries.json",
        {"user": q_users[0], "num": 10, "blackList": black})
    served("blackList query", status, answer, q_users[0], 10, black)
    run.stop("deploy", dep)
    with open(run.log_path("deploy"), errors="replace") as f:
        dep_log = f.read()
    dep_dev = parse_devices(dep_log, "pio deploy")
    require_tpu(dep_dev, "pio deploy", run.args.cpu_dry_run)
    check_deploy_log(dep_log)
    start = grep1(r"serving start-up: load ([\d.]+)s, warm ([\d.]+)s, of "
                  r"which compile ([\d.]+)s over (\d+) programs \((\d+) "
                  r"persistent-cache hits\)", dep_log, "pio deploy")
    obs["deploy"] = {
        "device": dep_dev,
        "ready_after_seconds": round(ready_s, 2),
        "load_seconds": float(start.group(1)),
        "warm_seconds": float(start.group(2)),
        "compile_seconds": float(start.group(3)),
        "programs": int(start.group(4)),
        "persistent_cache_hits": int(start.group(5)),
        "query_ms_sequential": lat_ms,
        "batch16_ms": round(batch_dt * 1e3, 2),
        "blacklist_query_ms": round(black_dt * 1e3, 2),
        "answers_checked": 8 + len(batch) + 1,
        "max_abs_score_deviation_vs_numpy": worst,
        "score_tolerance": SCORE_TOL,
        "device_memory_at_close": grep1(
            r"serving device memory at close: (.*)", dep_log,
            "pio deploy").group(1),
    }


def smoke(run: Run) -> dict:
    args = run.args
    dry = args.cpu_dry_run
    n_users, n_items = (DRY_USERS, DRY_ITEMS) if dry else (N_USERS, N_ITEMS)
    n_events = DRY_EVENTS if dry else args.events
    obs: dict = {"note": "observations of one run, not benchmark results"}
    if dry:
        obs["DRY_RUN"] = "CPU backend at a tiny size: NOT a chip result"

    # is there a chip? (the first process to touch jax says)
    t0 = time.monotonic()
    dev = parse_devices(run.pio("status", "status", env=run.env_chip,
                                timeout=300), "pio status")
    require_tpu(dev, "pio status", dry)
    obs["device"] = dev
    obs["status_seconds"] = round(time.monotonic() - t0, 2)

    users, items, pairs = ingest_events(run, obs, n_events, n_users, n_items)
    cache0 = json.loads(run.pio("cache_before", "compilecache", "--json"))
    engine_dir = train_on_chip(run, obs, pairs)
    train_cpu_reference(run, obs)
    rng = np.random.default_rng(args.seed + 1)
    model = check_model(run, obs, users, items, n_users, n_items, rng)
    q_users = [f"u{u}" for u in
               [0, 1, n_users - 1] + rng.integers(0, n_users, 21).tolist()]
    deploy_and_query(run, obs, engine_dir, model, q_users)
    cache1 = json.loads(run.pio("cache_after", "compilecache", "--json"))
    obs["compile_cache"] = {
        "dir": cache1["dir"],
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": cache0["entries"],
        "entries_after": cache1["entries"],
        "bytes_after": cache1["bytes"]}
    return obs


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--export-child":
        return export_child(sys.argv[2], sys.argv[3:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--events", type=int, default=2_000_000,
                    help="events to ingest (default 2,000,000; the full "
                         f"data set is {FULL_EVENTS:,})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time-limit", type=float, default=1140.0,
                    help="seconds after which the run gives up")
    ap.add_argument("--log-dir",
                    help="keep the children's logs here (default: with "
                         "the temp data, removed at the end)")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debug the script itself: tiny size, CPU "
                         "backend, output marked as NOT a chip result")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "pio_tpu")):
        print("chip_smoke.py: the pio_tpu package is not beside this "
              "script; nothing to run", file=sys.stderr)
        return 2

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    log_dir = os.path.abspath(args.log_dir) if args.log_dir else work
    os.makedirs(log_dir, exist_ok=True)
    try:
        obs = smoke(Run(args, work, log_dir))
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        _kill_children()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(obs, indent=1))
    dev = obs["device"]
    if args.cpu_dry_run:
        print(json.dumps({"dry_run": True, "checks_passed": True,
                          "device": dev}))
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
