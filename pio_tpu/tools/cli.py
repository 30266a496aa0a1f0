"""`pio` command-line interface.

Verb parity with reference tools/.../console/Console.scala:186-677:
  version status
  app {new,list,show,delete,data-delete,trim,cleanup,channel-new,channel-delete}
  accesskey {new,list,delete}
  build train deploy undeploy eval
  eventserver adminserver dashboard
  export import template-new

Differences by design (single-controller runtime, SURVEY.md section 7): no
spark-submit hop — train/eval/deploy run in-process on the JAX mesh; `build`
is a syntax check of the engine dir instead of an sbt assembly.

Run as `python -m pio_tpu.tools.cli <verb>` (or `python -m pio_tpu`).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from pio_tpu import __version__
from pio_tpu.data.dao import AccessKey, Channel
from pio_tpu.data.storage import get_storage
from pio_tpu.tools import appops


def _fail(msg: str) -> int:
    print(f"[ERROR] {msg}", file=sys.stderr)
    return 1


def _load_variant(engine_dir: str) -> dict:
    path = os.path.join(engine_dir, "engine.json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{path} not found. Run inside an engine directory or pass "
            "--engine-dir."
        )
    with open(path) as f:
        return json.load(f)


def _load_factory(class_path: str, engine_dir: str | None = None):
    """'pkg.module.ClassName' -> class (reference WorkflowUtils.getEngine
    reflective load). With engine_dir, the directory joins sys.path first so
    user-code engines (`engine.MyEngine` next to engine.json — the template
    layout, reference examples/*/src/main/scala/Engine.scala) resolve."""
    module_name, _, cls_name = class_path.rpartition(".")
    if not module_name:
        raise ValueError(f"invalid class path {class_path!r}")
    if engine_dir:
        d = os.path.abspath(engine_dir)
        if d not in sys.path:
            # stays on sys.path: the user module may lazily import more of
            # its directory at predict time, long after this returns
            sys.path.insert(0, d)
    mod = importlib.import_module(module_name)
    return getattr(mod, cls_name)


def _engine_from_variant(variant: dict, engine_dir: str | None = None):
    factory = _load_factory(variant["engineFactory"], engine_dir)
    engine = factory.apply()
    ep = engine.engine_params_from_variant(variant)
    if engine_dir:
        ep = _absolutize_param_paths(ep, engine_dir)
    return engine, ep


def _retrieval_block(ep) -> dict | None:
    """The engine's two-stage retrieval block (ops/retrieval.py). Fleet
    shards score partitions themselves rather than through the algorithm
    instance, so `pio deploy --shards` must lift the block out of the
    algorithm params and hand it to the shard servers explicitly —
    otherwise an engine.json that asks for clustered retrieval would
    silently serve exact in fleet mode."""
    for _name, p in (ep.algorithms or []):
        block = p.get("retrieval") if isinstance(p, dict) \
            else getattr(p, "retrieval", None)
        if block:
            return block
    return None


def _absolutize_param_paths(ep, engine_dir: str):
    """Engine-dir-relative paths in params become absolute at load time, so
    `pio train --engine-dir X` behaves the same from any cwd. Any Params
    subclass opts in by declaring `path_fields = ("field", ...)` (e.g. the
    external-engine bridge's workdir)."""
    import dataclasses

    base = os.path.abspath(engine_dir)

    def fix(p):
        fields = getattr(p, "path_fields", ())
        if not fields:
            return p, False
        updates = {
            f: os.path.join(base, v)
            for f in fields
            if (v := getattr(p, f, "")) and not os.path.isabs(v)
        }
        return (dataclasses.replace(p, **updates), True) if updates \
            else (p, False)

    changed = False

    def fix_stage(stage):
        nonlocal changed
        if stage is None:
            return stage
        name, p = stage
        p2, did = fix(p) if p is not None else (p, False)
        changed |= did
        return (name, p2)

    algos = [fix_stage(s) for s in (ep.algorithms or [])]
    out = dataclasses.replace(
        ep,
        datasource=fix_stage(ep.datasource),
        preparator=fix_stage(ep.preparator),
        algorithms=algos,
        serving=fix_stage(ep.serving),
    )
    return out if changed else ep


def _engine_ids(variant: dict, engine_dir: str) -> tuple[str, str, str]:
    engine_id = variant.get("id") or os.path.basename(
        os.path.abspath(engine_dir)
    )
    return engine_id, variant.get("engineVersion", "1"), "default"


# ---------------------------------------------------------------------------
# verbs
# ---------------------------------------------------------------------------

def cmd_version(args) -> int:
    print(__version__)
    return 0


def cmd_status(args) -> int:
    """Environment doctor (reference Console.status:1035-1107)."""
    from pio_tpu.parallel.mesh import describe_devices

    print(f"pio-tpu {__version__}")
    print(f"Python {sys.version.split()[0]}")
    print(f"devices: {describe_devices()}")
    storage = get_storage()
    print("storage sources:")
    for name, spec in storage.sources.items():
        print(f"  {name}: type={spec.type} {spec.properties}")
    print("repositories:")
    for repo, src in storage.repositories.items():
        print(f"  {repo} -> {src}")
    errors = storage.verify_all()
    if errors:
        for e in errors:
            print(f"  [ERROR] {e}")
        return 1
    from pio_tpu.tools.daemon import status_all

    daemons = status_all(getattr(args, "pid_dir", None))
    if daemons:
        print("daemons:")
        for name, info in daemons.items():
            state = "up" if info["alive"] else "DOWN"
            print(f"  {name}: {state} (pid {info['pid']})")
    print("(sanity check passed)")
    return 0


def _doctor_fleet_tenants(args, fleet: dict, router_url: str) -> int:
    """`pio doctor --fleet` against a MULTI-TENANT router: one row per
    tenant — placement (instance, bytes, per-shard spread), quota
    consumption (admitted/shed/inflight), and per-tenant shard health.
    A tenant is AFFECTED (exit 1) when any of its shard groups has zero
    routable replicas or a shard serves a different instance than the
    placement recorded (last-good degradation after a corrupt blob).
    `--tenant KEY` scopes the exit code to that one tenant, so a page
    about tenant A does not fail a check run on healthy tenant B."""
    from pio_tpu.utils.httpclient import JsonHttpClient

    tenants = fleet.get("tenants", {})
    if args.tenant and args.tenant not in tenants:
        return _fail(f"tenant {args.tenant!r} is not on fleet "
                     f"{fleet.get('fleet')!r} "
                     f"(tenants: {sorted(tenants)})")
    rows = []
    for key, t in sorted(tenants.items()):
        placement = t.get("placement") or {}
        status = t.get("status") or {}
        quota = t.get("quota") or {}
        shards = status.get("shards", {})
        routable = sum(1 for g in shards.values() if g.get("ok"))
        # the router prober fills engineInstanceId asynchronously; probe
        # each replica ourselves (tenant-stamped) so doctor is accurate
        # even right after deploy
        served = set()
        for g in shards.values():
            for rep in g.get("replicas", ()):
                iid = rep.get("engineInstanceId")
                if not iid and rep.get("url"):
                    try:
                        info = JsonHttpClient(
                            rep["url"], timeout=args.timeout,
                        ).request("GET", "/shard/info",
                                  headers={"X-Pio-Tenant": key})
                        iid = info.get("engineInstanceId")
                    except Exception:
                        pass
                if iid:
                    served.add(str(iid))
        served = sorted(served)
        placed = placement.get("instanceId")
        last_good = bool(served and placed
                         and any(s != str(placed) for s in served))
        affected = routable < len(shards) or last_good
        rows.append({
            "tenant": key,
            "instanceId": placed,
            "servedInstances": served,
            "lastGoodFallback": last_good,
            "shardsRoutable": f"{routable}/{len(shards)}",
            "partitionBytes": placement.get("partitionBytes"),
            "shardBytes": placement.get("shardBytes"),
            "quotaQps": quota.get("quotaQps"),
            "admitted": quota.get("admitted"),
            "shed": quota.get("shedTotal"),
            "inflight": quota.get("inflight"),
            "instanceSkew": status.get("instanceSkew", False),
            "degradedResponses": status.get("degradedResponses", 0),
            "affected": affected,
        })
    if args.tenant:
        exit_code = int(any(r["affected"] for r in rows
                            if r["tenant"] == args.tenant))
    else:
        exit_code = int(any(r["affected"] for r in rows))
    if args.json:
        print(json.dumps({
            "router": router_url,
            "fleet": fleet.get("fleet"),
            "multiTenant": True,
            "nShards": fleet.get("nShards"),
            "nReplicas": fleet.get("nReplicas"),
            "memoryBudgetBytes": fleet.get("memoryBudgetBytes"),
            "shardLoads": fleet.get("shardLoads"),
            "tenants": rows,
        }, indent=2))
        return exit_code
    print(f"multi-tenant fleet {fleet.get('fleet')!r} at {router_url}: "
          f"{len(rows)} tenant(s) on {fleet.get('nShards')} shards x "
          f"{fleet.get('nReplicas')} replicas")
    print(f"  pool loads (bytes/shard): {fleet.get('shardLoads')}"
          + (f"  budget: {fleet.get('memoryBudgetBytes')}"
             if fleet.get("memoryBudgetBytes") else ""))
    print(f"{'tenant':<28} {'instance':<12} {'shards':>6} "
          f"{'bytes':>10} {'quota':>7} {'admitted':>8} {'shed':>6} "
          "state")
    for r in rows:
        qps = r["quotaQps"]
        state = []
        if r["lastGoodFallback"]:
            state.append(f"LAST-GOOD (serving {r['servedInstances']})")
        if r["shardsRoutable"].split("/")[0] == "0":
            state.append("DOWN")
        elif r["affected"] and not r["lastGoodFallback"]:
            state.append("DEGRADED")
        if r["instanceSkew"]:
            state.append("skew")
        if r["degradedResponses"]:
            state.append(f"degraded={r['degradedResponses']}")
        print(f"{r['tenant']:<28} {str(r['instanceId']):<12} "
              f"{r['shardsRoutable']:>6} "
              f"{r['partitionBytes'] or 0:>10} "
              f"{'-' if not qps else f'{qps:g}/s':>7} "
              f"{r['admitted'] or 0:>8} {r['shed'] or 0:>6} "
              f"{' '.join(state) or 'ok'}")
    affected = [r["tenant"] for r in rows if r["affected"]]
    if affected:
        print(f"[WARN] affected tenant(s): {', '.join(affected)} — "
              "co-resident tenants above report ok and keep serving")
    return exit_code


def _doctor_fleet(args) -> int:
    """`pio doctor --fleet`: one table over the whole serving fleet —
    shard plan, every shard/replica's /healthz + /readyz + serving
    instance, replication status per shard group, and open breakers as
    the router sees them. Endpoints come from the router's /fleet.json,
    so the only address the operator needs is the router's."""
    from pio_tpu.utils.httpclient import HttpClientError, JsonHttpClient

    router_url = args.router_url or f"http://{args.ip}:{args.serving_port}"
    client = JsonHttpClient(router_url, timeout=args.timeout)
    try:
        fleet = client.request("GET", "/fleet.json")
    except HttpClientError as e:
        return _fail(f"fleet router at {router_url} unreachable: "
                     f"{e.message}")
    if fleet.get("multiTenant"):
        return _doctor_fleet_tenants(args, fleet, router_url)
    plan = fleet.get("plan", {})
    rollout = fleet.get("rollout")
    rows = []
    foldin_lag: dict[str, dict] = {}
    candidate_coverage: dict[str, dict] = {}
    exit_code = 0
    for s, group in sorted(fleet.get("shards", {}).items(),
                           key=lambda kv: int(kv[0])):
        group_ready = 0
        group_stale: list[float] = []
        group_applied: list[int] = []
        group_candidates: list = []
        for rep in group["replicas"]:
            probe = JsonHttpClient(rep["url"], timeout=args.timeout)
            live = ready = False
            instance = rep.get("engineInstanceId")
            candidate = rep.get("candidateInstanceId")
            foldin = None
            retrieval = None
            plan_version = rep.get("planVersion")
            try:
                probe.request("GET", "/healthz")
                live = True
                probe.request("GET", "/readyz")
                ready = True
                info = probe.request("GET", "/shard/info")
                instance = info.get("engineInstanceId", instance)
                candidate = info.get("candidateInstanceId", candidate)
                foldin = info.get("foldin")
                retrieval = info.get("retrieval")
                plan_version = info.get("planVersion", plan_version)
            except HttpClientError:
                pass
            group_ready += ready
            group_candidates.append(candidate)
            if foldin:
                group_applied.append(int(foldin.get("appliedUsers") or 0))
                if foldin.get("stalenessSeconds") is not None:
                    group_stale.append(float(foldin["stalenessSeconds"]))
            rows.append({
                "shard": int(s), "replica": rep["replica"],
                "url": rep["url"], "live": live, "ready": ready,
                "breaker": rep["breaker"], "instance": instance,
                "candidate": candidate,
                "foldin": foldin,
                "retrieval": retrieval,
                "planVersion": plan_version,
                # internal RPC plane (docs/performance.md): the
                # router's client-side connection-reuse ratio toward
                # this replica and the negotiated wire — a 0% reuse
                # replica under steady traffic means every RPC
                # re-dialed (keep-alive-stripping proxy, idle timeout
                # below the query cadence): a latency page in the
                # making, visible here first
                "connReuse": rep.get("connReuse"),
                "binaryWire": rep.get("binaryWire"),
            })
        # per-group candidate coverage (guarded rollout): how many
        # replicas have the canary candidate staged — a group at 0/N
        # cannot serve its slice of the candidate's partition
        candidate_coverage[s] = {
            "staged": sum(1 for c in group_candidates if c),
            "total": len(group_candidates),
            "instances": sorted({c for c in group_candidates if c}),
        }
        # per-group fold-in lag: MAX staleness any replica recorded at
        # its last apply, plus replica skew (a replica that missed
        # upserts — e.g. it was down during a fold — serves older rows
        # than its group mates until the next fold or /reload)
        foldin_lag[s] = {
            "maxStalenessSeconds": max(group_stale) if group_stale
            else None,
            "appliedUsers": group_applied,
            "replicaSkew": len(set(group_applied)) > 1,
            "overBudget": bool(group_stale
                               and max(group_stale)
                               > args.staleness_budget),
        }
        # fail on the router's breaker view OR the doctor's own probes:
        # on an IDLE fleet breakers never trip (they only open on failed
        # calls), so a dead group still reports routable until traffic
        # starts failing — the direct /readyz probe catches it now
        if not group["ok"] or group_ready == 0:
            exit_code = 1
    # plan-version agreement (live elastic resharding): every replica
    # should serve the router's plan version; a straggler answers
    # old-topology fans correctly (retired arm) but marks a replica
    # that missed the activate fan and is waiting on /reload
    router_pv = plan.get("planVersion")
    stale_plan = [f"shard{r['shard']}/replica{r['replica']}"
                  f"(v{r['planVersion']})"
                  for r in rows
                  if r["planVersion"] is not None
                  and router_pv is not None
                  and int(r["planVersion"]) != int(router_pv)]
    reshard = fleet.get("reshard")
    open_breakers = [f"shard{r['shard']}/replica{r['replica']}"
                     for r in rows if r["breaker"] == "open"]
    replication = {
        s: f"{g['routable']}/{len(g['replicas'])}"
        for s, g in sorted(fleet.get("shards", {}).items(),
                           key=lambda kv: int(kv[0]))
    }
    # two-stage retrieval (ops/retrieval.py): per-group mode/dtype/
    # nprobe, quantized sidecar vs f32 bytes, items headroom under the
    # budget. Replicas of one group MUST agree on mode — a replica
    # quietly serving exact while its group mates serve clustered
    # changes failover semantics (and latency) silently on the next
    # replica scan, so disagreement is an operator page, not a detail
    retr_by_group: dict[int, list] = {}
    for r in rows:
        if r.get("retrieval"):
            retr_by_group.setdefault(r["shard"], []).append(r["retrieval"])
    retr_cells: list[str] = []
    retr_disagree: list[str] = []
    for s, infos in sorted(retr_by_group.items()):
        modes = sorted({str(i.get("mode")) for i in infos})
        if len(modes) > 1:
            retr_disagree.append(f"shard {s}: {'/'.join(modes)}")
        i0 = infos[0]
        cell = f"shard {s}: {i0.get('mode')}"
        if i0.get("mode") == "clustered":
            hd = i0.get("itemsHeadroom")
            cell += (f"/{i0.get('dtype')} nprobe={i0.get('nprobe')} "
                     f"quantized {i0.get('quantizedBytes')}B vs f32 "
                     f"{i0.get('f32ItemBytes')}B headroom "
                     f"{'-' if hd is None else hd}")
        retr_cells.append(cell)
    batching = fleet.get("batching") or {"enabled": False}
    if args.json:
        print(json.dumps({
            "router": router_url, "plan": plan, "replicas": rows,
            "replication": replication, "openBreakers": open_breakers,
            "instanceSkew": fleet.get("instanceSkew", False),
            "degradedResponses": fleet.get("degradedResponses", 0),
            "foldinLag": foldin_lag,
            "stalenessBudgetSeconds": args.staleness_budget,
            "rollout": rollout,
            "candidateCoverage": candidate_coverage,
            "planVersion": router_pv,
            "stalePlanReplicas": stale_plan,
            "reshard": reshard,
            "retrievalModeDisagreement": retr_disagree,
            "batching": batching,
        }, indent=2))
        return exit_code
    print(f"fleet router {router_url}: instance {plan.get('instanceId')} "
          f"plan {plan.get('planHash')} v{plan.get('planVersion')} "
          f"({plan.get('nShards')} shards x {plan.get('nReplicas')} "
          "replicas)")
    print(f"  users/shard: {plan.get('userCounts')}  "
          f"items/shard: {plan.get('itemCounts')}")
    print(f"{'shard':>5} {'rep':>3} {'live':<5} {'ready':<5} "
          f"{'breaker':<9} {'instance':<12} {'wire':<6} {'reuse':>6} url")
    for r in rows:
        reuse = r.get("connReuse")
        wire = r.get("binaryWire")
        print(f"{r['shard']:>5} {r['replica']:>3} "
              f"{'up' if r['live'] else 'DOWN':<5} "
              f"{'yes' if r['ready'] else 'NO':<5} "
              f"{r['breaker']:<9} {str(r['instance']):<12} "
              f"{'binary' if wire else ('json' if wire is False else '-'):<6} "
              f"{'-' if reuse is None else f'{reuse:.0%}':>6} {r['url']}")
    print("replication (routable/total): "
          + ", ".join(f"shard {s}: {v}" for s, v in replication.items()))
    zero_reuse = [f"shard{r['shard']}/replica{r['replica']}"
                  for r in rows if r.get("connReuse") == 0.0]
    if zero_reuse:
        print("[WARN] 0% connection reuse toward: "
              + ", ".join(zero_reuse)
              + " — every RPC re-dials (a keep-alive-stripping proxy or "
              "an idle timeout below the query cadence?)")
    lag_cells = []
    for s, lag in sorted(foldin_lag.items(), key=lambda kv: int(kv[0])):
        ms = lag["maxStalenessSeconds"]
        cell = f"shard {s}: {'-' if ms is None else f'{ms:.1f}s'}"
        if lag["replicaSkew"]:
            cell += " (replica skew)"
        lag_cells.append(cell)
    if lag_cells:
        print("fold-in lag (max staleness at last apply): "
              + ", ".join(lag_cells))
    if retr_cells:
        print("retrieval: " + ", ".join(retr_cells))
    # continuous batching (docs/serving.md): coalescer health. Mean
    # occupancy pinned at ~1.0 means every window fills to max_batch —
    # arrivals are queuing behind full dispatches, so p99 is climbing;
    # widen --coalesce-window-ms gains nothing at that point (the batch
    # is already full): raise max batch or add replicas
    if batching.get("enabled"):
        occ = batching.get("meanOccupancy")
        wait = (batching.get("coalesceWaitMs") or {}).get("p50")
        print(f"batching: window {batching.get('windowMs')}ms "
              f"max {batching.get('maxBatch')} — "
              f"{batching.get('coalescedQueries', 0)} queries over "
              f"{batching.get('coalescedCalls', 0)} batched dispatches, "
              f"occupancy {'-' if occ is None else f'{occ:.2f}'} mean, "
              f"coalesce wait p50 "
              f"{'-' if wait is None else f'{wait:.2f}ms'}")
        if occ is not None and occ >= 0.95:
            print("[WARN] batch occupancy ~1.0: every coalesce window "
                  "fills to max batch — queries queue behind full "
                  "dispatches. Raise the max batch or add replicas; a "
                  "wider window will not help")
    if retr_disagree:
        print("[WARN] retrieval mode disagreement within shard "
              "group(s): " + "; ".join(retr_disagree)
              + " — replicas of one group must serve the same candidate "
              "tier (check --retrieval-* flags / the engine's retrieval "
              "block on the odd replica out)")
    over = sorted((s for s, lag in foldin_lag.items()
                   if lag["overBudget"]), key=int)
    if over:
        print(f"[WARN] fold-in staleness over the "
              f"{args.staleness_budget:.0f}s budget in shard group(s): "
              f"{', '.join(over)}")
    if rollout and rollout.get("candidateInstanceId"):
        state = rollout.get("verdict") or f"{rollout.get('stagePct')}%"
        print(f"rollout: candidate {rollout['candidateInstanceId']} "
              f"[{state}] {rollout.get('timeInStageSeconds', 0):.0f}s "
              "in stage")
        cov_cells = [
            f"shard {s}: {c['staged']}/{c['total']}"
            for s, c in sorted(candidate_coverage.items(),
                               key=lambda kv: int(kv[0]))
        ]
        print("candidate coverage (staged/total): " + ", ".join(cov_cells))
        under = [s for s, c in candidate_coverage.items()
                 if rollout.get("verdict") is None
                 and c["staged"] < c["total"]]
        if under:
            print(f"[WARN] candidate not staged on every replica of "
                  f"shard group(s): {', '.join(sorted(under, key=int))}")
    if reshard and reshard.get("inFlight"):
        print(f"reshard: {reshard.get('nShardsOld')} -> "
              f"{reshard.get('nShardsNew')} shard(s) in flight — "
              f"{reshard.get('partitionsStaged', 0)}/"
              f"{reshard.get('partitionsMoving', 0)} partition(s) "
              f"staged (plan v{reshard.get('planVersionOld')} -> "
              f"v{reshard.get('planVersionNew')})")
    elif reshard and reshard.get("verdict"):
        print(f"reshard: last migration {reshard['verdict']} "
              f"({reshard.get('reason') or 'no reason recorded'})")
    if stale_plan:
        print("[WARN] plan-version disagreement: router serves "
              f"plan v{router_pv} but {', '.join(stale_plan)} "
              "answer(s) an older version — replica(s) missed the "
              "reshard activate fan; a /reload (or `pio reshard "
              "--status` until convergence) clears it")
    if open_breakers:
        print(f"[WARN] open breakers: {', '.join(open_breakers)}")
    if fleet.get("instanceSkew"):
        print("[WARN] instance skew: shards serve different engine "
              "instances (a corrupt partition fell back last-good; "
              "retrain or repartition to converge)")
    if fleet.get("degradedResponses"):
        print(f"degraded responses served: {fleet['degradedResponses']}")
    return exit_code


def _doctor_storage(args) -> int:
    """`pio doctor --storage`: the replicated event store's health in
    one table — per-replica live/breaker/hint-depth/oldest-hint-age,
    quorum status (exit 1 on lost quorum: fewer live replicas than the
    write quorum means acked writes would start failing), the last
    scrub record, and a LIVE read-only convergence check (per-app
    bucket-digest comparison; `--scrub` repairs divergent buckets in
    the same pass). Reads THIS process's PIO_STORAGE_* config, like
    `pio status` — run it where the event tier runs so it sees the
    same replica set and hint directory."""
    storage = get_storage()
    try:
        dao = storage.get_events()
    except Exception as e:  # noqa: BLE001 - doctor reports, never dies
        return _fail(f"could not open the EVENTDATA source: {e}")
    status_fn = getattr(dao, "replication_status", None)
    if status_fn is None:
        return _fail(
            "the EVENTDATA source is not replicated — `doctor --storage` "
            "inspects a `replicated` backend (docs/storage.md)")
    st = status_fn(probe=True)
    live = st.get("liveReplicas",
                  sum(1 for r in st["replicas"] if r["live"]))
    # the sharded composition's verdict is per GROUP (every group must
    # hold its own quorum); the flat live>=W test covers single-group
    quorum_ok = st.get("quorumOk", live >= st["writeQuorum"])

    # live convergence check across every known namespace (apps +
    # channels from the metadata source); --scrub repairs in-pass
    scrub_results: list[dict] = []
    scrub_error = ""
    try:
        apps = storage.get_metadata_apps().get_all()
        channels = storage.get_metadata_channels()
        for app in apps:
            namespaces: list[int | None] = [None]
            namespaces += [c.id for c in channels.get_by_appid(app.id)]
            for ch in namespaces:
                try:
                    scrub_results.append(dao.scrub(
                        app.id, ch, repair=bool(args.scrub)))
                except Exception as e:  # noqa: BLE001 - a namespace
                    # that cannot be read is reported, not fatal
                    scrub_results.append({
                        "appId": app.id, "channelId": ch,
                        "error": f"{type(e).__name__}: {e}"})
    except Exception as e:  # noqa: BLE001 - doctor reports, never dies
        scrub_error = f"{type(e).__name__}: {e}"
    divergent = sum(r.get("divergentBuckets", 0) for r in scrub_results)
    repaired = sum(r.get("repairedEvents", 0) for r in scrub_results)

    if args.json:
        print(json.dumps({
            "replication": st,
            "liveReplicas": live,
            "quorumOk": quorum_ok,
            "convergence": scrub_results,
            "divergentBuckets": divergent,
            "repairedEvents": repaired,
            **({"scrubError": scrub_error} if scrub_error else {}),
        }, indent=2))
        return 0 if quorum_ok else 1

    print(f"replicated event store: {st['n']} replicas, write quorum "
          f"{st['writeQuorum']}, {live} live")
    for g in st.get("groups", ()):
        ok = ("ok" if g.get("quorumOk", True) else "QUORUM LOST")
        print(f"  shard group {g['shard']}: "
              f"{g.get('liveReplicas', '?')}/{g['n']} live, "
              f"quorum {g['writeQuorum']} — {ok}")
    print(f"{'replica':>7} {'live':<5} {'breaker':<9} {'hints':>6} "
          f"{'oldest':>8} {'corrupt':>7}")
    for r in st["replicas"]:
        age = r["hintOldestAgeSeconds"]
        print(f"{r['replica']:>7} {'up' if r['live'] else 'DOWN':<5} "
              f"{r['breaker']:<9} {r['hintDepth']:>6} "
              f"{'-' if age is None else f'{age:.0f}s':>8} "
              f"{r['hintsCorrupt']:>7}")
    c = st["counters"]
    print(f"lifetime: hinted {c['hinted']}, drained {c['drained']}, "
          f"dropped {c['hintsDropped']}, read-repairs {c['readRepairs']}")
    last = (st.get("scrub") or {})
    if last.get("lastScrubTs"):
        import datetime as _dt

        when = _dt.datetime.fromtimestamp(last["lastScrubTs"])
        res = last.get("lastResult") or {}
        print(f"last scrub: {when:%Y-%m-%d %H:%M:%S} — "
              f"{res.get('divergentBuckets', '?')} divergent bucket(s), "
              f"{res.get('repairedEvents', '?')} event(s) repaired")
    else:
        print("last scrub: never")
    verb = "repair" if args.scrub else "check"
    print(f"convergence {verb}: {len(scrub_results)} namespace(s), "
          f"{divergent} divergent bucket(s)"
          + (f", {repaired} event(s) repaired" if args.scrub else ""))
    if scrub_error:
        print(f"[WARN] convergence check failed: {scrub_error}")
    for r in scrub_results:
        if r.get("error"):
            print(f"[WARN] app {r['appId']} channel {r['channelId']}: "
                  f"{r['error']}")
    if not quorum_ok:
        print(f"[FAIL] write quorum LOST: {live} live < "
              f"{st['writeQuorum']} required — acked writes will fail "
              "until a replica rejoins")
    return 0 if quorum_ok else 1


def cmd_doctor(args) -> int:
    """Resilience doctor: poll every server surface's /healthz (liveness)
    + /readyz (readiness) and print the per-check detail — storage
    circuit-breaker states, load-shedder queue depth, eventserver spill
    backlog, the serving model's instance. The aggregate view `pio
    status` cannot give: status inspects THIS process's storage config;
    doctor inspects the RUNNING stack's health surfaces. With --fleet,
    inspects a sharded serving fleet through its router; with
    --storage, the replicated event store's replicas/hints/convergence."""
    from pio_tpu.utils.httpclient import HttpClientError, JsonHttpClient

    if getattr(args, "fleet", False):
        return _doctor_fleet(args)
    if getattr(args, "storage", False):
        return _doctor_storage(args)

    surfaces = {
        "eventserver": args.eventserver_port,
        "serving": args.serving_port,
        "adminserver": args.adminserver_port,
        "storageserver": args.storageserver_port,
        "dashboard": args.dashboard_port,
        # the freshness row: the fold-in worker's /healthz carries
        # staleness_seconds + queue depth, its /readyz flips past the
        # staleness budget (docs/freshness.md)
        "foldin": args.foldin_port,
    }
    report: dict[str, dict] = {}
    exit_code = 0
    for name, port in surfaces.items():
        url = f"http://{args.ip}:{port}"
        client = JsonHttpClient(url, timeout=args.timeout)
        entry: dict = {"url": url}
        try:
            client.request("GET", "/healthz")
            entry["live"] = True
        except HttpClientError as e:
            entry["live"] = False
            entry["error"] = e.message
            report[name] = entry
            continue  # down surfaces are reported, not failed: doctor
            # judges the health of what IS running
        try:
            ready = client.request("GET", "/readyz")
        except HttpClientError as e:
            # 503 carries the readiness payload in its message body;
            # surface the raw state either way
            entry["ready"] = False
            entry["detail"] = e.message
            exit_code = 1
            report[name] = entry
            continue
        entry["ready"] = bool(ready.get("ready"))
        entry["checks"] = ready.get("checks", {})
        if not entry["ready"]:
            exit_code = 1
        report[name] = entry

    # guarded rollout row: what (if anything) is canarying on the
    # serving surface — stage, verdict, per-arm guard stats
    rollout = None
    if report.get("serving", {}).get("live"):
        try:
            status = JsonHttpClient(
                report["serving"]["url"], timeout=args.timeout
            ).request("GET", "/rollout/status")
            if status and status.get("candidateInstanceId"):
                rollout = status
        except HttpClientError:
            pass

    # training-lifecycle sweep: kill -9'd runs leave INIT/TRAINING
    # instances whose heartbeat went stale; report them (and, with
    # --sweep-zombies, transition them to FAILED so they become
    # explicitly resumable and can never starve deploy's
    # get_latest_completed contract)
    zombies: list[dict] = []
    sweep_error = ""
    try:
        from pio_tpu.workflow.lifecycle import stale_instances, sweep_zombies

        storage = get_storage()
        stale_s = getattr(args, "zombie_stale_s", 600.0)
        if getattr(args, "sweep_zombies", False):
            found = sweep_zombies(storage, stale_after_s=stale_s)
            action = "swept"
        else:
            found = stale_instances(storage, stale_after_s=stale_s)
            action = "stale"
        zombies = [
            {"id": i.id, "status": i.status, "action": action,
             "lastStep": (i.progress or {}).get("step"),
             "heartbeat": (i.progress or {}).get("heartbeat")}
            for i in found
        ]
    except Exception as e:  # noqa: BLE001 - doctor reports, never dies
        sweep_error = f"{type(e).__name__}: {e}"

    # eval/tuning row: the last completed sweep's verdict, and whether
    # production actually serves the winning params — a COMPLETED
    # instance batch-tagged `from-eval:<id>` was trained by
    # `pio train --from-eval` from that sweep's best_params record
    eval_row = None
    eval_error = ""
    try:
        from pio_tpu.tuning.records import latest_best_params

        storage = get_storage()
        found = latest_best_params(storage)
        if found is not None:
            inst, payload = found
            completed = [
                i for i in
                storage.get_metadata_engine_instances().get_all()
                if i.status == "COMPLETED"
            ]
            if payload.get("engineId"):
                # NO fallback to other engines' instances: a sweep for
                # an engine that was never trained must report "not
                # trained yet", not point at an unrelated engine
                completed = [i for i in completed
                             if i.engine_id == payload["engineId"]]
            completed.sort(key=lambda i: i.start_time, reverse=True)
            prod = completed[0] if completed else None
            marker = f"from-eval:{inst.id}"
            eval_row = {
                "evaluationInstanceId": inst.id,
                "completedAt": inst.end_time.isoformat(),
                "metric": payload.get("metric"),
                "bestScore": payload.get("score"),
                "productionInstanceId": prod.id if prod else None,
                "productionBatch": prod.batch if prod else None,
                # substring match: `pio train --from-eval --batch X`
                # appends the marker to the operator's label
                "productionHasBestParams": bool(
                    prod and marker in (prod.batch or "")),
            }
    except Exception as e:  # noqa: BLE001 - doctor reports, never dies
        eval_error = f"{type(e).__name__}: {e}"

    chaos_spec = os.environ.get("PIO_TPU_CHAOS", "")
    if args.json:
        out = {"surfaces": report, "zombies": zombies}
        if rollout is not None:
            out["rollout"] = rollout
        if eval_row is not None:
            out["eval"] = eval_row
        if eval_error:
            out["evalError"] = eval_error
        if sweep_error:
            out["zombieSweepError"] = sweep_error
        if chaos_spec:
            out["chaos"] = chaos_spec
        print(json.dumps(out, indent=2))
        return exit_code

    if chaos_spec:
        print(f"[WARN] chaos injection active: PIO_TPU_CHAOS={chaos_spec}")
    for name, entry in report.items():
        if not entry["live"]:
            print(f"{name:14s} DOWN    {entry['url']}  ({entry['error']})")
            continue
        state = "ready" if entry.get("ready") else "NOT READY"
        print(f"{name:14s} up      {entry['url']}  {state}")
        for check, detail in sorted(entry.get("checks", {}).items()):
            ok = "ok " if detail.get("ok") else "FAIL"
            rest = {k: v for k, v in detail.items() if k != "ok"}
            print(f"  [{ok}] {check}: {rest}")
        if not entry.get("ready") and "detail" in entry:
            print(f"  detail: {entry['detail']}")
    if rollout is not None:
        state = rollout.get("verdict") or f"{rollout.get('stagePct')}%"
        arms = rollout.get("arms", {})
        cells = ", ".join(
            f"{arm}: {s.get('requests', 0)} req / {s.get('errors', 0)} err "
            f"/ {s.get('empty', 0)} empty"
            for arm, s in sorted(arms.items()))
        print(f"rollout        candidate {rollout.get('candidateInstanceId')}"
              f" [{state}] {rollout.get('timeInStageSeconds', 0):.0f}s "
              f"in stage — {cells}")
        div = (rollout.get("shadow") or {}).get("meanDivergence")
        if div is not None:
            print(f"  shadow divergence: {div} over "
                  f"{rollout['shadow'].get('samples', 0)} sample(s)")
    if eval_row is not None:
        score = eval_row["bestScore"]
        score_s = "nan" if score is None else f"{score:.4f}"
        print(f"eval           last sweep {eval_row['evaluationInstanceId']}"
              f" best {eval_row['metric']}={score_s}")
        if eval_row["productionInstanceId"] is None:
            print("  production: no COMPLETED engine instance yet — "
                  f"pio train --from-eval "
                  f"{eval_row['evaluationInstanceId']}")
        elif eval_row["productionHasBestParams"]:
            print(f"  production: instance "
                  f"{eval_row['productionInstanceId']} trained from "
                  "this sweep (best-known params in production)")
        else:
            print(f"  [WARN] production instance "
                  f"{eval_row['productionInstanceId']} was NOT trained "
                  "from the winning params — pio train --from-eval "
                  f"{eval_row['evaluationInstanceId']}")
    if eval_error:
        print(f"[WARN] eval check failed: {eval_error}")
    if sweep_error:
        print(f"[WARN] zombie check failed: {sweep_error}")
    for z in zombies:
        verb = ("swept to FAILED (resumable)" if z["action"] == "swept"
                else "stale (run doctor --sweep-zombies to mark FAILED)")
        print(f"zombie instance {z['id']} [{z['status']}] last step "
              f"{z['lastStep']} heartbeat {z['heartbeat']}: {verb}")
    return exit_code


def cmd_run(args) -> int:
    """Run a user script in the workflow environment (reference
    Console.scala `run` verb: arbitrary main class on the configured
    cluster; here: in-process with storage + mesh config active)."""
    import runpy

    script = args.script
    if not os.path.exists(script):
        return _fail(f"script {script} not found")
    get_storage()  # fail fast on storage misconfiguration
    saved_argv, saved_path = sys.argv, list(sys.path)
    sys.argv = [script] + list(args.args or [])
    sys.path.insert(0, os.path.dirname(os.path.abspath(script)) or ".")
    try:
        runpy.run_path(script, run_name="__main__")
    finally:
        sys.argv, sys.path[:] = saved_argv, saved_path
    return 0


def cmd_shell(args) -> int:
    """Interactive REPL with the storage + event store preloaded
    (reference bin/pio-shell: spark-shell with the assembly on the
    classpath)."""
    import code

    from pio_tpu.data.eventstore import EventStore

    storage = get_storage()
    ns = {
        "storage": storage,
        "events": storage.get_events(),
        "apps": storage.get_metadata_apps(),
        "event_store": EventStore(storage),
    }
    banner = (
        f"pio-tpu {__version__} shell\n"
        "preloaded: storage, events, apps, event_store"
    )
    code.interact(banner=banner, local=ns)
    return 0


def cmd_app(args) -> int:
    storage = get_storage()
    apps = storage.get_metadata_apps()
    keys = storage.get_metadata_access_keys()
    channels = storage.get_metadata_channels()
    sub = args.subcommand
    if sub == "new":
        created = appops.create_app(
            storage, args.name, args.description,
            app_id=args.id or 0, access_key=args.access_key or "",
        )
        if created is None:
            return _fail(f"App {args.name} already exists.")
        app_id, key = created
        print(f"App '{args.name}' created (id {app_id}).")
        print(f"Access key: {key}")
        return 0
    if sub == "list":
        for a in sorted(apps.get_all(), key=lambda a: a.id):
            ks = keys.get_by_appid(a.id)
            print(f"{a.id:>6}  {a.name:<24} keys={len(ks)}")
        return 0
    if sub == "show":
        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        print(f"App: {a.name} (id {a.id})")
        print(f"Description: {a.description or ''}")
        for k in keys.get_by_appid(a.id):
            events = ",".join(k.events) or "(all)"
            print(f"  key {k.key} events={events}")
        for c in channels.get_by_appid(a.id):
            print(f"  channel {c.id}: {c.name}")
        return 0
    if sub == "delete":
        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        appops.delete_app(storage, a)
        print(f"App '{args.name}' deleted.")
        return 0
    if sub == "data-delete":
        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        channel_id = None
        if args.channel:
            ch = next((c for c in channels.get_by_appid(a.id)
                       if c.name == args.channel), None)
            if ch is None:
                return _fail(f"Channel {args.channel} does not exist.")
            channel_id = ch.id
        appops.delete_app_data(storage, a, channel_id)
        print(f"Data of app '{args.name}' deleted.")
        return 0
    if sub == "trim":
        from pio_tpu.utils.time import parse_time

        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        dst = apps.get_by_name(args.dst)
        if dst is None:
            return _fail(f"Destination app {args.dst} does not exist "
                         "(create it with `pio app new` first).")
        try:
            counts = appops.trim_copy(
                storage, a, dst,
                start_time=parse_time(args.start) if args.start else None,
                until_time=parse_time(args.until) if args.until else None,
                channel_name=args.channel or None,
            )
        except ValueError as e:
            return _fail(str(e))
        total = sum(counts.values())
        detail = ", ".join(f"{k}: {v}" for k, v in counts.items())
        print(f"Copied {total} events from '{a.name}' to '{dst.name}' "
              f"({detail}).")
        return 0
    if sub == "cleanup":
        from pio_tpu.utils.time import parse_time

        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        try:
            counts = appops.cleanup_events(
                storage, a,
                until_time=parse_time(args.until),  # --until is required
                channel_name=args.channel or None,
            )
        except ValueError as e:
            return _fail(str(e))
        total = sum(counts.values())
        detail = ", ".join(f"{k}: {v}" for k, v in counts.items())
        print(f"Deleted {total} events from '{a.name}' ({detail}).")
        return 0
    if sub == "channel-new":
        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        if not Channel.is_valid_name(args.channel):
            return _fail(
                f"Channel name {args.channel} is invalid "
                "(1-16 alphanumeric/dash characters)."
            )
        cid = channels.insert(Channel(0, args.channel, a.id))
        if cid is None:
            return _fail(f"Channel {args.channel} could not be created.")
        storage.get_events().init(a.id, cid)
        print(f"Channel '{args.channel}' (id {cid}) created for app "
              f"'{args.name}'.")
        return 0
    if sub == "channel-delete":
        a = apps.get_by_name(args.name)
        if a is None:
            return _fail(f"App {args.name} does not exist.")
        ch = next((c for c in channels.get_by_appid(a.id)
                   if c.name == args.channel), None)
        if ch is None:
            return _fail(f"Channel {args.channel} does not exist.")
        storage.get_events().remove(a.id, ch.id)
        channels.delete(ch.id)
        print(f"Channel '{args.channel}' deleted.")
        return 0
    return _fail(f"unknown app subcommand {sub}")


def cmd_accesskey(args) -> int:
    storage = get_storage()
    keys = storage.get_metadata_access_keys()
    if args.subcommand == "new":
        a = storage.get_metadata_apps().get_by_name(args.app_name)
        if a is None:
            return _fail(f"App {args.app_name} does not exist.")
        key = keys.insert(
            AccessKey("", a.id, tuple(args.event or ()))
        )
        print(f"Access key: {key}")
        return 0
    if args.subcommand == "list":
        app_filter = None
        if args.app_name:
            a = storage.get_metadata_apps().get_by_name(args.app_name)
            if a is None:
                return _fail(f"App {args.app_name} does not exist.")
            app_filter = a.id
        for k in keys.get_all():
            if app_filter is not None and k.appid != app_filter:
                continue
            events = ",".join(k.events) or "(all)"
            print(f"{k.key} app={k.appid} events={events}")
        return 0
    if args.subcommand == "delete":
        keys.delete(args.key)
        print(f"Access key {args.key} deleted.")
        return 0
    return _fail(f"unknown accesskey subcommand {args.subcommand}")


def cmd_build(args) -> int:
    """Check the engine dir: engine.json parses + factory imports
    (replaces the reference's sbt package, Console.compile:933-997)."""
    variant = _load_variant(args.engine_dir)
    engine, ep = _engine_from_variant(variant, args.engine_dir)
    print(f"Engine factory {variant['engineFactory']} loads; "
          f"{len(ep.algorithms)} algorithm(s) configured.")
    return 0


def cmd_train(args) -> int:
    from pio_tpu.workflow.lifecycle import EXIT_PREEMPTED, TrainingPreempted
    from pio_tpu.workflow.train import run_train

    if args.resume and args.auto_resume:
        return _fail("--resume and --auto-resume are mutually exclusive")
    variant = _load_variant(args.engine_dir)
    engine, ep = _engine_from_variant(variant, args.engine_dir)
    engine_id, engine_version, engine_variant = _engine_ids(
        variant, args.engine_dir
    )
    from pio_tpu.controller.base import TrainingInterruption

    storage = get_storage()
    batch = args.batch or ""
    if getattr(args, "from_eval", ""):
        ep, eval_id = _apply_from_eval(engine, ep, storage,
                                       args.from_eval)
        # the batch marker is how `pio doctor` knows production runs
        # the sweep's winner (docs/evaluation.md "Close the loop") —
        # APPENDED to an operator-supplied batch label, never displaced
        # by it (doctor matches by substring)
        marker = f"from-eval:{eval_id}"
        batch = f"{batch} {marker}".strip()
        print(f"Training with best params from evaluation {eval_id}")
    if args.device_profile:
        from pio_tpu.utils.tracing import start_device_profile

        start_device_profile(args.device_profile)
    try:
        instance_id = run_train(
            engine, ep, storage,
            engine_id=engine_id, engine_version=engine_version,
            engine_variant=engine_variant,
            engine_factory=variant["engineFactory"],
            batch=batch,
            # the run makes its context: reaching the chip (~15 s in a
            # fresh process) is then its `train.devices` span
            use_mesh=not args.no_mesh,
            stop_after_read=args.stop_after_read,
            stop_after_prepare=args.stop_after_prepare,
            resume_instance_id=args.resume or None,
            auto_resume=args.auto_resume,
            checkpoint_root=args.checkpoint_root or None,
        )
    except TrainingPreempted as e:
        # preemption honored: checkpoint on disk, instance INTERRUPTED.
        # EXIT_PREEMPTED (75, EX_TEMPFAIL) tells supervisors this run
        # wants `pio train --resume` (or --auto-resume), not a bug report.
        print(f"Training preempted ({e}); resume with: "
              "pio train --auto-resume")
        return EXIT_PREEMPTED
    except TrainingInterruption as e:
        # controlled debug stop (reference --stop-after-read/-prepare)
        print(f"Training interrupted: {e}")
        return 0
    finally:
        if args.device_profile:
            from pio_tpu.utils.tracing import stop_device_profile

            stop_device_profile()
            print(f"Device profile in {args.device_profile}; read it "
                  "with: python -m pio_tpu.obs.profile "
                  f"{args.device_profile}")
    print(f"Training completed. Engine instance: {instance_id}")
    return 0


def cmd_eval(args) -> int:
    if args.sweep:
        return _eval_sweep(args)
    if not args.evaluation_class or not args.params_generator_class:
        return _fail("pio eval takes either --sweep (grid mode) or "
                     "<EvaluationClass> <ParamsGeneratorClass>")
    from pio_tpu.workflow.evaluate import run_evaluation_class

    evaluation = _load_factory(args.evaluation_class, args.engine_dir)
    generator = _load_factory(args.params_generator_class, args.engine_dir)
    instance_id, result = run_evaluation_class(
        evaluation, generator, get_storage(),
        output_path=args.output or None,
        workers=args.workers,
    )
    print(f"Evaluation completed. Instance: {instance_id}")
    print(f"Best score: [{result.best_score.score}]")
    print(f"Best params: {result.best_engine_params.to_json()}")
    return 0


def _sweep_candidates(engine, base_ep, args) -> list:
    """The candidate grid: either an EngineParamsGenerator class (full
    EngineParams control) or a --grid JSON over the FIRST algorithm's
    params — {"lambda_": [0.01, 0.1], "rank": [8, 16]} expands to the
    cartesian product, each candidate overriding engine.json's params."""
    import dataclasses
    import itertools

    if args.params_generator:
        gen = _load_factory(args.params_generator, args.engine_dir)
        return gen.params_list()
    if not args.grid:
        raise ValueError(
            "--sweep needs --grid '{\"param\": [values...]}' (or "
            "@file.json) or --params-generator pkg.Class")
    spec = args.grid
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            grid = json.load(f)
    else:
        grid = json.loads(spec)
    if not isinstance(grid, dict) or not grid:
        raise ValueError("--grid must be a non-empty JSON object of "
                         "param name -> list of values")
    base_algos = base_ep.algorithms or [("", None)]
    algo_name, algo_params = base_algos[0]
    keys = sorted(grid)           # deterministic candidate order
    values = []
    for k in keys:
        v = grid[k]
        values.append(v if isinstance(v, list) else [v])
    candidates = []
    for combo in itertools.product(*values):
        overrides = dict(zip(keys, combo))
        if dataclasses.is_dataclass(algo_params):
            try:
                p = dataclasses.replace(algo_params, **overrides)
            except TypeError:
                valid = sorted(
                    f.name for f in dataclasses.fields(algo_params))
                bad = sorted(set(overrides) - set(valid))
                raise ValueError(
                    f"--grid key(s) {bad} are not params of "
                    f"{type(algo_params).__name__} (valid: "
                    f"{', '.join(valid)})") from None
        else:
            p = {**(algo_params or {}), **overrides}
        # vary ONLY the first algorithm; a multi-algo engine keeps its
        # trailing algorithms in every candidate (and in the persisted
        # winner --from-eval deploys)
        candidates.append(dataclasses.replace(
            base_ep, algorithms=[(algo_name, p), *base_algos[1:]]))
    return candidates


def _eval_sweep(args) -> int:
    """`pio eval --sweep` — the batched hyperparameter sweep
    (docs/evaluation.md): grid/generator candidates over deterministic
    k-fold or event-time splits, shape-compatible candidates trained as
    ONE stacked device program, per-fold results checkpointed durably
    (resume with --resume-eval), winner persisted as
    `<eval-iid>:best_params` for `pio train/deploy --from-eval`."""
    from pio_tpu.obs import make_recorder
    from pio_tpu.tuning import SweepConfig, parse_metric
    from pio_tpu.utils.tracing import Tracer
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.evaluate import run_sweep_evaluation

    engine_dir = args.engine_dir or "."
    variant = _load_variant(engine_dir)
    engine, ep = _engine_from_variant(variant, engine_dir)
    engine_id, engine_version, engine_variant = _engine_ids(
        variant, engine_dir)
    try:
        candidates = _sweep_candidates(engine, ep, args)
        metric = parse_metric(args.metric)
        others = [parse_metric(s)
                  for s in (args.other_metrics or "").split(",")
                  if s.strip()]
    except (ValueError, OSError) as e:
        # OSError: --grid @file.json that does not exist/read — the
        # same one-line error every other argument mistake gets
        return _fail(str(e))
    config = SweepConfig(
        metric=metric, other_metrics=others,
        split=args.split, folds=args.folds, seed=args.seed,
    )
    storage = get_storage()
    ctx = create_workflow_context(storage, use_mesh=not args.no_mesh)
    recorder = make_recorder("eval")
    tracer = Tracer(recorder=recorder)
    http = status = None
    if args.metrics_port is not None:
        from pio_tpu.tuning.server import EvalStatus, create_eval_server

        status = EvalStatus(tracer, recorder)
        http = create_eval_server(
            status, ip=args.ip, port=args.metrics_port,
            server_key=args.server_key
            or os.environ.get("PIO_SERVER_KEY", ""))
        http.start()
        print(f"sweep metrics on http://{args.ip}:{http.port} "
              "(/metrics, /debug/traces.json; watch with `pio top "
              f"--url http://{args.ip}:{http.port}`)")
    try:
        instance_id, result = run_sweep_evaluation(
            engine, candidates, storage, config,
            engine_id=engine_id, engine_version=engine_version,
            engine_variant=engine_variant,
            batch=args.batch or "",
            output_path=args.output or None,
            resume_eval_id=args.resume_eval or None,
            ctx=ctx, tracer=tracer,
            status=status,
        )
    finally:
        if http is not None:
            http.stop()
    print(f"Sweep completed. Evaluation instance: {instance_id} "
          f"({len(candidates)} candidate(s), {args.split} x "
          f"{args.folds})")
    print(f"Best {result.metric_header}: [{result.best_score.score}] "
          f"(candidate #{result.best_idx})")
    print(f"Best params: {result.best_engine_params.to_json()}")
    print(f"Deploy the winner: pio train --from-eval {instance_id} "
          f"&& pio deploy --from-eval {instance_id}")
    return 0


def _apply_from_eval(engine, ep, storage, from_eval: str):
    """Merge a sweep's winning ALGORITHM params into engine.json's
    EngineParams (datasource/preparator/serving stay the operator's —
    the sweep tuned the model, not the read). -> (merged ep, eval id)."""
    import dataclasses

    from pio_tpu.tuning.records import resolve_from_eval

    eval_id, payload = resolve_from_eval(storage, from_eval)
    tuned = engine.engine_params_from_variant(
        {"algorithms": payload["variant"]["algorithms"]})
    return dataclasses.replace(ep, algorithms=tuned.algorithms), eval_id


def cmd_deploy(args) -> int:
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.serve import ServingConfig, create_query_server

    if args.canary:
        # canary mode is a CLIENT verb: it tells the ALREADY-RUNNING
        # serving process (single-host server or fleet router — same
        # /rollout surface) to stage a candidate, rather than booting a
        # new one (docs/serving.md "Guarded rollout")
        if getattr(args, "from_eval", ""):
            return _fail("--from-eval does not combine with --canary: "
                         "the canary stages an already-TRAINED "
                         "instance — run `pio train --from-eval` "
                         "first, then canary that instance")
        return _deploy_canary_cmd(args)
    if args.fleet:
        # multi-tenant pool boot: everything comes from the recorded
        # FleetPlan (tenants, packing, pool shape) — no engine dir
        if args.fleet_join:
            return _fail("--fleet boots a pool from its recorded plan; "
                         "--fleet-join adds THIS engine to a plan — "
                         "run them as separate commands")
        return _deploy_fleet_pool_cmd(args)
    variant = _load_variant(args.engine_dir)
    engine, ep = _engine_from_variant(variant, args.engine_dir)
    engine_id, engine_version, engine_variant = _engine_ids(
        variant, args.engine_dir
    )
    storage = get_storage()
    if getattr(args, "from_eval", ""):
        if args.shards > 0:
            return _fail("--from-eval is not supported with --shards "
                         "yet: fleet shards serve already-partitioned "
                         "model blobs; train the winner "
                         "(`pio train --from-eval`) and fleet-deploy "
                         "that instance")
        ep, eval_id = _apply_from_eval(engine, ep, storage,
                                       args.from_eval)
        print(f"Deploying with best params from evaluation {eval_id}")
    if args.fleet_join:
        return _deploy_fleet_join_cmd(args, storage, engine_id,
                                      engine_version, engine_variant)
    if args.shards > 0:
        # fleet path: partition the persisted model at deploy time, boot
        # N x R shard servers + the router front-end (serving_fleet/)
        return _deploy_fleet_cmd(args, storage, engine_id, engine_version,
                                 engine_variant,
                                 retrieval=_retrieval_block(ep))
    ctx = create_workflow_context(storage, use_mesh=not args.no_mesh)
    config = ServingConfig(
        ip=args.ip, port=args.port,
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant,
        feedback=args.feedback,
        feedback_app_name=args.feedback_app or "",
        server_key=args.server_key or os.environ.get("PIO_SERVER_KEY", ""),
        warm_query=json.loads(args.warm_query) if args.warm_query else None,
        certfile=args.cert, keyfile=args.key,
        backend=args.server_backend,
        batch_window_ms=args.batch_window_ms,
        coalesce_window_ms=args.coalesce_window_ms,
    )
    http, qs = create_query_server(
        engine, ep, storage, config, ctx=ctx,
        instance_id=args.engine_instance_id,
    )
    http.start()  # bind first: with --port 0 the real port is only known now
    scheme = "https" if http.tls else "http"
    print(f"Engine instance {qs.instance.id} deployed on "
          f"{scheme}://{args.ip}:{http.port}")
    import threading

    def watch_stop():
        qs._stop_requested.wait()
        http.stop()

    # pio: lint-ok[context-loss] deliberate detach: shutdown watcher
    # waits for /stop for the process lifetime; no request context
    threading.Thread(target=watch_stop, daemon=True).start()
    try:
        http.wait()
    except KeyboardInterrupt:
        http.stop()
    qs.close()
    print("Server stopped.")
    return 0


def _deploy_fleet_cmd(args, storage, engine_id: str, engine_version: str,
                      engine_variant: str,
                      retrieval: dict | None = None) -> int:
    """`pio deploy --shards N [--replicas R]`: sharded, replicated
    serving (docs/serving.md "Sharded fleet"). The router binds
    --ip/--port; shard servers take ephemeral ports (printed, and always
    discoverable via the router's /fleet.json)."""
    from pio_tpu.serving_fleet.fleet import deploy_fleet
    from pio_tpu.serving_fleet.router import RouterConfig

    # fail loudly on single-host-only options rather than silently
    # ignoring them — --cert/--key especially: an operator asking for
    # TLS must never get plaintext without an error
    if args.cert or args.key:
        return _fail("TLS termination is not supported in fleet mode yet "
                     "(--shards with --cert/--key); front the router with "
                     "a TLS-terminating proxy instead")
    unsupported = [flag for flag, on in (
        ("--feedback", args.feedback),
        ("--warm-query", bool(args.warm_query)),
        ("--batch-window-ms", args.batch_window_ms > 0),
    ) if on]
    if unsupported:
        return _fail(f"{', '.join(unsupported)} not supported in fleet "
                     "mode (--shards); they configure the single-host "
                     "QueryServer")
    if args.replicas < 1:
        return _fail("--replicas must be >= 1")

    # shard endpoints must be dialable by the router, so a wildcard bind
    # resolves to loopback for the in-process fleet shape
    ip = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    handle = deploy_fleet(
        storage,
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant,
        n_shards=args.shards, n_replicas=args.replicas,
        ip=ip,
        router_port=args.port,
        instance_id=args.engine_instance_id,
        server_key=args.server_key or os.environ.get("PIO_SERVER_KEY", ""),
        memory_budget_bytes=args.shard_memory_budget_mb * 1024 * 1024,
        shard_backend=args.server_backend,
        retrieval=retrieval,
        # continuous batching: coalesce concurrent fan-outs per shard
        # group into one batched binary frame (docs/serving.md)
        router_config=(RouterConfig(
            coalesce_window_ms=args.coalesce_window_ms)
            if args.coalesce_window_ms > 0 else None),
    )
    mode = (retrieval or {}).get("mode", "exact")
    print(f"Fleet router for instance {handle.plan.instance_id} on "
          f"http://{ip}:{handle.router_http.port} "
          f"({args.shards} shards x {args.replicas} replicas, "
          f"retrieval: {mode})")
    for s, urls in enumerate(handle.endpoints):
        print(f"  shard {s}: {' '.join(urls)}")
    import threading

    def watch_stop():
        handle.router._stop_requested.wait()
        handle.router_http.stop()

    # pio: lint-ok[context-loss] deliberate detach: shutdown watcher
    # waits for /stop for the process lifetime; no request context
    threading.Thread(target=watch_stop, daemon=True).start()
    try:
        handle.wait()
    except KeyboardInterrupt:
        pass
    handle.close()
    print("Fleet stopped.")
    return 0


def _deploy_fleet_join_cmd(args, storage, engine_id: str,
                           engine_version: str,
                           engine_variant: str) -> int:
    """`pio deploy --fleet-join NAME`: pack THIS engine's partitions
    into the named pool's remaining capacity (residents never move),
    persist the placement, and — when a multi-tenant router is already
    running at --ip/--port — fan the live attach so the tenant starts
    serving with zero pool downtime (docs/serving.md "Multi-tenant
    fleet")."""
    from pio_tpu.serving_fleet.tenancy import (
        FleetCapacityError, TenantSpec, join_fleet_plan,
    )
    from pio_tpu.utils.httpclient import JsonHttpClient

    spec = TenantSpec(
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant,
        instance_id=args.engine_instance_id or "",
        quota_qps=args.tenant_quota_qps,
        quota_burst=args.tenant_quota_burst,
        weight=args.tenant_weight,
        max_concurrency=args.tenant_max_concurrency,
    )
    try:
        plan, placement = join_fleet_plan(
            storage, args.fleet_join, spec,
            n_shards=args.shards if args.shards > 0 else 2,
            n_replicas=args.replicas,
            memory_budget_bytes=args.shard_memory_budget_mb
            * 1024 * 1024,
        )
    except FleetCapacityError as e:
        return _fail(str(e))
    except ValueError as e:
        return _fail(f"fleet join failed: {e}")
    print(f"Tenant {spec.key} joined fleet {plan.name!r}: instance "
          f"{placement.instance_id}, {placement.total_bytes()} bytes "
          f"over shard(s) {sorted(set(placement.owners))} "
          f"(pool loads: {plan.shard_loads()})")
    # best-effort live attach: a pool that is not running yet is fine —
    # the recorded placement serves on the next `pio deploy --fleet`
    ip = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    key = args.server_key or os.environ.get("PIO_SERVER_KEY", "")
    try:
        out = JsonHttpClient(f"http://{ip}:{args.port}",
                             timeout=30).request(
            "POST", "/fleet/attach_tenant", {"tenant": spec.key},
            params={"accessKey": key} if key else None)
        print(f"live attach: {json.dumps(out)}")
    except Exception as e:  # noqa: BLE001 - attach is best-effort
        print(f"no live router attached at http://{ip}:{args.port} "
              f"({e}); placement is recorded — `pio deploy --fleet "
              f"{plan.name}` serves it")
    return 0


def _deploy_fleet_pool_cmd(args) -> int:
    """`pio deploy --fleet NAME`: boot the whole multi-tenant pool —
    tenant-mux shard hosts + the multi-tenant router — from the
    recorded FleetPlan."""
    from pio_tpu.serving_fleet.tenancy import deploy_multi_fleet

    storage = get_storage()
    ip = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    try:
        handle = deploy_multi_fleet(
            storage, name=args.fleet, ip=ip, router_port=args.port,
            server_key=args.server_key
            or os.environ.get("PIO_SERVER_KEY", ""),
            router_backend=args.server_backend,
        )
    except ValueError as e:
        return _fail(str(e))
    plan = handle.fleet_plan
    print(f"Multi-tenant fleet {plan.name!r} on "
          f"http://{ip}:{handle.router_http.port} "
          f"({plan.n_shards} shards x {plan.n_replicas} replicas, "
          f"{len(plan.tenants)} tenants)")
    for t in plan.tenants:
        print(f"  tenant {t.tenant}: instance {t.instance_id}, "
              f"{t.total_bytes()} bytes over shard(s) "
              f"{sorted(set(t.owners))}")
    for s, urls in enumerate(handle.endpoints):
        print(f"  shard host {s}: {' '.join(urls)}")
    import threading

    def watch_stop():
        handle.router._stop_requested.wait()
        handle.router_http.stop()

    # pio: lint-ok[context-loss] deliberate detach: shutdown watcher
    # waits for /stop for the process lifetime; no request context
    threading.Thread(target=watch_stop, daemon=True).start()
    try:
        handle.wait()
    except KeyboardInterrupt:
        pass
    handle.close()
    print("Fleet stopped.")
    return 0


def _rollout_call(args, method: str, path: str, body=None) -> int:
    """Shared client for the rollout verbs: POST to the running serving
    process's /rollout surface (single-host server and fleet router
    expose the identical routes), print the JSON answer."""
    from pio_tpu.utils.httpclient import HttpClientError, JsonHttpClient

    ip = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    url = f"http://{ip}:{args.port}"
    key = args.server_key or os.environ.get("PIO_SERVER_KEY", "")
    client = JsonHttpClient(url, timeout=getattr(args, "timeout", 30.0))
    try:
        out = client.request(method, path, body,
                             params={"accessKey": key} if key else None)
    except HttpClientError as e:
        if e.status == 0:
            return _fail(f"no serving process at {url}: {e.message}")
        return _fail(f"{path} answered HTTP {e.status}: {e.message}")
    print(json.dumps(out, indent=2))
    return 0


def _deploy_canary_cmd(args) -> int:
    """`pio deploy --canary <pct|auto>` — begin a guarded rollout of the
    latest eligible COMPLETED instance (or --engine-instance-id) on the
    running server. `auto` ramps 1% -> 5% -> 25% -> 100% while guards
    stay green; a fixed pct holds there until `pio promote` /
    `pio rollback`."""
    spec = args.canary.strip().lower()
    body: dict = {}
    if spec == "auto":
        body["auto"] = True
    else:
        try:
            body["pct"] = int(spec)
        except ValueError:
            return _fail(f"--canary takes a percentage or 'auto', "
                         f"got {args.canary!r}")
    if args.engine_instance_id:
        body["instanceId"] = args.engine_instance_id
    if args.canary_min_stage_seconds is not None:
        body["minStageSeconds"] = args.canary_min_stage_seconds
    if args.canary_min_stage_samples is not None:
        body["minStageSamples"] = args.canary_min_stage_samples
    return _rollout_call(args, "POST", "/rollout/deploy", body)


def cmd_promote(args) -> int:
    """`pio promote` — conclude a green canary: the candidate becomes
    the active instance at 100% and the PROMOTED verdict is persisted
    (it survives restarts; docs/serving.md "Guarded rollout")."""
    return _rollout_call(args, "POST", "/rollout/promote", {})


def cmd_rollback(args) -> int:
    """`pio rollback` — one-command instant rollback: 100% of traffic
    reverts to the last-good instance atomically and the ROLLED_BACK
    verdict is persisted, so no reload ever auto-advances onto the
    rejected instance again."""
    return _rollout_call(args, "POST", "/rollout/rollback",
                         {"reason": args.reason or "operator rollback"})


def cmd_reshard(args) -> int:
    """`pio reshard --shards N'` — live elastic resharding: grow or
    shrink the RUNNING fleet to N' shard groups with zero downtime
    (docs/serving.md "Elastic resharding"). The router streams moved
    partitions to their new owners, double-routes affected partitions
    during the move, and flips the durable plan atomically; `--status`
    follows an in-flight migration, `--abort` restores the old plan
    bit-identical."""
    from pio_tpu.utils.httpclient import HttpClientError, JsonHttpClient

    ip = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    url = f"http://{ip}:{args.port}"
    key = args.server_key or os.environ.get("PIO_SERVER_KEY", "")
    params = {"accessKey": key} if key else None
    client = JsonHttpClient(url, timeout=args.timeout)

    def call(method, path, body=None):
        return client.request(method, path, body, params=params)

    try:
        if args.status:
            print(json.dumps(call("GET", "/reshard/status"), indent=2))
            return 0
        if args.abort:
            out = call("POST", "/reshard/abort")
            print(json.dumps(out, indent=2))
            return 0 if out.get("verdict") == "ABORTED" else 1
        if args.shards is None or args.shards < 1:
            return _fail("pio reshard needs --shards N' (or --status / "
                         "--abort)")
        body: dict = {"nShards": args.shards}
        if args.endpoint:
            # each --endpoint is ONE new shard group; commas separate
            # its replicas: --endpoint http://h1:9107,http://h2:9107
            body["endpoints"] = [
                [u.strip() for u in e.split(",") if u.strip()]
                for e in args.endpoint]
        out = call("POST", "/reshard/begin", body)
        if out.get("noop"):
            print(out.get("message", "nothing to do"))
            return 0
        print(f"resharding {out.get('nShardsOld')} -> "
              f"{out.get('nShardsNew')} shard(s): "
              f"{out.get('partitionsMoving')} partition(s) to move "
              f"(plan v{out.get('planVersionOld')} -> "
              f"v{out.get('planVersionNew')})")
        if args.no_wait:
            print("migration running; follow with `pio reshard "
                  "--status`")
            return 0
        last = -1
        while True:
            st = call("GET", "/reshard/status")
            staged = st.get("partitionsStaged", 0)
            if staged != last:
                print(f"  staged {staged}/"
                      f"{st.get('partitionsMoving', 0)} partition(s)")
                last = staged
            if not st.get("inFlight"):
                verdict = st.get("verdict")
                print(f"reshard {verdict}: "
                      f"{st.get('reason') or 'no reason recorded'}")
                return 0 if verdict == "COMMITTED" else 1
            time.sleep(0.2)
    except HttpClientError as e:
        if e.status == 0:
            return _fail(f"no fleet router at {url}: {e.message}")
        return _fail(f"HTTP {e.status}: {e.message}")


def _obs_urls(args) -> list[str]:
    """The surfaces `pio trace` / `pio top` poll: explicit --url flags,
    plus (given --router-url) the router AND every shard replica it
    knows from /fleet.json — one address covers the whole fleet."""
    from pio_tpu.obs.assemble import discover_fleet_urls

    urls = [u.rstrip("/") for u in (args.url or [])]
    if args.router_url:
        for u in discover_fleet_urls(args.router_url,
                                     timeout=args.timeout):
            if u not in urls:
                urls.append(u)
    if not urls:
        urls = [f"http://127.0.0.1:{args.port}"]
    return urls


def cmd_trace(args) -> int:
    """`pio trace <trace_id>` — collect span records from every surface
    (router, its shard replicas, serving, storage, folder) and print the
    MERGED span tree with per-hop self-time (docs/observability.md).
    Get a trace id from a response's X-Pio-Trace-Id echo header (send
    `X-Pio-Trace: 1`), from /metrics.json exemplars, or from a
    surface's /debug/traces.json listing."""
    from pio_tpu.obs.assemble import collect_trace, render_tree

    urls = _obs_urls(args)
    spans, misses = collect_trace(urls, args.trace_id,
                                  server_key=args.server_key or "",
                                  timeout=args.timeout)
    if args.json:
        print(json.dumps({
            "traceId": args.trace_id,
            "spans": [s.to_dict() for s in spans],
            "misses": misses,
        }, indent=2))
        return 0 if spans else 1
    print(render_tree(args.trace_id, spans, misses))
    return 0 if spans else 1


def cmd_top(args) -> int:
    """`pio top` — the live span table across surfaces: rate, p50, p99,
    error% per span per arm over each recorder's recent window. One
    shot by default; --watch N refreshes every N seconds."""
    import time as _time

    from pio_tpu.obs.assemble import collect_span_tables, render_span_table

    urls = _obs_urls(args)
    while True:
        rows, errors = collect_span_tables(
            urls, server_key=args.server_key or "", timeout=args.timeout)
        if args.json:
            print(json.dumps({"spans": rows, "errors": errors}))
        else:
            print(render_span_table(rows, errors))
        if not args.watch:
            return 0 if rows or not errors else 1
        try:
            _time.sleep(args.watch)
            if not args.json:
                print()
        except KeyboardInterrupt:
            return 0


def cmd_foldin(args) -> int:
    """`pio foldin` — the streaming fold-in worker (docs/freshness.md):
    tail the event stream, solve refreshed user rows against the
    deployed model's item factors, and hot-swap them into serving
    (single host or fleet router). Training-read semantics and solver
    params come from the SAME engine.json train/deploy read, so they
    cannot drift from the model being refreshed."""
    import threading

    from pio_tpu.freshness import (
        FoldInConfig, FoldInWorker, RouterFleetApplier, ServingHttpApplier,
        create_foldin_server,
    )
    from pio_tpu.freshness.tail import HttpEventSource
    from pio_tpu.ops import als

    variant = _load_variant(args.engine_dir)
    engine, ep = _engine_from_variant(variant, args.engine_dir)
    engine_id, engine_version, engine_variant = _engine_ids(
        variant, args.engine_dir
    )
    _, ds = ep.datasource
    _, ap = (ep.algorithms or [(None, None)])[0]
    rank = getattr(ap, "rank", None)
    if rank is None:
        return _fail(
            "fold-in needs a factor-model engine (algorithm params with "
            f"rank/lambda_/alpha/implicit_prefs); got {type(ap).__name__}")
    als_params = als.ALSParams(
        rank=rank,
        reg=getattr(ap, "lambda_", 0.1),
        alpha=getattr(ap, "alpha", 1.0),
        implicit=getattr(ap, "implicit_prefs", False),
    )
    app_name = getattr(ds, "app_name", "")
    if not app_name:
        return _fail("engine.json datasource params carry no appName")
    state_path = args.state_path or os.path.join(
        os.path.expanduser(os.environ.get("PIO_TPU_HOME", "~/.pio_tpu")),
        "foldin", f"{engine_id}-{engine_variant}.cursor")
    key = args.server_key or os.environ.get("PIO_SERVER_KEY", "")
    config = FoldInConfig(
        app_name=app_name,
        channel_name=getattr(ds, "channel_name", None),
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant,
        event_names=tuple(getattr(ds, "event_names", ("rate", "buy"))),
        value_event=getattr(ds, "rating_event", "rate"),
        default_value=getattr(ds, "implicit_value", 4.0),
        als_params=als_params,
        state_path=state_path,
        replay=args.replay,
        poll_interval_s=args.interval,
        max_batch_users=args.max_batch_users,
        staleness_budget_s=args.staleness_budget,
        ip=args.ip, port=args.port,
        # the same key that authenticates the applies guards the
        # folder's own /debug trace routes (traces carry request paths
        # + user-batch timing)
        server_key=key,
    )
    if args.router_url:
        applier = RouterFleetApplier(args.router_url, key)
        target = args.router_url
    else:
        applier = ServingHttpApplier(args.serving_url, key)
        target = args.serving_url
    source = None
    if args.event_server_url:
        source = HttpEventSource(
            args.event_server_url, args.access_key,
            channel_name=config.channel_name,
            event_names=config.event_names,
            wait_s=args.tail_wait,
        )
    storage = get_storage()
    worker = FoldInWorker(storage, config, applier, source=source)
    if args.once:
        try:
            stats = worker.run_once()
        except Exception as e:  # noqa: BLE001 - --once reports, not loops
            print(json.dumps({"error": f"{type(e).__name__}: {e}",
                              **worker.snapshot()}))
            return 1
        print(json.dumps({**stats, **worker.snapshot()}))
        return 0
    http = create_foldin_server(worker)
    http.start()
    worker.start()
    print(f"fold-in worker for engine {engine_id} -> {target} "
          f"(health on http://{args.ip}:{http.port}, cursor {state_path})")

    stop = threading.Event()
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    worker.stop()
    http.stop()
    print("fold-in worker stopped.")
    return 0


def cmd_batchpredict(args) -> int:
    """Offline bulk scoring through the full serving composition
    (workflow/batchpredict.py); no HTTP server involved."""
    import contextlib
    import sys as _sys

    from pio_tpu.workflow.batchpredict import run_batch_predict
    from pio_tpu.workflow.context import create_workflow_context

    variant = _load_variant(args.engine_dir)
    engine, ep = _engine_from_variant(variant, args.engine_dir)
    engine_id, engine_version, engine_variant = _engine_ids(
        variant, args.engine_dir
    )
    storage = get_storage()
    ctx = create_workflow_context(storage, use_mesh=not args.no_mesh)
    with contextlib.ExitStack() as stack:
        inp = (_sys.stdin if args.input == "-"
               else stack.enter_context(open(args.input)))
        out = (_sys.stdout if args.output == "-"
               else stack.enter_context(open(args.output, "w")))
        report = run_batch_predict(
            engine, ep, storage, inp, out,
            engine_id=engine_id, engine_version=engine_version,
            engine_variant=engine_variant,
            instance_id=args.engine_instance_id,
            batch_size=args.batch_size, ctx=ctx,
        )
    print(f"Batch predict done: {report.n_queries} queries"
          + (f", {report.n_errors} failed (malformed or engine-rejected; "
             "see the output's error records)" if report.n_errors else ""),
          file=_sys.stderr)
    return 0


def cmd_undeploy(args) -> int:
    """POST /stop to a running deploy server (reference Console.undeploy).
    Rides utils/httpclient like every other outbound call (the obs:
    raw-http contract — raw urllib would drop trace/deadline context).
    With --tenant: remove ONE tenant from a multi-tenant fleet (plan
    record + best-effort live detach) and leave the pool serving the
    rest."""
    from pio_tpu.utils.httpclient import JsonHttpClient

    key = args.server_key or os.environ.get("PIO_SERVER_KEY", "")
    if args.tenant:
        from pio_tpu.serving_fleet.tenancy import remove_tenant

        try:
            plan = remove_tenant(get_storage(), args.fleet, args.tenant)
        except ValueError as e:
            return _fail(str(e))
        print(f"Tenant {args.tenant} removed from fleet {plan.name!r} "
              f"({len(plan.tenants)} tenant(s) remain)")
        try:
            out = JsonHttpClient(f"http://{args.ip}:{args.port}",
                                 timeout=30).request(
                "POST", "/fleet/detach_tenant",
                {"tenant": args.tenant},
                params={"accessKey": key} if key else None)
            print(f"live detach: {json.dumps(out)}")
        except Exception as e:  # noqa: BLE001 - detach is best-effort
            print(f"no live router detached at "
                  f"http://{args.ip}:{args.port} ({e}); the plan "
                  f"record is updated")
        return 0
    try:
        out = JsonHttpClient(f"http://{args.ip}:{args.port}",
                             timeout=10).request(
            "POST", "/stop", params={"accessKey": key} if key else None)
        print(json.dumps(out) if out is not None else "")
        return 0
    except Exception as e:  # noqa: BLE001
        return _fail(f"undeploy failed: {e}")


def cmd_compilecache(args) -> int:
    """Inspect or clear the persistent XLA compile cache (the thing that
    makes the SECOND `pio train`/`pio deploy` skip cold-start XLA; see
    docs/performance.md). Shows the serving bucket registries too."""
    from pio_tpu.utils.compilecache import (
        cache_disabled, cache_stats, clear_cache, default_cache_dir,
    )

    d = args.dir or default_cache_dir()
    if args.clear:
        n = clear_cache(d)
        print(f"removed {n} file(s) from {d}")
        return 0
    stats = cache_stats(d)
    registries = sorted(
        f for f in (os.listdir(d) if os.path.isdir(d) else [])
        if f.startswith("buckets__") and f.endswith(".json")
    )
    if args.json:
        print(json.dumps({**stats, "disabled": cache_disabled(),
                          "bucket_registries": registries}))
        return 0
    state = "DISABLED (PIO_TPU_COMPILE_CACHE=off)" if cache_disabled() \
        else "enabled"
    print(f"compile cache: {state}")
    print(f"  dir:     {stats['dir']}")
    print(f"  entries: {stats['entries']}"
          f" ({stats['bytes'] / 1e6:.1f} MB)")
    for r in registries:
        with open(os.path.join(d, r), encoding="utf-8") as f:
            buckets = json.load(f).get("buckets", [])
        print(f"  buckets: {r[len('buckets__'):-len('.json')]} -> {buckets}")
    return 0


def cmd_start_all(args) -> int:
    from pio_tpu.tools.daemon import default_pid_dir, start_all

    if args.pid_dir is None:
        args.pid_dir = default_pid_dir()
    return start_all(args)


def cmd_stop_all(args) -> int:
    from pio_tpu.tools.daemon import default_pid_dir, stop_all

    if args.pid_dir is None:
        args.pid_dir = default_pid_dir()
    return stop_all(args)


def cmd_eventserver(args) -> int:
    from pio_tpu.server.eventserver import EventServerConfig, create_event_server

    srv = create_event_server(
        get_storage(),
        EventServerConfig(ip=args.ip, port=args.port, stats=args.stats,
                          metrics_key=args.metrics_key or "",
                          certfile=args.cert, keyfile=args.key,
                          backend=args.server_backend),
    )
    srv.start()  # bind first: with --port 0 the real port is only known now
    scheme = "https" if srv.tls else "http"
    print(f"Event Server on {scheme}://{args.ip}:{srv.port}")
    try:
        srv.wait()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_storageserver(args) -> int:
    """Serve this host's configured storage to other hosts (the networked
    shared store; reference analogue: pointing every host's PIO_STORAGE_*
    at one Postgres/HBase — here one host owns the store and the rest mount
    it with the `remote` backend)."""
    from pio_tpu.server.storageserver import (
        StorageServerConfig, create_storage_server,
    )

    srv = create_storage_server(
        get_storage(),
        StorageServerConfig(ip=args.ip, port=args.port,
                            server_key=args.server_key or "",
                            certfile=args.cert, keyfile=args.key),
    )
    scheme = "https" if srv.tls else "http"
    print(f"Storage Server on {scheme}://{args.ip}:{srv.port}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


def cmd_adminserver(args) -> int:
    from pio_tpu.tools.admin import create_admin_server

    srv = create_admin_server(get_storage(), ip=args.ip, port=args.port)
    print(f"Admin Server on http://{args.ip}:{srv.port}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


def cmd_dashboard(args) -> int:
    from pio_tpu.tools.dashboard import create_dashboard

    srv = create_dashboard(get_storage(), ip=args.ip, port=args.port)
    print(f"Dashboard on http://{args.ip}:{srv.port}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


def _io_format(explicit: str | None, path: str) -> str:
    if explicit:
        return explicit
    return "parquet" if path.endswith(".parquet") else "json"


def cmd_export(args) -> int:
    from pio_tpu.tools.export_import import export_events, export_events_parquet

    storage = get_storage()
    a = storage.get_metadata_apps().get(args.appid)
    if a is None:
        return _fail(f"App id {args.appid} does not exist.")
    channel_id = None
    if args.channel:
        ch = next((c for c in storage.get_metadata_channels()
                   .get_by_appid(a.id) if c.name == args.channel), None)
        if ch is None:
            return _fail(f"Channel {args.channel} does not exist.")
        channel_id = ch.id
    if _io_format(getattr(args, "format", None), args.output) == "parquet":
        n = export_events_parquet(
            storage, args.appid, args.output, channel_id=channel_id
        )
    else:
        with _open_text(args.output, "wt") as f:
            n = export_events(storage, args.appid, f, channel_id=channel_id)
    print(f"Exported {n} events to {args.output}")
    return 0


def _open_text(path: str, mode: str):
    """open() with transparent .gz (committed datasets ship gzipped)."""
    if path.endswith(".gz"):
        import gzip

        return gzip.open(path, mode, encoding="utf-8")
    return open(path, mode.rstrip("t"), encoding="utf-8")


def cmd_import(args) -> int:
    from pio_tpu.tools.export_import import import_events, import_events_parquet

    if _io_format(getattr(args, "format", None), args.input) == "parquet":
        ok, failed = import_events_parquet(get_storage(), args.appid, args.input)
    else:
        with _open_text(args.input, "rt") as f:
            ok, failed = import_events(get_storage(), args.appid, f)
    print(f"Imported {ok} events ({failed} failed).")
    return 0 if failed == 0 else 1


def cmd_upgrade(args) -> int:
    """Migrate events + app metadata between storage backends (the
    reference's `pio upgrade` generalized: any source -> any target)."""
    from pio_tpu.data.storage import Storage
    from pio_tpu.tools.migrate import migrate_events

    def load_env(path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    src = Storage(env=load_env(args.from_env))
    dst = Storage(env=load_env(args.to_env))
    try:
        report = migrate_events(
            src, dst,
            app_ids=[args.appid] if args.appid is not None else None,
            copy_metadata=not args.no_metadata,
        )
    finally:
        src.close()
        dst.close()
    print(report.one_liner())
    return 0


def cmd_lint(args) -> int:
    """Static trace-safety & concurrency analysis (pio_tpu/analysis/):
    the compile-time net the reference gets from Scala's type system.
    Exits 0 when no error/warning findings survive suppressions (INFO
    findings are advisory). `--deep` switches to the whole-program tier
    (lock-order cycles, blocking-under-lock, context-loss,
    route-contract drift) with its committed baseline. See docs/lint.md
    for both rule catalogues."""
    select = {s for s in (args.select or "").split(",") if s}
    ignore = {s for s in (args.ignore or "").split(",") if s}
    paths = args.paths
    if args.deep:
        from pio_tpu.analysis.deep import run_deep_lint

        # `pio lint --deep` from the repo root means the package, not
        # the tree of tests/fixtures around it
        if paths == ["."] and os.path.isdir("pio_tpu"):
            paths = ["pio_tpu"]
        report = run_deep_lint(
            paths, select=select or None, ignore=ignore or None,
            baseline_path=args.baseline,
            update_baseline=args.update_baseline,
            use_baseline=not args.no_baseline)
    else:
        from pio_tpu.analysis import run_lint

        report = run_lint(paths, select=select or None,
                          ignore=ignore or None)
    exit_code = report.exit_code
    if args.deep and args.max_seconds and report.elapsed_s > args.max_seconds:
        exit_code = exit_code or 1
    if args.format == "json":
        print(json.dumps({
            "findings": [f.to_dict() for f in report.findings],
            "baselined": [f.to_dict() for f in report.baselined],
            "suppressed": len(report.suppressed),
            "files": report.n_files,
            "elapsed_s": round(report.elapsed_s, 3),
            "deep": bool(args.deep),
        }, indent=2))
        return exit_code
    shown = [f for f in report.findings
             if args.show_info or f.severity.label() != "info"]
    for f in shown:
        print(f.format())
    print(report.summary())
    if args.deep:
        print(f"deep analysis took {report.elapsed_s:.2f}s"
              + (f" (budget {args.max_seconds:.0f}s"
                 + (" EXCEEDED)" if report.elapsed_s > args.max_seconds
                    else " ok)")
                 if args.max_seconds else ""))
    return exit_code


def cmd_template(args) -> int:
    """Scaffold a new engine directory from the template gallery
    (reference console/Template.scala). The built-in gallery is the local
    model zoo; `--gallery-url` (or PIO_TEMPLATE_GALLERY_URL) additionally
    lists/fetches an organization-hosted remote gallery."""
    from pio_tpu.tools.templates import (
        GALLERY_ENV, TEMPLATES, GalleryError, fetch_gallery, readme_for,
        scaffold_remote,
    )

    explicit_url = getattr(args, "gallery_url", None)
    gallery_url = explicit_url or os.environ.get(GALLERY_ENV)
    # a builtin scaffold must never need the network: fetch the remote
    # index only when the command actually involves it (list, or a non-
    # builtin name); an env-var-configured gallery that is down degrades
    # to a warning instead of blocking local work
    need_remote = gallery_url and (
        args.subcommand == "list"
        or (args.subcommand == "new" and args.template not in TEMPLATES)
    )
    remote = {}
    if need_remote:
        try:
            remote = fetch_gallery(gallery_url)
        except GalleryError as e:
            if explicit_url or args.subcommand == "new":
                return _fail(str(e))
            print(f"[WARN] {e} (continuing with the builtin gallery)",
                  file=sys.stderr)
        # builtin names are trusted: a remote entry cannot shadow one
        for clash in set(remote) & set(TEMPLATES):
            print(f"[WARN] remote template {clash!r} shadows a builtin "
                  "and is ignored", file=sys.stderr)
            del remote[clash]

    if args.subcommand == "list":
        for spec in TEMPLATES.values():
            print(f"{spec.name:16} {spec.description}")
        for rspec in remote.values():
            print(f"{rspec.name:16} {rspec.description} [remote]")
        return 0
    if args.subcommand != "new":
        return _fail("use 'template new <dir> [--template NAME]' or "
                     "'template list'")
    spec = TEMPLATES.get(args.template)
    if spec is None and args.template not in remote:
        choices = list(TEMPLATES) + list(remote)
        return _fail(
            f"unknown template {args.template!r}; "
            f"choose from: {', '.join(choices)}"
        )
    target = args.directory
    if os.path.exists(target) and (
        not os.path.isdir(target) or os.listdir(target)
    ):
        return _fail(f"{target} exists and is not an empty directory")
    os.makedirs(target, exist_ok=True)
    if spec is None:              # remote template
        try:
            scaffold_remote(remote[args.template], gallery_url, target)
        except GalleryError as e:
            return _fail(str(e))
        print(f"Engine template '{args.template}' (remote) created at "
              f"{target}")
        return 0
    name = os.path.basename(os.path.abspath(target))
    variant = dict(spec.engine_json, id=name)
    with open(os.path.join(target, "engine.json"), "w") as f:
        json.dump(variant, f, indent=2)
    if spec.engine_py is not None:
        with open(os.path.join(target, "engine.py"), "w") as f:
            f.write(spec.engine_py)
    for rel, content in spec.data_files.items():
        path = os.path.join(target, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(content)
    with open(os.path.join(target, "README.md"), "w") as f:
        f.write(readme_for(spec, name))
    print(f"Engine template '{spec.name}' created at {target}")
    return 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="pio-tpu command line interface"
    )
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("version").set_defaults(fn=cmd_version)
    x = sub.add_parser("status")
    x.add_argument("--pid-dir", default=None,
                   help="where start-all wrote pidfiles (default "
                        "$PIO_TPU_PID_DIR or ~/.pio_tpu/run)")
    x.set_defaults(fn=cmd_status)

    x = sub.add_parser(
        "doctor",
        help="poll every server surface's /healthz + /readyz: breaker "
             "states, shed queue depth, spill backlog, serving model",
    )
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--eventserver-port", type=int, default=7070)
    x.add_argument("--serving-port", type=int, default=8000)
    x.add_argument("--adminserver-port", type=int, default=7071)
    x.add_argument("--storageserver-port", type=int, default=7072)
    x.add_argument("--dashboard-port", type=int, default=9000)
    x.add_argument("--timeout", type=float, default=3.0)
    x.add_argument("--json", action="store_true")
    x.add_argument("--sweep-zombies", action="store_true",
                   help="transition INIT/TRAINING instances with stale "
                        "heartbeats to FAILED (resumable) instead of "
                        "just reporting them")
    x.add_argument("--zombie-stale-s", type=float, default=600.0,
                   help="heartbeat age (seconds) after which an "
                        "in-flight instance counts as a zombie")
    x.add_argument("--fleet", action="store_true",
                   help="inspect a sharded serving fleet via its router: "
                        "shard plan, per-replica health, replication "
                        "status, open breakers in one table")
    x.add_argument("--storage", action="store_true",
                   help="inspect the replicated event store (this "
                        "process's PIO_STORAGE_* config): per-replica "
                        "live/breaker/hint-depth/last-scrub + a live "
                        "convergence check; exit 1 on lost write quorum")
    x.add_argument("--scrub", action="store_true",
                   help="with --storage: repair divergent buckets during "
                        "the convergence pass instead of only reporting")
    x.add_argument("--router-url", default="",
                   help="fleet router base URL (default "
                        "http://<ip>:<serving-port>)")
    x.add_argument("--foldin-port", type=int, default=8100,
                   help="fold-in worker health port (the freshness row; "
                        "reported down when no folder is running)")
    x.add_argument("--staleness-budget", type=float, default=60.0,
                   help="fold-in staleness warn threshold (seconds) for "
                        "--fleet's per-group lag column")
    x.add_argument("--tenant", default="", metavar="KEY",
                   help="with --fleet against a multi-tenant router: "
                        "scope the exit code to this tenant — a page "
                        "about a noisy/broken co-tenant must not fail "
                        "a healthy tenant's check run")
    x.set_defaults(fn=cmd_doctor)

    x = sub.add_parser("run")
    x.add_argument("script")
    x.add_argument("args", nargs="*")
    x.set_defaults(fn=cmd_run)

    sub.add_parser("shell").set_defaults(fn=cmd_shell)

    pa = sub.add_parser("app")
    pas = pa.add_subparsers(dest="subcommand", required=True)
    x = pas.add_parser("new")
    x.add_argument("name")
    x.add_argument("--id", type=int, default=0)
    x.add_argument("--description")
    x.add_argument("--access-key", default="")
    pas.add_parser("list")
    x = pas.add_parser("show")
    x.add_argument("name")
    x = pas.add_parser("delete")
    x.add_argument("name")
    x = pas.add_parser(
        "trim", help="copy a time window of events into an EMPTY "
        "destination app (reference experimental trim-app)")
    x.add_argument("name")
    x.add_argument("dst")
    x.add_argument("--start", default="", help="ISO-8601 inclusive start")
    x.add_argument("--until", default="", help="ISO-8601 exclusive end")
    x.add_argument("--channel", default="",
                   help="copy only this named channel (all namespaces — "
                        "default + every channel — are copied otherwise)")
    x.set_defaults(fn=cmd_app, subcommand="trim")

    x = pas.add_parser(
        "cleanup", help="delete events OLDER than --until in place "
        "(reference experimental cleanup-app)")
    x.add_argument("name")
    x.add_argument("--until", required=True,
                   help="ISO-8601 exclusive cutoff: events before it go")
    x.add_argument("--channel", default="",
                   help="clean only this channel (all namespaces otherwise)")
    x.set_defaults(fn=cmd_app, subcommand="cleanup")

    x = pas.add_parser("data-delete")
    x.add_argument("name")
    x.add_argument("--channel")
    x = pas.add_parser("channel-new")
    x.add_argument("name")
    x.add_argument("channel")
    x = pas.add_parser("channel-delete")
    x.add_argument("name")
    x.add_argument("channel")
    pa.set_defaults(fn=cmd_app)

    pk = sub.add_parser("accesskey")
    pks = pk.add_subparsers(dest="subcommand", required=True)
    x = pks.add_parser("new")
    x.add_argument("app_name")
    x.add_argument("--event", action="append")
    x = pks.add_parser("list")
    x.add_argument("app_name", nargs="?")
    x = pks.add_parser("delete")
    x.add_argument("key")
    pk.set_defaults(fn=cmd_accesskey)

    def engine_dir_arg(q):
        q.add_argument("--engine-dir", default=".")

    x = sub.add_parser("build")
    engine_dir_arg(x)
    x.set_defaults(fn=cmd_build)

    x = sub.add_parser("train")
    engine_dir_arg(x)
    x.add_argument("--batch", default="")
    x.add_argument("--no-mesh", action="store_true")
    x.add_argument("--stop-after-read", action="store_true")
    x.add_argument("--stop-after-prepare", action="store_true")
    x.add_argument("--resume", default="", metavar="INSTANCE_ID",
                   help="resume an INTERRUPTED/FAILED engine instance "
                        "from its step checkpoints")
    x.add_argument("--auto-resume", action="store_true",
                   help="resume the most recent resumable instance of "
                        "this engine (fresh run when none has "
                        "checkpoints)")
    x.add_argument("--checkpoint-root", default="",
                   help="root for per-instance step-checkpoint dirs "
                        "(default $PIO_TPU_CKPT_ROOT or "
                        "$PIO_TPU_HOME/checkpoints)")
    x.add_argument("--from-eval", default="", metavar="EVAL_ID|latest",
                   help="train with the winning algorithm params a "
                        "`pio eval --sweep` persisted (the "
                        "<eval-iid>:best_params record); the instance "
                        "is batch-tagged from-eval:<id> so doctor can "
                        "tell production runs the best-known params")
    x.add_argument("--device-profile", default="", metavar="DIR",
                   help="take a jax device profile of the whole train "
                        "into DIR; `python -m pio_tpu.obs.profile DIR` "
                        "reads it as device seconds by scope and idle "
                        "seconds by span (docs/observability.md)")
    x.set_defaults(fn=cmd_train)

    x = sub.add_parser("eval")
    x.add_argument("evaluation_class", nargs="?", default="")
    x.add_argument("params_generator_class", nargs="?", default="")
    x.add_argument("--engine-dir", default=None,
                   help="directory holding the user-code engine.py the "
                        "classes live in (joins sys.path); with --sweep "
                        "also where engine.json lives")
    x.add_argument("--output", default="best.json")
    x.add_argument("--workers", type=int, default=1,
                   help="params-grid parallelism (reference runs .par)")
    x.add_argument("--sweep", action="store_true",
                   help="batched hyperparameter sweep over engine.json's "
                        "engine (docs/evaluation.md): shape-compatible "
                        "candidates train as ONE stacked device "
                        "program; per-fold results persist durably and "
                        "the winner lands in <eval-iid>:best_params "
                        "for `pio train/deploy --from-eval`")
    x.add_argument("--grid", default="",
                   help="with --sweep: JSON object (or @file.json) of "
                        "algorithm-param name -> list of values; the "
                        "cartesian product is the candidate grid, e.g. "
                        "'{\"lambda_\": [0.01, 0.1], \"rank\": [8, 16]}'")
    x.add_argument("--params-generator", default="",
                   help="with --sweep: EngineParamsGenerator class path "
                        "instead of --grid (full EngineParams control)")
    x.add_argument("--metric", default="map@10",
                   help="primary metric: map@K, ndcg@K, precision@K, "
                        "or auc (batched path only)")
    x.add_argument("--other-metrics", default="",
                   help="comma-separated supplementary metric columns")
    x.add_argument("--split", choices=["kfold", "time"], default="kfold",
                   help="kfold: seeded balanced folds over deduped "
                        "interactions; time: event-time rolling splits "
                        "(train on the past, test on the next window)")
    x.add_argument("--folds", type=int, default=3)
    x.add_argument("--seed", type=int, default=42,
                   help="kfold assignment seed (bit-reproducible)")
    x.add_argument("--resume-eval", default="", metavar="EVAL_ID",
                   help="resume a killed/failed sweep: completed folds "
                        "are read from the durable record, only the "
                        "remaining units run (result identical to an "
                        "uninterrupted sweep)")
    x.add_argument("--batch", default="",
                   help="batch label recorded on the EvaluationInstance")
    x.add_argument("--no-mesh", action="store_true")
    x.add_argument("--metrics-port", type=int, default=None,
                   help="with --sweep: serve /healthz /metrics "
                        "/debug/traces.json during the sweep (0 = "
                        "ephemeral port) so `pio top`/`pio trace` cover "
                        "it like every other surface")
    x.add_argument("--ip", default="127.0.0.1",
                   help="bind address for --metrics-port")
    x.add_argument("--server-key", default="",
                   help="guards the sweep's /debug trace routes")
    x.set_defaults(fn=cmd_eval)

    x = sub.add_parser("deploy")
    engine_dir_arg(x)
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--engine-instance-id")
    x.add_argument("--feedback", action="store_true")
    x.add_argument("--feedback-app")
    x.add_argument("--server-key")
    x.add_argument("--warm-query")
    x.add_argument("--no-mesh", action="store_true")
    x.add_argument("--cert", help="TLS certificate (PEM) -> serve HTTPS")
    x.add_argument("--key", help="TLS private key (PEM)")
    x.add_argument("--server-backend", choices=["async", "threaded"],
                   default="async")
    x.add_argument("--batch-window-ms", type=float, default=0.0,
                   help="micro-batching: > 0 coalesces concurrent queries "
                        "within this fixed window (ms); < 0 = adaptive "
                        "continuous batching (no added wait; batch = "
                        "whatever queued during the previous execution); "
                        "0 = off")
    x.add_argument("--coalesce-window-ms", type=float, default=0.0,
                   help="continuous batching: > 0 admits queries through "
                        "a coalescing stage that merges concurrent "
                        "requests into one device dispatch (single-host) "
                        "or one batched shard RPC per group (fleet); "
                        "~2 ms is the recommended starting window. "
                        "Deadline-doomed requests dispatch solo or shed "
                        "503. 0 = off")
    x.add_argument("--shards", type=int, default=0,
                   help="> 0 deploys a SHARDED fleet: partition the "
                        "model's factor tables across this many shard "
                        "servers behind a top-k-merging router "
                        "(docs/serving.md); 0 = single-host serve")
    x.add_argument("--replicas", type=int, default=2,
                   help="replicas per shard (fleet mode; >= 2 gives warm "
                        "failover)")
    x.add_argument("--shard-memory-budget-mb", type=int, default=0,
                   help="hard cap (MB) each shard may hold; a partition "
                        "over budget fails deploy instead of lying about "
                        "capacity. 0 = unlimited")
    x.add_argument("--canary", default="", metavar="PCT|auto",
                   help="guarded rollout: tell the RUNNING serving "
                        "process at --ip/--port to stage the latest "
                        "eligible instance (or --engine-instance-id) as "
                        "a canary at PCT percent of traffic, or 'auto' "
                        "to ramp 1->5->25->100 while live guards stay "
                        "green (docs/serving.md). Conclude with `pio "
                        "promote` / `pio rollback`")
    x.add_argument("--canary-min-stage-seconds", type=float, default=None,
                   help="with --canary auto: minimum seconds per stage")
    x.add_argument("--canary-min-stage-samples", type=int, default=None,
                   help="with --canary auto: minimum candidate-arm "
                        "requests per stage")
    x.add_argument("--from-eval", default="", metavar="EVAL_ID|latest",
                   help="serve with the winning algorithm params a "
                        "`pio eval --sweep` persisted (single-host "
                        "mode; pair with `pio train --from-eval` so "
                        "the served instance was trained with them)")
    x.add_argument("--fleet", default="", metavar="NAME",
                   help="boot a MULTI-TENANT pool from the named "
                        "recorded FleetPlan (tenant-mux shard hosts + "
                        "multi-tenant router; no engine dir needed) — "
                        "join tenants first with --fleet-join "
                        "(docs/serving.md \"Multi-tenant fleet\")")
    x.add_argument("--fleet-join", default="", metavar="NAME",
                   help="bin-pack THIS engine's partitions into the "
                        "named fleet's remaining capacity (resident "
                        "tenants never move), record the placement, "
                        "and live-attach to a running router at "
                        "--ip/--port when one answers; pool shape for "
                        "a NEW fleet comes from --shards/--replicas/"
                        "--shard-memory-budget-mb")
    x.add_argument("--tenant-quota-qps", type=float, default=0.0,
                   help="with --fleet-join: this tenant's admitted "
                        "query rate; floods past it answer per-tenant "
                        "429 + Retry-After while co-tenants keep their "
                        "p99. 0 = unlimited")
    x.add_argument("--tenant-quota-burst", type=float, default=0.0,
                   help="with --fleet-join: token-bucket burst "
                        "capacity; 0 = max(rate, 1)")
    x.add_argument("--tenant-weight", type=float, default=1.0,
                   help="with --fleet-join: weighted-fair share under "
                        "admission pressure")
    x.add_argument("--tenant-max-concurrency", type=int, default=0,
                   help="with --fleet-join: cap on this tenant's "
                        "in-flight queries; 0 = unlimited")
    x.set_defaults(fn=cmd_deploy)

    for verb, fn, descr in (
        ("promote", cmd_promote,
         "conclude a green canary: candidate becomes the active "
         "instance at 100% (verdict persisted; survives restart)"),
        ("rollback", cmd_rollback,
         "instant rollback: revert 100% of traffic to the last-good "
         "instance and persist ROLLED_BACK (reloads never auto-advance "
         "onto it again)"),
    ):
        x = sub.add_parser(verb, help=descr)
        x.add_argument("--ip", default="127.0.0.1")
        x.add_argument("--port", type=int, default=8000,
                       help="serving server or fleet router port")
        x.add_argument("--server-key")
        if verb == "rollback":
            x.add_argument("--reason", default="",
                           help="recorded on the rollout verdict")
        x.set_defaults(fn=fn)

    x = sub.add_parser(
        "reshard",
        help="live elastic resharding: grow/shrink the RUNNING fleet "
             "to --shards N' with zero downtime (streams moved "
             "partitions, double-routes during the move, flips the "
             "plan atomically; docs/serving.md)")
    x.add_argument("--shards", type=int, default=None, metavar="N",
                   help="target shard-group count (1..32 virtual "
                        "partitions bound the range)")
    x.add_argument("--endpoint", action="append", default=None,
                   metavar="URL[,URL...]",
                   help="one NEW shard group per flag (repeatable), "
                        "commas separating its replica URLs — required "
                        "when growing past the groups the router "
                        "already knows")
    x.add_argument("--status", action="store_true",
                   help="report the in-flight (or last) migration and "
                        "exit")
    x.add_argument("--abort", action="store_true",
                   help="abort the in-flight migration: the old plan "
                        "was never touched, serving reverts "
                        "bit-identical")
    x.add_argument("--no-wait", action="store_true",
                   help="start the migration and return immediately "
                        "instead of following progress")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000,
                   help="fleet router port")
    x.add_argument("--server-key")
    x.add_argument("--timeout", type=float, default=30.0)
    x.set_defaults(fn=cmd_reshard)

    def obs_args(q):
        q.add_argument("--url", action="append", default=None,
                       help="surface base URL to poll (repeatable: "
                            "serving, event server, storage server, "
                            "folder, shard)")
        q.add_argument("--router-url", default="",
                       help="fleet router base URL; its /fleet.json "
                            "auto-discovers every shard replica")
        q.add_argument("--port", type=int, default=8000,
                       help="default single-host serving port when no "
                            "--url/--router-url is given")
        q.add_argument("--server-key", default="",
                       help="accessKey for the /debug trace routes")
        q.add_argument("--timeout", type=float, default=5.0)
        q.add_argument("--json", action="store_true")

    x = sub.add_parser(
        "trace",
        help="print one request's merged span tree (router + shards + "
             "serving/storage/folder) with per-hop self-time",
    )
    x.add_argument("trace_id", help="32-hex trace id (from the "
                                    "X-Pio-Trace-Id echo header, "
                                    "/metrics.json exemplars, or "
                                    "/debug/traces.json)")
    obs_args(x)
    x.set_defaults(fn=cmd_trace)

    x = sub.add_parser(
        "top",
        help="live span table across surfaces: rate/p50/p99/error% per "
             "span, per arm",
    )
    obs_args(x)
    x.add_argument("--watch", type=float, default=0.0,
                   help="refresh every N seconds (0 = print once)")
    x.set_defaults(fn=cmd_top)

    x = sub.add_parser(
        "foldin",
        help="streaming fold-in worker: tail the event stream, solve "
             "refreshed user rows against the deployed item factors, "
             "hot-swap them into serving (docs/freshness.md)")
    engine_dir_arg(x)
    x.add_argument("--serving-url", default="http://127.0.0.1:8000",
                   help="single-host deploy server to apply rows to")
    x.add_argument("--router-url", default="",
                   help="fleet router base URL — apply rows through the "
                        "sharded fleet instead of --serving-url")
    x.add_argument("--event-server-url", default="",
                   help="tail a remote event server's GET /tail/events.json"
                        " (default: read the event store directly)")
    x.add_argument("--access-key", default="",
                   help="event-server app access key "
                        "(with --event-server-url)")
    x.add_argument("--server-key", default="",
                   help="serving/router server key (or PIO_SERVER_KEY)")
    x.add_argument("--state-path", default="",
                   help="durable cursor file (default $PIO_TPU_HOME/foldin/"
                        "<engine>-<variant>.cursor)")
    x.add_argument("--replay", action="store_true",
                   help="a FRESH cursor replays the whole event log "
                        "(re-fold every historical user) instead of "
                        "starting at now")
    x.add_argument("--interval", type=float, default=0.5,
                   help="tail poll interval (seconds)")
    x.add_argument("--tail-wait", type=float, default=10.0,
                   help="with --event-server-url: long-poll push "
                        "subscription — an idle tail blocks server-side "
                        "this many seconds for new events before "
                        "answering (0 = plain polling; pre-long-poll "
                        "servers degrade to polling automatically)")
    x.add_argument("--max-batch-users", type=int, default=1024,
                   help="fold batch cap per cycle")
    x.add_argument("--staleness-budget", type=float, default=60.0,
                   help="the folder's /readyz flips once event->servable "
                        "staleness exceeds this many seconds")
    x.add_argument("--once", action="store_true",
                   help="run exactly one tail->solve->apply cycle, print "
                        "its stats as JSON, and exit (cron-style fold-in)")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8100,
                   help="health port (/healthz /readyz /metrics.json)")
    x.set_defaults(fn=cmd_foldin)

    x = sub.add_parser(
        "batchpredict",
        help="offline bulk scoring: JSON-lines queries in, "
             "{query, prediction} JSON-lines out (0.13-era verb; device "
             "batches amortize the per-query dispatch)")
    engine_dir_arg(x)
    x.add_argument("--input", required=True,
                   help="queries file, one JSON object per line "
                        "('-' = stdin)")
    x.add_argument("--output", required=True,
                   help="predictions file ('-' = stdout)")
    x.add_argument("--engine-instance-id")
    x.add_argument("--batch-size", type=int, default=256,
                   help="queries per device batch")
    x.add_argument("--no-mesh", action="store_true")
    x.set_defaults(fn=cmd_batchpredict)

    x = sub.add_parser("undeploy")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--server-key")
    x.add_argument("--tenant", default="", metavar="KEY",
                   help="remove ONE tenant (engine triple key, e.g. "
                        "rec/1/default) from a multi-tenant fleet: "
                        "plan record + best-effort live detach at "
                        "--ip/--port; the pool keeps serving the rest")
    x.add_argument("--fleet", default="default", metavar="NAME",
                   help="with --tenant: the fleet plan to update")
    x.set_defaults(fn=cmd_undeploy)

    x = sub.add_parser("eventserver")
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=7070)
    x.add_argument("--stats", action="store_true")
    x.add_argument("--metrics-key",
                   help="with --stats: enable GET /metrics (Prometheus "
                        "ingest counters, cross-app) guarded by this key")
    x.add_argument("--cert", help="TLS certificate (PEM) -> serve HTTPS")
    x.add_argument("--key", help="TLS private key (PEM)")
    x.add_argument("--server-backend", choices=["async", "threaded"],
                   default="async")
    x.set_defaults(fn=cmd_eventserver)

    x = sub.add_parser("start-all", help="daemon-start the full stack "
                       "(reference bin/pio-start-all)")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--eventserver-port", type=int, default=7070)
    x.add_argument("--adminserver-port", type=int, default=7071)
    x.add_argument("--dashboard-port", type=int, default=9000)
    x.add_argument("--with-storageserver", action="store_true")
    x.add_argument("--storageserver-port", type=int, default=7072)
    x.add_argument("--server-key",
                   help="storage-server shared secret (required for a "
                        "non-loopback --ip)")
    x.add_argument("--pid-dir", default=None)
    x.set_defaults(fn=cmd_start_all)

    x = sub.add_parser("stop-all", help="stop everything start-all started "
                       "(reference bin/pio-stop-all)")
    x.add_argument("--pid-dir", default=None)
    x.set_defaults(fn=cmd_stop_all)

    x = sub.add_parser("storageserver")
    # loopback default: a non-loopback bind requires --server-key (the RPC
    # surface includes access keys and model blobs)
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=7072)
    x.add_argument("--server-key", help="shared secret required on every call")
    x.add_argument("--cert", help="TLS certificate (PEM) -> serve HTTPS")
    x.add_argument("--key", help="TLS private key (PEM)")
    x.set_defaults(fn=cmd_storageserver)

    x = sub.add_parser("adminserver")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=7071)
    x.set_defaults(fn=cmd_adminserver)

    x = sub.add_parser("dashboard")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=9000)
    x.set_defaults(fn=cmd_dashboard)

    x = sub.add_parser("export")
    x.add_argument("--appid", type=int, required=True)
    x.add_argument("--output", required=True)
    x.add_argument("--channel")
    x.add_argument("--format", choices=["json", "parquet"],
                   help="default: by --output extension (.parquet), else json")
    x.set_defaults(fn=cmd_export)

    x = sub.add_parser("import")
    x.add_argument("--appid", type=int, required=True)
    x.add_argument("--input", required=True)
    x.add_argument("--format", choices=["json", "parquet"],
                   help="default: by --input extension (.parquet), else json")
    x.set_defaults(fn=cmd_import)

    x = sub.add_parser("upgrade")
    x.add_argument("--from-env", required=True,
                   help="JSON file of PIO_STORAGE_* vars for the source")
    x.add_argument("--to-env", required=True,
                   help="JSON file of PIO_STORAGE_* vars for the target")
    x.add_argument("--appid", type=int)
    x.add_argument("--no-metadata", action="store_true")
    x.set_defaults(fn=cmd_upgrade)

    x = sub.add_parser(
        "compilecache",
        help="persistent XLA compile cache: show size/location, prune, "
             "or clear (docs/performance.md)")
    x.add_argument("--dir", default=None,
                   help="cache directory (default "
                        "$JAX_COMPILATION_CACHE_DIR, else .jax_cache in "
                        "the checkout)")
    x.add_argument("--clear", action="store_true",
                   help="delete every cached executable and bucket "
                        "registry (next train/deploy recompiles)")
    x.add_argument("--json", action="store_true")
    x.set_defaults(fn=cmd_compilecache)

    x = sub.add_parser(
        "lint",
        help="static trace-safety/concurrency analysis (docs/lint.md)")
    x.add_argument("paths", nargs="*", default=["."],
                   help="files or directories to lint (default: .)")
    x.add_argument("--format", choices=["text", "json"], default="text")
    x.add_argument("--select", default="",
                   help="comma-separated rule-id prefixes to run "
                        "(e.g. trace,bench)")
    x.add_argument("--ignore", default="",
                   help="comma-separated rule-id prefixes to skip")
    x.add_argument("--show-info", action="store_true",
                   help="print INFO-level (advisory) findings too")
    x.add_argument("--deep", action="store_true",
                   help="whole-program tier: lock-order cycles, "
                        "blocking-under-lock, context-loss, "
                        "route-contract drift (docs/lint.md)")
    x.add_argument("--baseline", default=None,
                   help="baseline JSON for --deep (default: the "
                        "committed pio_tpu/analysis/deep_baseline.json)")
    x.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to accept every current "
                        "deep finding (ratchet after review)")
    x.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline and report everything "
                        "(the CI self-check mode)")
    x.add_argument("--max-seconds", type=float, default=0.0,
                   help="fail if the deep analysis wall-clock exceeds "
                        "this budget (CI uses 30)")
    x.set_defaults(fn=cmd_lint)

    x = sub.add_parser("template")
    xs = x.add_subparsers(dest="subcommand", required=True)
    t = xs.add_parser("new")
    t.add_argument("directory")
    t.add_argument("--template", default="custom",
                   help="engine shape (see `pio template list`)")
    t.add_argument("--gallery-url",
                   help="remote gallery base URL (or "
                        "PIO_TEMPLATE_GALLERY_URL)")
    t.set_defaults(fn=cmd_template)
    t = xs.add_parser("list")
    t.add_argument("--gallery-url",
                   help="remote gallery base URL (or "
                        "PIO_TEMPLATE_GALLERY_URL)")
    t.set_defaults(fn=cmd_template)
    x.set_defaults(fn=cmd_template)

    return p


def main(argv: list[str] | None = None) -> int:
    # Platform override for CPU-only hosts / CI (JAX_PLATFORMS works too;
    # this one also holds when jax was imported before the CLI ran).
    platform = os.environ.get("PIO_TPU_PLATFORM")
    n_cpu = os.environ.get("PIO_TPU_CPU_DEVICES")
    if platform or n_cpu:
        import jax

        if platform:
            jax.config.update("jax_platforms", platform)
        if n_cpu:
            try:
                jax.config.update("jax_num_cpu_devices", int(n_cpu))
            except ValueError:
                return _fail(f"PIO_TPU_CPU_DEVICES={n_cpu!r} is not an int")
    # engine dirs put engine.py on the path (factory "engine.MyEngine")
    if "" not in sys.path and "." not in sys.path:
        sys.path.insert(0, os.getcwd())
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except FileNotFoundError as e:
        return _fail(str(e))
    except (ValueError, KeyError) as e:
        return _fail(f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    # a pio process says on stderr what it runs on and what it did
    # (devices, compile cache, stage timings, instance ids)
    import logging

    _handler = logging.StreamHandler()
    _handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s"))
    logging.getLogger("pio_tpu").addHandler(_handler)
    logging.getLogger("pio_tpu").setLevel(logging.INFO)
    sys.exit(main())
