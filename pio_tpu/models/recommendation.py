"""Recommendation engine template — ALS collaborative filtering.

Parity target: reference examples/scala-parallel-recommendation/* (DataSource
reads rate/buy events, MLlib ALS.trainImplicit/train, query {"user", "num"}
-> {"itemScores": [...]}; custom-query variant adds item whitelist filtering,
ALSAlgorithm.scala:56-67, ALSModel.scala:18-47). TPU-native: the ALS kernel
is pio_tpu.ops.als (batched normal equations on the MXU, sharded over the
mesh); the model keeps factors as jax arrays resident in HBM for serving.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from pio_tpu.controller.base import (
    DataSource,
    FirstServing,
    IdentityPreparator,
    PAlgorithm,
    Params,
)
from pio_tpu.controller.engine import Engine, EngineFactory
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.data.eventstore import Interactions
from pio_tpu.ops import als

log = logging.getLogger("pio_tpu.workflow")


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    channel_name: str | None = None
    event_names: tuple[str, ...] = ("rate", "buy")
    rating_event: str = "rate"      # events carrying an explicit rating
    implicit_value: float = 4.0     # value assigned to non-rating events
    eval_k: int = 0                 # >0 -> read_eval produces k folds
    eval_num: int = 10              # ranking depth of each fold query
    # fold queries blacklist the user's train-fold items (unseen-item
    # evaluation; see e2.crossvalidation.split_interactions)
    eval_exclude_seen: bool = True


class RecommendationDataSource(DataSource):
    """Reads rate/buy events into Interactions (reference
    custom-query/src/main/scala/DataSource.scala behavior: `rate` events use
    properties.rating, `buy` maps to a fixed implicit value)."""

    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def _read(self, ctx) -> Interactions:
        p = self.params
        # EventStore.interactions: one native C++ sweep on the eventlog
        # backend, find + to_interactions on the others — same semantics
        # (rate events carry properties.rating, everything else maps to the
        # fixed implicit value).
        return ctx.event_store.interactions(
            app_name=p.app_name,
            channel_name=p.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(p.event_names),
            value_key="rating",
            default_value=p.implicit_value,
            value_event=p.rating_event,
            dedup="last",
        )

    def read_training(self, ctx) -> Interactions:
        return self._read(ctx)

    def read_eval(self, ctx):
        """Index-mod-k folds (reference e2 CrossValidation.splitData)."""
        from pio_tpu.e2.crossvalidation import split_interactions

        data = self._read(ctx)
        return split_interactions(
            data, self.params.eval_k, num=self.params.eval_num,
            exclude_seen=self.params.eval_exclude_seen,
        )


def _rank_candidates(cand: list, scores, num: int) -> dict:
    """Candidate ids + their scores -> top-`num` PredictedResult shape
    (shared by the single-query and batched whitelist paths so their
    ranking semantics cannot drift)."""
    order = np.argsort(-np.asarray(scores))[:num]
    return {
        "itemScores": [
            {"item": cand[i], "score": float(scores[i])} for i in order
        ]
    }


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    implicit_prefs: bool = False
    seed: int | None = None
    chunk: int = 65536
    # inner-solver knobs (ops/als.py): cg_iters -1 = auto per side;
    # warm-sweep schedule drops to cg_warm_iters after cg_warm_sweeps
    # full-strength sweeps — -1 disables
    cg_iters: int = -1
    # 6 = the ops-layer ALSParams default, so the engine path runs the
    # exact schedule the tuning grid (eval/CG_WARM_QUALITY.json) and the
    # benchmark's ALS cells run; override per-engine in engine.json
    cg_warm_iters: int = 6
    cg_warm_sweeps: int = 2
    # > 0: hold out this fraction of interactions, score heldout RMSE
    # after every sweep inside the training scan, and keep the BEST
    # sweep's factors instead of the last (ops/als.py ALSValidation —
    # measured on ML-20M the final sweep is ~4.6% worse than the curve
    # minimum). 0 disables (exact reference behavior: last sweep wins).
    validation_fraction: float = 0.0
    # two-stage retrieval (ops/retrieval.py; docs/serving.md): the
    # engine.json `retrieval` block. None/absent = exact mode — every
    # query rides the oracle einsum exactly as before. {"mode":
    # "clustered", ...} serves top-k via the quantized candidate scan +
    # exact re-rank; whiteList queries always stay on predict_pairs.
    retrieval: dict | None = None


@jax.tree_util.register_pytree_node_class
@dataclass
class RecommendationModel:
    """ALS factors + id indexes (reference ALSModel.scala:18-47).

    `validation` (aux, optional): the ALSValidation trajectory when the
    algorithm trained with validation_fraction > 0 — surfaces the
    per-sweep heldout curve + chosen sweep to eval artifacts and the
    dashboard."""

    factors: als.ALSModel
    users: EntityIdIndex
    items: EntityIdIndex
    validation: als.ALSValidation | None = None

    def tree_flatten(self):
        return (self.factors,), (self.users, self.items, self.validation)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


class ALSAlgorithm(PAlgorithm):
    """Reference ALSAlgorithm.scala:56-67 (MLlib ALS.trainImplicit) — TPU
    re-design in ops/als.py. Device model: factors live in HBM."""

    params_class = ALSAlgorithmParams

    def __init__(self, params: ALSAlgorithmParams):
        self.params = params
        # parse the retrieval block NOW so a typo'd knob fails engine
        # construction (deploy/train time), never silently serves exact
        from pio_tpu.ops.retrieval import RetrievalParams

        self._rparams = RetrievalParams.from_config(params.retrieval)

    def _retrieval_index(self, model: RecommendationModel):
        """The (RetrievalIndex, DeviceRetrievalIndex) pair for this
        model's CURRENT item factors, cached on the model object (a
        plain attribute — pytree aux ignores it) and keyed by item-table
        identity, so a fold-in swap that replaces the factors rebuilds
        the sidecar while the hot path pays the k-means exactly once.
        The fold-in applier updates the cache in the SAME swap
        (workflow/serve.py), so this rebuild is the cold-start/fallback
        path, not the freshness contract."""
        from pio_tpu.ops import retrieval as rt

        itf = model.factors.item_factors
        cached = getattr(model, "_retrieval_cache", None)
        if cached is not None and cached[0] is itf:
            return cached[1]
        idx = rt.build_index(np.asarray(itf), self._rparams)
        pair = (idx, rt.build_device_index(idx))
        model._retrieval_cache = (itf, pair)
        return pair

    def _als_params(self) -> als.ALSParams:
        p = self.params
        return als.ALSParams(
            rank=p.rank,
            iterations=p.num_iterations,
            reg=p.lambda_,
            alpha=p.alpha,
            implicit=p.implicit_prefs,
            seed=p.seed if p.seed is not None else 3,
            chunk=p.chunk,
            cg_iters=p.cg_iters,
            cg_warm_iters=p.cg_warm_iters,
            cg_warm_sweeps=p.cg_warm_sweeps,
        )

    def train(self, ctx, data: Interactions) -> RecommendationModel:
        data.sanity_check()
        ap = self._als_params()
        vf = self.params.validation_fraction
        sharded = ctx.mesh is not None and ctx.mesh.devices.size > 1
        log.info(
            "ALS train: %d users x %d items, %d ratings, rank %d, %d "
            "sweeps, %s; accum=%s",
            data.n_users, data.n_items, len(data.values), ap.rank,
            ap.iterations,
            f"sharded over {ctx.mesh.devices.size} devices" if sharded
            else "one device",
            ap.resolved_accum())
        if sharded:
            # sharded path: best-sweep selection not yet threaded through
            # shard_map (the curve would need a psum'd heldout metric);
            # last-sweep factors, as the reference always does
            factors = als.als_train_sharded(
                data.user_idx, data.item_idx, data.values,
                data.n_users, data.n_items, ap, ctx.mesh,
            )
            return RecommendationModel(factors, data.users, data.items)
        if vf > 0.0:
            nnz = len(data.values)
            n_val = max(1, int(nnz * vf))
            if nnz < 10:
                raise ValueError(
                    "validation_fraction needs >=10 interactions")
            rng = np.random.default_rng(ap.seed)
            perm = rng.permutation(nnz)
            va, tr = perm[:n_val], perm[n_val:]
            factors, validation = als.als_train_validated(
                data.user_idx[tr], data.item_idx[tr], data.values[tr],
                data.n_users, data.n_items, ap,
                data.user_idx[va], data.item_idx[va], data.values[va],
            )
            return RecommendationModel(
                factors, data.users, data.items, validation)
        factors = als.als_train(
            data.user_idx, data.item_idx, data.values,
            data.n_users, data.n_items, ap,
        )
        return RecommendationModel(factors, data.users, data.items)

    def predict(self, model: RecommendationModel, query: dict) -> dict:
        """query {"user": id, "num": k, "whiteList"?: [...], "blackList"?: [...]}
        -> {"itemScores": [{"item": id, "score": s}]} (reference Serving.scala
        PredictedResult shape; whitelist per custom-query variant)."""
        user = query["user"]
        num = int(query.get("num", 10))
        if user not in model.users:
            return {"itemScores": []}
        uidx = model.users.index_of(user)
        white = query.get("whiteList")
        black = set(query.get("blackList") or ())
        if white:
            # score the whitelist candidates directly (reference custom-query
            # variant restricts scoring to the candidate set, so a small
            # whitelist still fills `num` slots)
            cand = [i for i in white if i in model.items and i not in black]
            if not cand:
                return {"itemScores": []}
            cidx = model.items.encode(cand)
            scores = np.asarray(
                als.predict_pairs(
                    model.factors,
                    np.full(len(cidx), uidx, dtype=np.int32),
                    cidx,
                )
            )
            return _rank_candidates(cand, scores, num)
        n_items = model.factors.item_factors.shape[0]
        k = min(num + len(black), n_items)
        rp = self._rparams
        if rp.mode == "clustered" and not rp.is_exhaustive(n_items):
            # two-stage tier: quantized clustered scan picks candidates,
            # the exact oracle einsum re-scores them (ops/retrieval.py).
            # Exhaustive knobs (nprobe >= n_clusters) take the oracle
            # branch below instead — bit-parity by running the literal
            # same computation, the module's exactness contract.
            from pio_tpu.ops import retrieval as rt

            _, didx = self._retrieval_index(model)
            urow = np.asarray(model.factors.user_factors)[uidx]
            scores, idx = rt.candidate_topk(
                didx, model.factors.item_factors, urow, k)
            scores, idx = scores[0], idx[0]
            keep = idx >= 0   # fewer real survivors than k: drop pads
            scores, idx = scores[keep], idx[keep]
        else:
            scores, idx = als.recommend_topk(
                model.factors, np.array([uidx]), k
            )
            scores = np.asarray(scores)[0]
            idx = np.asarray(idx)[0]
        item_ids = model.items.decode(idx)
        out = []
        for item, score in zip(item_ids, scores):
            if item in black:
                continue
            out.append({"item": item, "score": float(score)})
            if len(out) >= num:
                break
        return {"itemScores": out}

    def batch_predict(self, model: RecommendationModel, queries) -> list:
        """Vectorized batch scoring (evaluation + the serving micro-batcher):
        ONE top-k matmul for all known-user queries — blackList queries
        included (over-fetch k = num + max blacklist, filter per row on
        host; unseen-item evaluation blacklists on every query, so routing
        them to the single-query path would collapse the batch API into
        thousands of single-row dispatches). whiteList queries batch too:
        their ragged candidate sets flatten into ONE predict_pairs call
        (user index repeated per candidate), ranked per query on host."""
        results: list[dict] = [{"itemScores": []} for _ in queries]
        known = []
        white_q = []   # (query_index, uidx, [candidate ids])
        for i, q in enumerate(queries):
            if q["user"] not in model.users:
                continue
            if q.get("whiteList"):
                black = set(q.get("blackList") or ())
                cand = [c for c in q["whiteList"]
                        if c in model.items and c not in black]
                if cand:
                    white_q.append(
                        (i, model.users.index_of(q["user"]), cand))
            else:
                known.append((i, model.users.index_of(q["user"])))
        if white_q:
            flat_u = np.concatenate([
                np.full(len(cand), u, np.int32)
                for _, u, cand in white_q
            ])
            flat_i = np.concatenate([
                model.items.encode(cand) for _, _, cand in white_q
            ]).astype(np.int32)
            flat_s = np.asarray(
                als.predict_pairs(model.factors, flat_u, flat_i))
            off = 0
            for qi, _, cand in white_q:
                s = flat_s[off:off + len(cand)]
                off += len(cand)
                results[qi] = _rank_candidates(
                    cand, s, int(queries[qi].get("num", 10)))
        if not known:
            return results
        n_items = model.factors.item_factors.shape[0]
        rows = np.array([u for _, u in known], dtype=np.int32)
        k = min(
            max(int(queries[qi].get("num", 10))
                + len(queries[qi].get("blackList") or ())
                for qi, _ in known),
            n_items,
        )
        rp = self._rparams
        if rp.mode == "clustered" and not rp.is_exhaustive(n_items):
            # batched two-stage tier (same branch contract as predict)
            from pio_tpu.ops import retrieval as rt

            _, didx = self._retrieval_index(model)
            urows = np.asarray(model.factors.user_factors)[rows]
            scores, idx = rt.candidate_topk(
                didx, model.factors.item_factors, urows, k)
        else:
            scores, idx = als.recommend_topk(model.factors, rows, k)
            scores, idx = np.asarray(scores), np.asarray(idx)
        for row, (qi, _) in enumerate(known):
            q = queries[qi]
            n = int(q.get("num", 10))
            black = set(q.get("blackList") or ())
            keep = idx[row] >= 0
            items = model.items.decode(idx[row][keep])
            out = []
            for it, s in zip(items, scores[row][keep]):
                if it in black:
                    continue
                out.append({"item": it, "score": float(s)})
                if len(out) >= n:
                    break
            results[qi] = {"itemScores": out}
        return results

    def prepare_model_for_deploy(self, ctx, model: RecommendationModel):
        """Re-hydrate factors into device HBM (replaces the reference's
        retrain-at-deploy for PAlgorithm, Engine.scala:208-230)."""
        factors = als.ALSModel(
            jax.device_put(model.factors.user_factors),
            jax.device_put(model.factors.item_factors),
        )
        return RecommendationModel(
            factors, model.users, model.items, model.validation)


class RecommendationEngine(EngineFactory):
    """engine.json engineFactory target (reference Engine.scala template
    object RecommendationEngine extends EngineFactory)."""

    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            RecommendationDataSource,
            IdentityPreparator,
            {"als": ALSAlgorithm},
            FirstServing,
        )
