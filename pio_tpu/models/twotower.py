"""Two-tower neural retrieval template — the flagship pjit model.

The new-capability template of the project's brief ("Two-tower neural
recommender template (new PAlgorithm, pjit data-parallel)"): user and item towers
(embedding + MLP) trained with in-batch sampled softmax over (user, item)
interaction pairs. This is where the mesh design shows its axes:

 * batch is sharded over the "data" axis (pure dp);
 * embedding tables and MLP kernels are sharded over the "model" axis
   (Megatron-style tp: vocab-sharded embeddings, alternating column/row
   sharded Dense kernels);
 * the in-batch softmax runs over the GLOBAL batch: XLA inserts the
   all_gather/psum for the (B, B) logits automatically from the sharding
   annotations — the "let GSPMD insert collectives" recipe.

Serving: item embeddings are precomputed into a matrix at train end; query =
user tower forward + the same top-k matmul path the ALS templates use.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pio_tpu.controller.base import (
    DataSource,
    FirstServing,
    IdentityPreparator,
    PAlgorithm,
    Params,
)
from pio_tpu.controller.engine import Engine, EngineFactory
from pio_tpu.data.eventstore import Interactions
from pio_tpu.ops.bucketing import pow2_bucket
from pio_tpu.ops.similarity import cosine_topk
from pio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


class Tower(nn.Module):
    """Embedding + 2-layer MLP -> L2-normalized embedding."""

    vocab: int
    embed_dim: int
    hidden_dim: int
    out_dim: int

    @nn.compact
    def __call__(self, ids):  # (B,) int32
        # vocab-sharded table (tp): rows split over the model axis
        e = nn.Embed(
            self.vocab, self.embed_dim,
            embedding_init=nn.initializers.normal(0.02),
        )(ids)
        h = nn.Dense(self.hidden_dim)(e)       # column-sharded kernel
        h = nn.relu(h)
        z = nn.Dense(self.out_dim)(h)          # row-sharded kernel
        return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-8)


@dataclass(frozen=True)
class TwoTowerParams(Params):
    embed_dim: int = 64
    hidden_dim: int = 128
    out_dim: int = 32
    temperature: float = 0.05
    learning_rate: float = 1e-3
    batch_size: int = 1024
    steps: int = 200
    seed: int = 0
    # mid-train step checkpoints (workflow/orbax_ckpt.py); "" = off
    checkpoint_dir: str = ""
    checkpoint_every: int = 100


def param_shardings(params_tree, mesh: Mesh):
    """Sharding tree for the tower params: embeddings vocab-sharded, Dense
    kernels alternately column/row sharded over the model axis."""

    def spec_for(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if leaf.ndim == 2:
            if any("Embed" in n or "embedding" in n for n in names):
                return P(MODEL_AXIS, None)      # vocab-sharded
            if "Dense_0" in names:
                return P(None, MODEL_AXIS)      # column parallel
            if "Dense_1" in names:
                return P(MODEL_AXIS, None)      # row parallel
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path, leaf)),
        params_tree,
    )


def make_towers(n_users: int, n_items: int, p: TwoTowerParams):
    user_tower = Tower(n_users, p.embed_dim, p.hidden_dim, p.out_dim)
    item_tower = Tower(n_items, p.embed_dim, p.hidden_dim, p.out_dim)
    return user_tower, item_tower


def init_params(n_users: int, n_items: int, p: TwoTowerParams):
    user_tower, item_tower = make_towers(n_users, n_items, p)
    ku, ki = jax.random.split(jax.random.PRNGKey(p.seed))
    dummy = jnp.zeros((1,), jnp.int32)
    return {
        "user": user_tower.init(ku, dummy)["params"],
        "item": item_tower.init(ki, dummy)["params"],
    }


def make_train_step(n_users: int, n_items: int, p: TwoTowerParams, optimizer):
    user_tower, item_tower = make_towers(n_users, n_items, p)

    def loss_fn(params, u_ids, i_ids):
        u = user_tower.apply({"params": params["user"]}, u_ids)   # (B, d)
        v = item_tower.apply({"params": params["item"]}, i_ids)   # (B, d)
        logits = (u @ v.T) / p.temperature                        # (B, B)
        labels = jnp.arange(u_ids.shape[0])
        # symmetric in-batch softmax (user->item and item->user)
        l1 = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        l2 = optax.softmax_cross_entropy_with_integer_labels(logits.T, labels)
        return (l1.mean() + l2.mean()) / 2

    def train_step(params, opt_state, u_ids, i_ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, u_ids, i_ids)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step, (user_tower, item_tower)


def train_two_tower(
    inter: Interactions,
    p: TwoTowerParams,
    mesh: Mesh | None = None,
    checkpoint=None,
    lifecycle=None,
) -> tuple[dict, jax.Array, Any]:
    """-> (params, item_embeddings matrix, towers). Sharded over the mesh
    when given; single-device jit otherwise. `checkpoint` is a
    StepCheckpointer (or None): training saves every save_every steps and
    resumes from the latest saved step with an identical batch stream
    (sampling is keyed by (seed, step)). `lifecycle` is a
    workflow.lifecycle.TrainLifecycle (or None): heartbeats every span
    boundary, and a requested preemption force-saves the current step
    then raises TrainingPreempted."""
    optimizer = optax.adam(p.learning_rate)
    train_step, towers = make_train_step(
        inter.n_users, inter.n_items, p, optimizer
    )
    params = init_params(inter.n_users, inter.n_items, p)
    opt_state = optimizer.init(params)

    from pio_tpu.workflow.orbax_ckpt import resume_or_init

    params, opt_state, start_step = resume_or_init(checkpoint, params, opt_state)

    batch = min(p.batch_size, max(8, len(inter)))
    if mesh is not None:
        n_data = mesh.shape[DATA_AXIS]
        batch = max(n_data, batch - batch % n_data)  # divisible by dp
        p_shard = param_shardings(params, mesh)
        o_shard = param_shardings_for_opt(opt_state, params, p_shard, mesh)
        # step axis replicated, batch axis dp-sharded
        xs_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)
    else:
        p_shard = o_shard = xs_sharding = None

    def run_span(params, opt_state, uu, ii):
        """lax.scan over a span of steps — the whole span is ONE device
        program: no per-step host round trip and no per-step transfer."""
        def body(carry, xs):
            params, opt_state = carry
            u, i = xs
            params, opt_state, _loss = train_step(params, opt_state, u, i)
            return (params, opt_state), None

        (params, opt_state), _ = jax.lax.scan(
            body, (params, opt_state), (uu, ii))
        return params, opt_state

    if mesh is not None:
        span = jax.jit(
            run_span,
            in_shardings=(p_shard, o_shard, xs_sharding, xs_sharding),
            out_shardings=(p_shard, o_shard),
        )
    else:
        span = jax.jit(run_span)

    # (seed, step)-keyed sampling: the stream is identical whether the run
    # is fresh or resumed from a checkpoint. Indices for a whole SPAN of
    # steps are built host-side and cross to the device once — a span is
    # one compiled program instead of span-many dispatches; boundaries
    # come from workflow/spans.py (bounded staging + checkpoint cadence).
    from pio_tpu.workflow.spans import span_bounds

    n = len(inter)

    def batches_for(lo: int, hi: int):
        idx = np.stack([
            np.random.default_rng((p.seed, s)).integers(0, n, size=batch)
            for s in range(lo, hi)
        ])
        uu = jnp.asarray(inter.user_idx[idx], jnp.int32)
        ii = jnp.asarray(inter.item_idx[idx], jnp.int32)
        if mesh is not None:
            uu = jax.device_put(uu, xs_sharding)
            ii = jax.device_put(ii, xs_sharding)
        return uu, ii

    every = (
        max(1, checkpoint.config.save_every) if checkpoint is not None
        else None
    )
    from pio_tpu.workflow.spans import after_span, step_chaos_active

    step_chaos = step_chaos_active()
    for lo, hi, save_after in span_bounds(
            start_step, p.steps, every, cap=1 if step_chaos else 512):
        uu, ii = batches_for(lo, hi)
        params, opt_state = span(params, opt_state, uu, ii)
        after_span(hi, p.steps, params, opt_state, checkpoint=checkpoint,
                   lifecycle=lifecycle, save_after=save_after,
                   step_chaos=step_chaos)

    # materialize all item embeddings for serving
    item_ids = jnp.arange(inter.n_items, dtype=jnp.int32)
    item_emb = towers[1].apply({"params": jax.device_get(params)["item"]}, item_ids)
    return jax.device_get(params), item_emb, towers


def param_shardings_for_opt(opt_state, params, p_shard, mesh: Mesh):
    """Optimizer state shardings: adam's mu/nu are pytrees with exactly the
    params' structure, so any subtree structurally identical to `params`
    gets the params' sharding tree verbatim; everything else (count and
    other scalars) is replicated. Structural matching avoids the shape-
    collision hazard of matching leaves by shape."""
    params_struct = jax.tree_util.tree_structure(params)
    replicated = NamedSharding(mesh, P())

    def is_params_like(node):
        if node is opt_state:
            return False
        try:
            return jax.tree_util.tree_structure(node) == params_struct
        except Exception:  # noqa: BLE001 - non-pytree leaves
            return False

    def handle(node):
        if is_params_like(node):
            return p_shard
        return jax.tree_util.tree_map(lambda _: replicated, node)

    return jax.tree_util.tree_map(handle, opt_state, is_leaf=is_params_like)


# ---------------------------------------------------------------------------
# DASE wrapper
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TwoTowerDataSourceParams(Params):
    app_name: str = ""
    event_names: tuple[str, ...] = ("view", "buy", "rate")
    # >0 -> read_eval produces k index-mod-k folds: the tuning sweep's
    # sequential path (pio eval --sweep on this engine) scores the
    # two-tower grid through the SAME fold contract the ALS templates
    # use — what promotes this engine from demo to tuned second class
    eval_k: int = 0
    eval_num: int = 10              # ranking depth of each fold query
    eval_exclude_seen: bool = True


class TwoTowerDataSource(DataSource):
    params_class = TwoTowerDataSourceParams

    def __init__(self, params: TwoTowerDataSourceParams):
        self.params = params

    def read_training(self, ctx) -> Interactions:
        return ctx.event_store.interactions(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
            value_key=None,
            default_value=1.0,
            dedup="sum",
        )

    def read_eval(self, ctx):
        """k folds of (train, info, [(query, heldout items)]) — the
        recommendation-template eval contract over the two-tower read."""
        from pio_tpu.e2.crossvalidation import split_interactions

        data = self.read_training(ctx)
        return split_interactions(
            data, self.params.eval_k, num=self.params.eval_num,
            exclude_seen=self.params.eval_exclude_seen,
        )


@jax.tree_util.register_pytree_node_class
@dataclass
class TwoTowerModel:
    params: dict           # tower params (host pytree after train)
    item_embeddings: jax.Array
    users: Any
    items: Any
    config: TwoTowerParams

    def tree_flatten(self):
        return (self.params, self.item_embeddings), (
            self.users, self.items, self.config,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


class TwoTowerAlgorithm(PAlgorithm):
    params_class = TwoTowerParams

    def __init__(self, params: TwoTowerParams = TwoTowerParams()):
        self.params = params

    def train(self, ctx, inter: Interactions) -> TwoTowerModel:
        inter.sanity_check()
        mesh = ctx.mesh if ctx and ctx.mesh and ctx.mesh.devices.size > 1 else None
        lifecycle = getattr(ctx, "lifecycle", None)
        # explicit params win; otherwise run_train's per-instance dir
        # (lifecycle.checkpoint_dir) makes every supervised run resumable
        ckpt_dir = self.params.checkpoint_dir or (
            lifecycle.checkpoint_dir if lifecycle is not None else ""
        )
        ckpt = None
        if ckpt_dir:
            from pio_tpu.workflow.orbax_ckpt import (
                StepCheckpointConfig,
                StepCheckpointer,
            )

            ckpt = StepCheckpointer(StepCheckpointConfig(
                ckpt_dir,
                save_every=self.params.checkpoint_every,
            ))
        try:
            params, item_emb, _ = train_two_tower(
                inter, self.params, mesh, checkpoint=ckpt,
                lifecycle=lifecycle,
            )
        finally:
            if ckpt is not None:
                ckpt.close()
        return TwoTowerModel(
            params=params, item_embeddings=item_emb,
            users=inter.users, items=inter.items, config=self.params,
        )

    def predict(self, model: TwoTowerModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: TwoTowerModel, queries) -> list:
        """Vectorized retrieval (the micro-batcher's path): ONE user-tower
        forward + ONE cosine top-k for every known user in the batch
        (blackList handled by over-fetch + host filter, like the
        recommendation template's batched path)."""
        results: list[dict] = [{"itemScores": []} for _ in queries]
        known = [
            (i, model.users.index_of(q["user"]))
            for i, q in enumerate(queries)
            if q.get("user", "") in model.users
        ]
        if not known:
            return results
        tower = Tower(
            len(model.users), model.config.embed_dim,
            model.config.hidden_dim, model.config.out_dim,
        )
        # batch dim bucketed: the micro-batcher produces varying sizes and
        # each distinct B would otherwise compile a fresh tower forward +
        # top-k program
        b = len(known)
        uidx = np.zeros(pow2_bucket(b), np.int32)
        uidx[:b] = [u for _, u in known]
        uv = tower.apply(
            {"params": model.params["user"]}, jnp.asarray(uidx),
        )                                                   # (B', d)
        n_items = model.item_embeddings.shape[0]
        k = min(
            max(int(queries[qi].get("num", 10))
                + len(queries[qi].get("blackList") or ())
                for qi, _ in known),
            n_items,
        )
        scores, idx = cosine_topk(model.item_embeddings, uv, k)
        scores, idx = np.asarray(scores)[:b], np.asarray(idx)[:b]
        for row, (qi, _) in enumerate(known):
            q = queries[qi]
            num = int(q.get("num", 10))
            black = set(q.get("blackList") or ())
            out = []
            for item, s in zip(model.items.decode(idx[row]), scores[row]):
                if item in black:
                    continue
                out.append({"item": item, "score": float(s)})
                if len(out) >= num:
                    break
            results[qi] = {"itemScores": out}
        return results


class TwoTowerEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            TwoTowerDataSource,
            IdentityPreparator,
            {"twotower": TwoTowerAlgorithm},
            FirstServing,
        )
