"""Sequential (session-based) recommendation template — self-attentive
next-item prediction over user event histories.

Net-new model family beyond the reference's capability set (the reference
has no sequence models: SURVEY.md section 5 "Long-context / sequence
parallelism: absent"); it is the framework's long-context showcase and the
engine that exercises ops/attention.py end to end:

 * training: causal transformer over time-ordered per-user item sequences
   (next-item cross-entropy, embedding-tied output head);
 * parallelism: one shard_map'd SPMD train step over the mesh — batch on
   the "data" axis, sequence on the "seq" axis with `ring_attention`
   rotating k/v shards over ICI, gradients psum'd across both axes.
   The same code path runs single-device (both axes size 1);
 * serving: encode the user's recent history (live event-store read, like
   the ecommerce template's cold-start path) with the Pallas
   `flash_attention` kernel, then the standard top-k matmul.

Event-data contract matches the other templates: user->item events with
event times (e.g. view/buy), sequences are the per-user time-ordered item
ids (same fold order as the reference's LEventAggregator time ordering).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
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
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.ops.attention import (
    attention_reference,
    chunked_attention,
    flash_attention,
    flash_attention_trainable,
    ring_attention,
    ulysses_attention,
)
from pio_tpu.ops.bucketing import pow2_bucket
from pio_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS


PAD = 0  # item index 0 is reserved as padding; real items start at 1


@dataclass(frozen=True)
class SequenceParams(Params):
    max_len: int = 64          # sequence length (pad/truncate buckets)
    embed_dim: int = 64
    num_heads: int = 2
    num_layers: int = 2
    ffn_dim: int = 128
    dropout: float = 0.0       # kept 0 in-graph; eval-mode determinism
    learning_rate: float = 1e-3
    batch_size: int = 128
    steps: int = 300
    seed: int = 0
    # "auto" | "reference" | "chunked" | "flash" | "ring" | "ulysses" —
    # "flash" trains with the Pallas forward + chunked backward
    # (ops/attention.py flash_attention_trainable; fastest forward on
    # TPU-class backends). auto picks
    # ring when the mesh shards the sequence axis; on a single device it
    # picks chunked (memory-efficient online-softmax scan,
    # ops/attention.py chunked_attention — logits memory O(S*chunk), so
    # long contexts train single-chip) above chunked_threshold tokens and
    # the naive reference below it. ulysses = all-to-all head-sharded
    # sequence parallelism (ops/attention.py ulysses_attention): two
    # collectives per layer vs ring's n-1 hops; requires num_heads
    # divisible by the seq-axis size.
    attention: str = "auto"
    # single-device auto: sequences at/above this length train with
    # chunked attention (naive logits at 1024 tokens are already
    # B*H*1024^2*4 bytes)
    chunked_threshold: int = 1024
    # mixture-of-experts FFN: 0 = dense (default). With > 0 experts each
    # block's FFN becomes a Switch-style MoE (ops/moe.py) — one-hot-matmul
    # dispatch, capacity-dropped tokens ride the residual, and the
    # load-balance aux loss joins the objective with moe_aux_weight
    moe_experts: int = 0
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    unseen_only: bool = True   # serve-time: drop items already in history
    # serve-time live history read (empty app_name = training snapshot only)
    app_name: str = ""
    event_names: tuple[str, ...] = ("view", "buy")
    # mid-train step checkpoints (workflow/orbax_ckpt.py); "" = off
    checkpoint_dir: str = ""
    checkpoint_every: int = 100
    # a block specification (models/seq_blocks.py BlockSpec: the keys of a
    # published config.json plus this rank's share of it), as a dict in
    # engine.json; kept as its JSON text so the params stay hashable.
    # With one, the engine trains that decoder stack (RMSNorm, rotary
    # positions, grouped-query window / full or latent attention, dense
    # or top-k expert layers, untied head) on whole histories of max_len
    # items (max_len + 1 where the specification has a multi-token-
    # prediction module: every position then also trains the id after
    # its next), and embed_dim, num_heads, num_layers, ffn_dim, attention
    # and the moe_* fields above, which size the SASRec-style encoder,
    # are not read.
    block_spec: Any = None

    def __post_init__(self):
        if isinstance(self.block_spec, dict):
            object.__setattr__(self, "block_spec", json.dumps(
                self.block_spec, sort_keys=True))


class Block(nn.Module):
    """Pre-LN transformer block with a pluggable attention fn and an
    optional MoE FFN (moe_experts > 0; ops/moe.py)."""

    num_heads: int
    head_dim: int
    ffn_dim: int
    moe_experts: int = 0
    moe_capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x, attn_fn):
        from pio_tpu.ops.moe import MoEConfig, moe_ffn

        b, s, e = x.shape
        h, d = self.num_heads, self.head_dim
        y = nn.LayerNorm()(x)
        qkv = nn.Dense(3 * h * d, use_bias=False)(y).reshape(b, s, 3, h, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        o = attn_fn(q, k, v)                            # (b, s, h, d)
        x = x + nn.Dense(e, use_bias=False)(o.reshape(b, s, h * d))
        y = nn.LayerNorm()(x)
        if self.moe_experts > 0:
            E, f = self.moe_experts, self.ffn_dim
            init = nn.initializers.normal(1.0 / np.sqrt(e))
            init_out = nn.initializers.normal(1.0 / np.sqrt(f))
            moe_params = {
                "router": self.param("moe_router", init, (e, E)),
                "w_in": self.param("moe_w_in", init, (E, e, f)),
                "b_in": self.param("moe_b_in", nn.initializers.zeros, (E, f)),
                "w_out": self.param("moe_w_out", init_out, (E, f, e)),
                "b_out": self.param(
                    "moe_b_out", nn.initializers.zeros, (E, e)),
            }
            cfg = MoEConfig(E, e, f, self.moe_capacity_factor)
            y2, aux = moe_ffn(moe_params, y.reshape(b * s, e), cfg)
            # sow is a no-op unless the caller makes "moe_aux" mutable
            # (training does; serving never pays for it)
            self.sow("moe_aux", "aux", aux)
            x = x + y2.reshape(b, s, e)
        else:
            y = nn.Dense(self.ffn_dim)(y)
            y = nn.gelu(y)
            x = x + nn.Dense(e)(y)
        return x


class SeqEncoder(nn.Module):
    """Item-id sequence -> per-position hidden states; logits are tied to
    the item embedding table (SASRec-style)."""

    vocab: int                 # includes PAD at index 0
    max_len: int               # GLOBAL max sequence length (for positions)
    embed_dim: int
    num_heads: int
    num_layers: int
    ffn_dim: int
    moe_experts: int = 0
    moe_capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, ids, attn_fn, pos_offset=0):
        emb = self.param(
            "item_emb", nn.initializers.normal(0.02),
            (self.vocab, self.embed_dim),
        )
        pos = self.param(
            "pos_emb", nn.initializers.normal(0.02),
            (self.max_len, self.embed_dim),
        )
        s = ids.shape[1]
        x = emb[ids] * np.sqrt(self.embed_dim)
        x = x + jax.lax.dynamic_slice_in_dim(pos, pos_offset, s, axis=0)[None]
        head_dim = self.embed_dim // self.num_heads
        for _ in range(self.num_layers):
            x = Block(self.num_heads, head_dim, self.ffn_dim,
                      self.moe_experts, self.moe_capacity_factor)(x, attn_fn)
        x = nn.LayerNorm()(x)
        logits = x @ emb.T                              # weight-tied head
        return x, logits


def user_histories(events):
    """-> ({user id: time-ordered item-id list}, items EntityIdIndex
    over EVERY item seen). The ONE event-grouping/ordering
    implementation behind read_training (build_sequences) and
    read_eval's rolling folds — so the two reads cannot drift on event
    filtering or ordering."""
    by_user: dict[str, list[tuple[Any, str]]] = {}
    item_ids: dict[str, None] = {}
    for e in events:
        if not e.target_entity_id:
            continue
        by_user.setdefault(e.entity_id, []).append(
            (e.event_time, e.target_entity_id)
        )
        item_ids.setdefault(e.target_entity_id, None)
    items = EntityIdIndex(item_ids.keys())
    hists = {}
    for uid, evs in by_user.items():
        evs.sort(key=lambda t: t[0])
        hists[uid] = [i for _, i in evs]
    return hists, items


def build_sequences(events, max_len: int):
    """Time-ordered per-user item sequences from user->item events.

    Returns (seqs int32 (N, max_len) right-aligned & PAD-left-padded,
    users EntityIdIndex over sequence owners, items EntityIdIndex with ids
    offset by 1 for PAD). Users with < 2 interactions are dropped (no
    next-item target exists)."""
    hists, items = user_histories(events)
    users, rows = [], []
    for uid, ids in hists.items():
        if len(ids) < 2:
            continue
        seq = [items.index_of(i) + 1 for i in ids][-max_len:]  # +1: PAD=0
        rows.append(np.pad(seq, (max_len - len(seq), 0)))
        users.append(uid)
    if not rows:
        raise ValueError("no user has >= 2 interactions; cannot train")
    return (
        np.stack(rows).astype(np.int32),
        EntityIdIndex(users),
        items,
    )


@dataclass
class SequenceData:
    seqs: np.ndarray            # (N, max_len) int32, PAD-left
    users: EntityIdIndex
    items: EntityIdIndex

    def sanity_check(self):
        assert self.seqs.ndim == 2 and self.seqs.shape[0] > 0


POS_HEADROOM = 16


def _apply_with_aux(encoder, params, inp, attn, pos_offset, p):
    """encoder.apply collecting the MoE load-balance aux loss (zero for
    dense models — the moe_aux collection is only populated by MoE
    blocks)."""
    if p.moe_experts > 0:
        out, aux_vars = encoder.apply(
            {"params": params}, inp, attn, pos_offset=pos_offset,
            mutable=["moe_aux"],
        )
        leaves = jax.tree_util.tree_leaves(aux_vars)
        aux = p.moe_aux_weight * sum(jnp.mean(a) for a in leaves) \
            / max(1, len(leaves))
        return out, aux
    out = encoder.apply(
        {"params": params}, inp, attn, pos_offset=pos_offset
    )
    return out, jnp.float32(0.0)


def make_encoder(n_items: int, p: SequenceParams) -> SeqEncoder:
    # Position-table headroom: the train step right-pads the sequence so it
    # splits evenly over the seq mesh axis (up to n_seq-1 extra positions).
    # The table size must be a pure function of the params — serving
    # re-creates the encoder without knowing the training mesh — so the
    # headroom is fixed and train_sequence_model validates the pad fits.
    return SeqEncoder(
        vocab=n_items + 1, max_len=p.max_len + POS_HEADROOM,
        embed_dim=p.embed_dim,
        num_heads=p.num_heads, num_layers=p.num_layers, ffn_dim=p.ffn_dim,
        moe_experts=p.moe_experts,
        moe_capacity_factor=p.moe_capacity_factor,
    )


def train_sequence_model(
    data: SequenceData, p: SequenceParams, mesh: Mesh | None = None,
    checkpoint=None, lifecycle=None,
):
    """SPMD train loop: dp x sp shard_map step (see module docstring).

    `checkpoint` is a StepCheckpointer (or None): saves every save_every
    steps, resumes from the latest step with an identical batch stream.
    `lifecycle` is a workflow.lifecycle.TrainLifecycle (or None):
    heartbeats at span boundaries; preemption force-saves then raises.
    Returns (params, encoder, final loss)."""
    if p.block_spec:
        from pio_tpu.models.seq_blocks import train_lm

        if mesh is not None or checkpoint is not None:
            raise ValueError(
                "a block specification trains on one chip, without step "
                "checkpoints (ROADMAP R1)")
        params, loss = train_lm(data.seqs, p, lifecycle=lifecycle)
        return params, None, loss
    encoder = make_encoder(len(data.items), p)
    optimizer = optax.adam(p.learning_rate)

    seqs = data.seqs
    inp_all, tgt_all = seqs[:, :-1], seqs[:, 1:]
    s_global = inp_all.shape[1]

    if p.attention not in ("auto", "reference", "chunked", "flash",
                           "ring", "ulysses"):
        raise ValueError(
            f"unknown attention mode {p.attention!r}: expected "
            "'auto' | 'reference' | 'chunked' | 'flash' | 'ring' | "
            "'ulysses'"
        )
    # once the sequence is sharded, attention MUST be sequence-parallel
    # (ring or ulysses) — a local-only attention would silently drop
    # cross-shard interactions
    use_sp = mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1
    if use_sp and p.attention in ("reference", "chunked", "flash"):
        raise ValueError(
            f"attention={p.attention!r} is a local-only path and cannot "
            "run with the sequence sharded over the mesh seq axis; use "
            "'auto'/'ring'/'ulysses' or a seq=1 mesh"
        )
    if not use_sp and p.attention in ("ring", "ulysses"):
        raise ValueError(
            f"attention={p.attention!r} requires a mesh with a seq axis > 1"
        )
    if use_sp and p.attention == "ulysses":
        n_seq_axis = mesh.shape[SEQ_AXIS]
        if p.num_heads % n_seq_axis:
            raise ValueError(
                f"attention='ulysses' needs num_heads ({p.num_heads}) "
                f"divisible by the seq axis ({n_seq_axis})"
            )

    # local (non-sequence-parallel) attention: chunked at/above the
    # threshold (compared on max_len: the training inputs are one token
    # shorter), naive reference below it
    use_chunked_local = p.attention == "chunked" or (
        p.attention == "auto" and p.max_len >= p.chunked_threshold
    )
    if p.attention == "flash":
        # Pallas forward + chunked-XLA backward (custom_vjp): the fast
        # training-forward option on TPU-class backends; on CPU the
        # kernel runs in interpret mode, so prefer chunked/reference
        local_attn = partial(flash_attention_trainable, causal=True)
    else:
        local_attn = partial(
            chunked_attention if use_chunked_local else attention_reference,
            causal=True,
        )
    # init with the SAME local attention: a naive-attention init forward
    # would materialize the full (1,H,S,S) logits and OOM at exactly the
    # long contexts the chunked path exists for
    params = encoder.init(
        jax.random.PRNGKey(p.seed),
        jnp.zeros((1, s_global), jnp.int32),
        local_attn,
    )["params"]
    opt_state = optimizer.init(params)

    if mesh is not None:
        n_data = mesh.shape[DATA_AXIS]
        n_seq = mesh.shape.get(SEQ_AXIS, 1)
        # sequence length must split evenly over the seq axis
        if s_global % n_seq:
            pad = n_seq - s_global % n_seq
            if s_global + pad > p.max_len + POS_HEADROOM:
                raise ValueError(
                    f"seq-axis padding ({pad}) overflows the position table "
                    f"({p.max_len} + {POS_HEADROOM} headroom); raise max_len "
                    f"or use a smaller seq mesh axis (n_seq={n_seq})"
                )
            inp_all = np.pad(inp_all, ((0, 0), (0, pad)))
            tgt_all = np.pad(tgt_all, ((0, 0), (0, pad)))
            s_global += pad
        s_local = s_global // n_seq

        def local_loss(params, inp, tgt, pos_offset):
            if use_sp and p.attention == "ulysses":
                attn = partial(
                    ulysses_attention, axis_name=SEQ_AXIS, causal=True,
                )
            elif use_sp:
                attn = partial(
                    ring_attention, axis_name=SEQ_AXIS, causal=True,
                )
            else:
                attn = local_attn
            (_, logits), aux = _apply_with_aux(
                encoder, params, inp, attn, pos_offset, p
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            mask = (tgt != PAD).astype(jnp.float32)
            loss_sum = jax.lax.psum(
                jnp.sum(ce * mask), (DATA_AXIS, SEQ_AXIS)
            )
            count = jax.lax.psum(jnp.sum(mask), (DATA_AXIS, SEQ_AXIS))
            aux = jax.lax.pmean(aux, (DATA_AXIS, SEQ_AXIS))
            return loss_sum / jnp.maximum(count, 1.0) + aux

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(
                P(), P(),
                P(DATA_AXIS, SEQ_AXIS), P(DATA_AXIS, SEQ_AXIS),
            ),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        def step(params, opt_state, inp, tgt):
            pos_offset = jax.lax.axis_index(SEQ_AXIS) * s_local
            loss, grads = jax.value_and_grad(local_loss)(
                params, inp, tgt, pos_offset
            )
            # local grads cover local tokens only; sum across dp and sp
            grads = jax.lax.psum(grads, (DATA_AXIS, SEQ_AXIS))
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        step = jax.jit(step)
        batch = max(n_data, p.batch_size - p.batch_size % n_data)
    else:
        n_data = 1
        attn = local_attn

        def loss_fn(params, inp, tgt):
            (_, logits), aux = _apply_with_aux(
                encoder, params, inp, attn, 0, p
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            mask = (tgt != PAD).astype(jnp.float32)
            return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0) + aux

        @jax.jit
        def step(params, opt_state, inp, tgt):
            loss, grads = jax.value_and_grad(loss_fn)(params, inp, tgt)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        batch = p.batch_size

    from pio_tpu.workflow.orbax_ckpt import resume_or_init

    params, opt_state, start_step = resume_or_init(checkpoint, params, opt_state)

    n = inp_all.shape[0]
    # the sampled batch must split evenly over the data mesh axis
    size = min(batch, max(8, n))
    size = max(n_data, size - size % n_data)

    # spans of steps scanned on device: one dispatch + one batch transfer
    # per span instead of per step (workflow/spans.py owns the boundary
    # math — bounded staging, checkpoint cadence preserved step-for-step)
    from pio_tpu.workflow.spans import span_bounds

    def run_span(params, opt_state, inps, tgts):
        def body(carry, xs):
            params, opt_state = carry
            inp, tgt = xs
            params, opt_state, loss = step_fn(params, opt_state, inp, tgt)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (inps, tgts))
        return params, opt_state, losses[-1]

    step_fn = step  # the (possibly shard_mapped) single-step update
    span = jax.jit(run_span)

    def batches_for(lo: int, hi: int):
        idx = np.stack([
            np.random.default_rng((p.seed, s)).integers(0, n, size=size)
            for s in range(lo, hi)
        ])
        inps = jnp.asarray(inp_all[idx])
        tgts = jnp.asarray(tgt_all[idx])
        if mesh is not None:
            xs_sharding = NamedSharding(
                mesh, P(None, DATA_AXIS, SEQ_AXIS))
            inps = jax.device_put(inps, xs_sharding)
            tgts = jax.device_put(tgts, xs_sharding)
        return inps, tgts

    every = (
        max(1, checkpoint.config.save_every) if checkpoint is not None
        else None
    )
    # spans stage (span, batch, seq) token tensors: cap by BYTES so long
    # sequences shrink the span instead of blowing up staging memory
    # (2 arrays x cap x size x seq_len x 4B <= ~64 MB)
    seq_len = inp_all.shape[1]
    cap = max(1, min(512, (64 << 20) // max(1, 2 * size * seq_len * 4)))
    from pio_tpu.workflow.spans import after_span, step_chaos_active

    step_chaos = step_chaos_active()
    if step_chaos:
        cap = 1
    loss = None
    for lo, hi, save_after in span_bounds(start_step, p.steps, every,
                                          cap=cap):
        inps, tgts = batches_for(lo, hi)
        params, opt_state, loss = span(params, opt_state, inps, tgts)
        after_span(hi, p.steps, params, opt_state, checkpoint=checkpoint,
                   lifecycle=lifecycle, save_after=save_after,
                   step_chaos=step_chaos)
    if loss is None:
        # resumed a run whose final step is already checkpointed (or
        # steps == 0): report the loss AT the restored params on the last
        # step's batch — span's loss is pre-update, and the updated
        # params/opt_state are discarded
        inps, tgts = batches_for(max(start_step - 1, 0),
                                 max(start_step, 1))
        _, _, loss = span(params, opt_state, inps, tgts)
    return jax.device_get(params), encoder, float(loss)


# ---------------------------------------------------------------------------
# DASE wrapper
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SequenceDataSourceParams(Params):
    app_name: str = ""
    event_names: tuple[str, ...] = ("view", "buy")
    max_len: int = 64
    # >0 -> read_eval produces k ROLLING next-item folds: fold f holds
    # out each user's (f+1)-th-from-last item and trains on the strict
    # prefix — the time-respecting split for sequence models, and what
    # lets `pio eval --sweep` tune this engine through the sequential
    # fallback like the two-tower grid
    eval_k: int = 0
    eval_num: int = 10              # ranking depth of each fold query


class SequenceDataSource(DataSource):
    params_class = SequenceDataSourceParams

    def __init__(self, params: SequenceDataSourceParams):
        self.params = params

    def _histories(self, ctx):
        """-> (per-user time-ordered item-id lists, full items index)
        via the SAME user_histories grouping read_training uses. The
        items index spans EVERY fold so vocab/embedding shapes stay
        identical across the sweep's candidates."""
        events = ctx.event_store.find(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )
        return user_histories(events)

    def read_training(self, ctx) -> SequenceData:
        events = ctx.event_store.find(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )
        seqs, users, items = build_sequences(events, self.params.max_len)
        return SequenceData(seqs, users, items)

    def read_eval(self, ctx):
        """k rolling next-item folds of (train, info, [(query, actual)]):
        fold f trains each user on their history minus the last f+1
        items and is scored on predicting the held-out item — strictly
        past-only, like the tuning subsystem's time split."""
        k = self.params.eval_k
        max_len = self.params.max_len
        hists, items = self._histories(ctx)
        folds = []
        for f in range(k):
            cut = f + 1
            users, rows, qa = [], [], []
            for uid, ids in hists.items():
                # >= 2 training items must remain (next-item training
                # needs a target inside the train split)
                if len(ids) < cut + 2:
                    continue
                train_ids = ids[:-cut]
                seq = [items.index_of(i) + 1
                       for i in train_ids][-max_len:]
                rows.append(np.pad(seq, (max_len - len(seq), 0)))
                users.append(uid)
                qa.append(({"user": uid, "num": self.params.eval_num},
                           [ids[-cut]]))
            if not rows:
                continue
            train = SequenceData(
                np.stack(rows).astype(np.int32),
                EntityIdIndex(users), items)
            folds.append((train, {"fold": f, "holdout": cut}, qa))
        return folds


@jax.tree_util.register_pytree_node_class
@dataclass
class SequenceModel:
    params: dict
    seqs: np.ndarray           # training-time sequences for serve lookup
    users: EntityIdIndex
    items: EntityIdIndex
    config: SequenceParams

    def tree_flatten(self):
        # seqs is a leaf (arrays in aux_data would make the treedef
        # unhashable and break jit/device_put over the model)
        return (self.params, self.seqs), (self.users, self.items, self.config)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


class SequenceAlgorithm(PAlgorithm):
    params_class = SequenceParams

    def __init__(self, params: SequenceParams = SequenceParams()):
        self.params = params
        self._event_store = None

    def train(self, ctx, data: SequenceData) -> SequenceModel:
        data.sanity_check()
        # max_len lives in BOTH the datasource and the algorithm params
        # (the datasource builds sequences, the algorithm sizes its
        # position table); adapt rather than explode on a mismatch —
        # right-aligned truncate (keep the most recent items) or left-pad
        s = data.seqs
        want = self.params.max_len
        if self.params.block_spec:
            from pio_tpu.models.seq_blocks import BlockSpec, history_ids

            # a prediction module trains one id more of every history
            want = history_ids(BlockSpec.parse(self.params.block_spec),
                               want - 1)
        if s.shape[1] != want:
            if s.shape[1] > want:
                s = s[:, -want:]
            else:
                s = np.pad(s, ((0, 0), (want - s.shape[1], 0)))
            data = SequenceData(
                seqs=np.ascontiguousarray(s), users=data.users,
                items=data.items,
            )
        mesh = (
            ctx.mesh
            if ctx and ctx.mesh is not None and ctx.mesh.devices.size > 1
            else None
        )
        lifecycle = getattr(ctx, "lifecycle", None)
        # explicit params win; otherwise run_train's per-instance dir
        ckpt_dir = self.params.checkpoint_dir or (
            lifecycle.checkpoint_dir if lifecycle is not None else ""
        )
        ckpt = None
        if ckpt_dir and not self.params.block_spec:
            from pio_tpu.workflow.orbax_ckpt import (
                StepCheckpointConfig,
                StepCheckpointer,
            )

            ckpt = StepCheckpointer(StepCheckpointConfig(
                ckpt_dir,
                save_every=self.params.checkpoint_every,
            ))
        try:
            params, _, _ = train_sequence_model(
                data, self.params, mesh, checkpoint=ckpt,
                lifecycle=lifecycle,
            )
        finally:
            if ckpt is not None:
                ckpt.close()
        if ctx is not None:
            self._event_store = getattr(ctx, "event_store", None)
        return SequenceModel(
            params=params, seqs=data.seqs, users=data.users,
            items=data.items, config=self.params,
        )

    def prepare_model_for_deploy(self, ctx, model: SequenceModel):
        self._event_store = ctx.event_store
        return model

    def _live_history(self, model: SequenceModel, user: str):
        """The user's recent item sequence from a live event-store read
        (the ecommerce template's serve-time pattern) — catches events that
        happened after training and users unseen at training time. Returns
        a PAD-left (max_len,) int32 row, or None when unavailable."""
        p = model.config
        if not p.app_name or self._event_store is None:
            return None
        try:
            events = self._event_store.find_by_entity(
                app_name=p.app_name,
                entity_type="user",
                entity_id=user,
                event_names=list(p.event_names),
                target_entity_type="item",
                limit=p.max_len,
                latest=True,
            )
        except Exception:  # noqa: BLE001 - storage outage must not kill serving
            return None
        seq = [
            model.items.index_of(e.target_entity_id) + 1
            for e in reversed(events)  # newest-first -> time order
            if e.target_entity_id in model.items
        ][-p.max_len:]
        if not seq:
            return None
        return np.pad(
            np.asarray(seq, np.int32), (p.max_len - len(seq), 0)
        )

    def _score_last_batch(self, model: SequenceModel, rows: np.ndarray):
        """Forward the last max_len-1 items of a (B, max_len) batch of
        history rows; return next-item scores (B, vocab) from the tied
        head at the final position. Training consumes inputs of length
        max_len-1 (positions 0..max_len-2), so serving must too — feeding
        all max_len items would read the never-trained last position row.
        The batch dim is bucketed to a power of two so the micro-batcher's
        varying sizes compile O(log) programs. Serving path: Pallas flash
        attention on TPU, reference on CPU."""
        p = model.config
        if p.block_spec:
            from pio_tpu.models.seq_blocks import BlockSpec, last_logits

            # whole histories only: every resolved row is max_len items;
            # the scores are the main head's (a prediction module trains
            # the stack and serves nothing)
            return last_logits(model.params,
                               jnp.asarray(rows[:, -(p.max_len - 1):]),
                               BlockSpec.parse(p.block_spec))
        encoder = make_encoder(len(model.items), p)
        on_cpu = jax.devices()[0].platform == "cpu"
        attn = partial(
            attention_reference if on_cpu else flash_attention, causal=True,
        )
        b = rows.shape[0]
        bucket = pow2_bucket(b)
        inp = rows[:, -(p.max_len - 1):]
        if bucket != b:
            inp = np.concatenate(
                [inp, np.zeros((bucket - b, inp.shape[1]), inp.dtype)])
        _, logits = encoder.apply(
            {"params": model.params}, jnp.asarray(inp), attn,
        )
        return logits[:b, -1]

    def history_row(self, model: SequenceModel, query: dict):
        """The (max_len,) PAD-left row predict actually scores from: the
        live event-store history when app_name is configured (including
        post-training events), else the training snapshot; None for an
        unknown user with no live history. Public so user-code stages
        (e.g. a no-repeat Serving) reason about the SAME history the
        scores came from instead of re-deriving a stale one."""
        user = query.get("user", "")
        row = self._live_history(model, user)
        if row is None and user in model.users:
            # (a prediction module's snapshot rows hold one id more)
            row = model.seqs[model.users.index_of(user)][
                -model.config.max_len:]
        return row

    def predict(self, model: SequenceModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: SequenceModel, queries) -> list:
        """Vectorized serving (the micro-batcher's path): the history rows
        of every resolvable user in the batch encode in ONE transformer
        forward (batch bucketed to a power of two for compile-cache
        bounds); per-query seen/blackList masking and ranking happen on
        host over the (B, vocab) score matrix."""
        results: list[dict] = [{"itemScores": []} for _ in queries]
        resolved = []
        for i, q in enumerate(queries):
            row = self.history_row(model, q)
            if row is not None:
                resolved.append((i, row))
        if not resolved:
            return results
        rows = np.stack([r for _, r in resolved])
        all_scores = np.array(self._score_last_batch(model, rows))
        for b, (qi, row) in enumerate(resolved):
            q = queries[qi]
            num = int(q.get("num", 10))
            scores = all_scores[b]   # view into all_scores: masked IN
            # PLACE — each row is consumed exactly once, here
            scores[PAD] = -np.inf
            seen = (
                set(int(i) for i in row if i != PAD)
                if model.config.unseen_only else set()
            )
            black = {
                model.items.index_of(x) + 1
                for x in (q.get("blackList") or ())
                if x in model.items
            }
            for i in seen | black:
                scores[i] = -np.inf
            order = np.argsort(-scores)[:num]
            results[qi] = {"itemScores": [
                {"item": model.items.decode([i - 1])[0],
                 "score": float(scores[i])}
                for i in order if np.isfinite(scores[i])
            ]}
        return results


class SequenceEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            SequenceDataSource,
            IdentityPreparator,
            {"sasrec": SequenceAlgorithm},
            FirstServing,
        )
