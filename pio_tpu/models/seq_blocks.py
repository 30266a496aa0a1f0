"""The sequence engine's configurable block stack: a decoder-only
language model over item histories, built from a block specification.

`SequenceParams.block_spec` carries the specification: the keys of a
published `config.json` (RMSNorm, rotary positions by layer kind, grouped-
query heads, a pattern of window and full attention layers, SwiGLU experts
with a top-k router, an untied head), plus what one rank of a deployment
holds of it (`experts_held` of `num_experts_routed`, `vocab_size` rows of
the vocabulary). With a specification, `train_sequence_model` trains this
stack in place of the SASRec-style encoder of models/sequence.py:

  x_0 = E[ids];  h = x + Attn_l(RMSNorm(x));  x' = h + MoE_l(RMSNorm(h))
  logits = RMSNorm(x_L) W_head^T;  loss = mean next-item cross-entropy

 * attention: ops/attention.py `banded_flash_attention` (Pallas forward
   and backward; causal, a window on `sliding_attention` layers);
 * experts: ops/moe.py `held_moe_ffn` (dropless top-k, the held experts'
   part by a grouped matrix product), one history at a time;
 * precision: float32 master weights and Adam state, bfloat16 operands,
   float32 accumulation, float32 residual stream, norms and loss;
 * memory: every layer's two halves are recomputed in the backward pass
   (`jax.checkpoint` at their boundaries); the loss is computed over
   chunks of tokens so the (tokens, vocabulary) logits never exist whole.

One chip. Histories are whole (no PAD inside a row): packing and padding
of short histories, the experts' exchange across chips and a cache for
serving are not here (ROADMAP R1, R4).
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pio_tpu.ops.attention import (
    band_blocks,
    band_pairs,
    banded_flash_attention,
)
from pio_tpu.ops.moe import HeldExperts, held_moe_ffn
from pio_tpu.utils import tracing

PAD = 0
COMPUTE = jnp.bfloat16
LOSS_CHUNK = 2048          # tokens whose logits exist at one time
ATTN_BLOCK = 512           # query and key block of the attention kernels
MOE_TILE = 512             # rows of one tile of the grouped product
ADAM_B1 = 0.9              # after one step from zero, mu = (1 - b1) * gradient


@dataclass(frozen=True)
class BlockSpec:
    hidden_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]        # one kind per layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rope: tuple[tuple[str, tuple], ...]  # kind -> sorted rope parameters
    rms_norm_eps: float
    moe_intermediate_size: int
    num_experts_routed: int             # the router's width
    experts_held: tuple[int, int]       # [lo, hi) of them held here
    num_experts_per_tok: int
    norm_topk_prob: bool
    vocab_size: int                     # rows held, PAD's row 0 among them
    initializer_range: float
    embedding_initializer_range: float  # the embedding's rows alone

    @classmethod
    def parse(cls, spec: str | dict) -> "BlockSpec":
        c = json.loads(spec) if isinstance(spec, str) else dict(spec)
        wrong = {
            "hidden_act": c.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(c.get("attention_bias", False)),
            "tie_word_embeddings": bool(c.get("tie_word_embeddings", False)),
            "mlp_layer_types": any(
                t != "sparse" for t in
                c.get("mlp_layer_types", [])[:c["num_hidden_layers"]]),
        }
        if any(wrong.values()):
            raise ValueError(
                "block specification asks for what this stack does not "
                f"compute: {sorted(k for k, v in wrong.items() if v)}")
        n_layers = c["num_hidden_layers"]
        kinds = tuple(c["layer_types"][:n_layers])
        if len(kinds) != n_layers or set(kinds) - {
                "sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types {kinds} for {n_layers} layers")
        held = tuple(c.get("experts_held", (0, c["num_experts"])))
        if held[1] - held[0] != c["num_experts"]:
            raise ValueError(
                f"experts_held {held} is not num_experts "
                f"{c['num_experts']} experts")
        return cls(
            hidden_size=c["hidden_size"], num_hidden_layers=n_layers,
            layer_types=kinds,
            num_attention_heads=c["num_attention_heads"],
            num_key_value_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], sliding_window=c["sliding_window"],
            rope=tuple(sorted(
                (kind, tuple(sorted(c["rope_parameters"][kind].items())))
                for kind in set(kinds))),
            rms_norm_eps=c["rms_norm_eps"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_experts_routed=c.get("num_experts_routed", c["num_experts"]),
            experts_held=held,
            num_experts_per_tok=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"], vocab_size=c["vocab_size"],
            initializer_range=c.get("initializer_range", 0.02),
            embedding_initializer_range=c.get(
                "embedding_initializer_range",
                c.get("initializer_range", 0.02)))

    @property
    def experts(self) -> HeldExperts:
        return HeldExperts(self.num_experts_routed, self.num_experts_per_tok,
                           self.experts_held, self.norm_topk_prob, MOE_TILE)

    def window(self, kind: str) -> int | None:
        return self.sliding_window if kind == "sliding_attention" else None


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def rope_inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim / 2,), the factor on cos and sin)
    for `rope_type` "default" or "yarn" (frequencies blended between
    interpolation and extrapolation by the usual linear ramp)."""
    half = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** half
    if rope["rope_type"] == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    inv = inv / factor * (1.0 - extrapolated) + inv * extrapolated
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def rope_tables(spec: BlockSpec, seq_len: int) -> dict:
    """kind -> (cos, sin), each (seq_len, head_dim / 2) float32."""
    out = {}
    for kind, items in spec.rope:
        inv, scale = rope_inv_freq(dict(items), spec.head_dim)
        angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv[None]
        out[kind] = (np.float32(np.cos(angle) * scale),
                     np.float32(np.sin(angle) * scale))
    return out


def apply_rope(x, cos, sin):
    """x: (B, H, S, D) float32; the two halves of D rotate as pairs."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(spec: BlockSpec) -> dict:
    d, f = spec.hidden_size, spec.moe_intermediate_size
    hq = spec.num_attention_heads * spec.head_dim
    hkv = spec.num_key_value_heads * spec.head_dim
    n_held = spec.experts.n_held
    layer = {"norm1": (d,), "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
             "wo": (hq, d), "norm2": (d,),
             "router": (d, spec.num_experts_routed),
             "w_gate": (n_held, d, f), "w_up": (n_held, d, f),
             "w_down": (n_held, f, d)}
    return {"embed": (spec.vocab_size, d), "head": (spec.vocab_size, d),
            "final_norm": (d,),
            "layers": [dict(layer) for _ in range(spec.num_hidden_layers)]}


@lru_cache(maxsize=None)
def _init_program(spec: BlockSpec):
    """One compiled program a specification: a job after the first
    compiles nothing."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))

    def scale(path) -> float:
        return (spec.embedding_initializer_range
                if path[0].key == "embed" else spec.initializer_range)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            jnp.ones(s, jnp.float32) if len(s) == 1 else
            scale(path) * jax.random.normal(k, s, jnp.float32)
            for (path, s), k in zip(leaves, keys)])

    return make


def init_params(spec: BlockSpec, seed: int) -> dict:
    """normal(0, initializer_range) matrices (the embedding's rows
    normal(0, embedding_initializer_range)) and unit norm gains, float32,
    a pure function of (spec, seed)."""
    return _init_program(spec)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _attention_half(lp, x, cos, sin, *, spec: BlockSpec, kind: str):
    b, s, d = x.shape
    hq, hkv, dh = (spec.num_attention_heads, spec.num_key_value_heads,
                   spec.head_dim)
    with jax.named_scope("seq.attn.proj"):
        y = rms_norm(x, lp["norm1"], spec.rms_norm_eps).astype(COMPUTE)

        def heads(w, n):
            return jnp.einsum("bsd,dhk->bhsk", y,
                              w.astype(COMPUTE).reshape(d, n, dh),
                              preferred_element_type=jnp.float32)

        q = apply_rope(heads(lp["wq"], hq), cos, sin).astype(COMPUTE)
        k = apply_rope(heads(lp["wk"], hkv), cos, sin).astype(COMPUTE)
        v = heads(lp["wv"], hkv).astype(COMPUTE)
    window = spec.window(kind)
    with jax.named_scope("seq.attn.window" if window else "seq.attn.full"):
        o = banded_flash_attention(q, k, v, window, None,
                                   ATTN_BLOCK, ATTN_BLOCK)
    with jax.named_scope("seq.attn.proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o,
                         lp["wo"].astype(COMPUTE).reshape(hq, dh, d),
                         preferred_element_type=jnp.float32)
    return x + out


def _experts_half(lp, h, *, spec: BlockSpec):
    """One history (S, d): -> (h + its held experts' part, counters)."""
    with jax.named_scope("seq.moe.route"):
        y = rms_norm(h, lp["norm2"], spec.rms_norm_eps)
    out, aux = held_moe_ffn(
        {k: lp[k] for k in ("router", "w_gate", "w_up", "w_down")},
        y, spec.experts, COMPUTE)
    return h + out, aux


def hidden_states(params, ids, spec: BlockSpec):
    """ids (B, S) int32 -> (x_L (B, S, d) float32, counters: `counts`
    (layers, B, held experts), `dropped` (layers, B))."""
    tables = rope_tables(spec, ids.shape[1])
    with jax.named_scope("seq.embed"):
        x = params["embed"][ids]
    counters = []
    for lp, kind in zip(params["layers"], spec.layer_types):
        cos, sin = tables[kind]
        x = jax.checkpoint(partial(_attention_half, spec=spec, kind=kind))(
            lp, x, cos, sin)
        x, aux = jax.lax.map(
            jax.checkpoint(partial(_experts_half, lp, spec=spec)), x)
        counters.append(aux)
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *counters)


def head_loss(params, x, targets, spec: BlockSpec):
    """Mean cross-entropy of the untied head over the targets that are
    not PAD, the logits made a chunk of tokens at a time."""
    with jax.named_scope("seq.head_loss"):
        d = x.shape[-1]
        xn = rms_norm(x, params["final_norm"], spec.rms_norm_eps
                      ).astype(COMPUTE).reshape(-1, d)
        tgt = targets.reshape(-1)
        chunk = min(LOSS_CHUNK, xn.shape[0])
        pad = (-xn.shape[0]) % chunk
        if pad:
            xn = jnp.pad(xn, ((0, pad), (0, 0)))
            tgt = jnp.pad(tgt, (0, pad))           # PAD targets: masked
        head = params["head"]

        @jax.checkpoint
        def chunk_sum(carry, xs):
            # the head is rounded inside the chunk: its gradient then
            # adds up over the chunks in float32, not in bfloat16
            x_c, t_c = xs
            logits = jax.lax.dot_general(
                x_c, head.astype(COMPUTE), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, t_c[:, None], axis=1)[:, 0]
            return carry + jnp.sum(jnp.where(t_c != PAD, ce, 0.0)), None

        total, _ = jax.lax.scan(
            chunk_sum, jnp.float32(0.0),
            (xn.reshape(-1, chunk, d), tgt.reshape(-1, chunk)))
        return total / jnp.maximum(jnp.sum(tgt != PAD), 1)


def loss_and_counters(params, tokens, spec: BlockSpec):
    """tokens (B, S + 1): inputs tokens[:, :-1], targets tokens[:, 1:].
    The function the train step differentiates."""
    x, counters = hidden_states(params, tokens[:, :-1], spec)
    return head_loss(params, x, tokens[:, 1:], spec), counters


def last_logits(params, ids, spec: BlockSpec):
    """Next-item logits (B, vocab) after the last position of ids."""
    x, _ = hidden_states(params, ids, spec)
    xn = rms_norm(x[:, -1], params["final_norm"], spec.rms_norm_eps)
    return jnp.dot(xn.astype(COMPUTE), params["head"].astype(COMPUTE).T,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def make_train_step(spec: BlockSpec, learning_rate: float):
    """-> (optimizer, step): `step(params, opt_state, batch)` trains on
    one batch of histories ((B, S + 1) on the device) and returns the new
    state, the loss before the update and the step's counters. params and
    opt_state are donated."""
    optimizer = optax.adam(learning_rate, b1=ADAM_B1)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        (loss, counters), grads = jax.value_and_grad(
            loss_and_counters, has_aux=True)(params, batch, spec)
        with jax.named_scope("seq.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, counters

    return optimizer, step


def epoch_order(n: int, steps: int, batch: int, seed: int) -> np.ndarray:
    """(steps, batch) history indices: a seeded permutation of the
    histories, taken in order and begun again when it runs out, so
    `steps * batch == n` trains on every history once."""
    order = np.random.default_rng(seed).permutation(n)
    return order[np.arange(steps * batch) % n].reshape(steps, batch)


def band_counters(spec: BlockSpec, seq_len: int) -> dict:
    """Key blocks the attention kernels visit in one (batch, head)
    program of a window layer, against the blocks in the band."""
    block = min(ATTN_BLOCK, seq_len)
    padded = seq_len + (-seq_len) % block
    window = spec.sliding_window
    return {"window_blocks_visited": len(
                band_pairs(padded, block, block, window)[0]),
            "window_blocks_in_band": band_blocks(
                padded, block, block, window)}


def train_lm(seqs: np.ndarray, p, lifecycle=None):
    """Train the block stack on (N, S + 1) whole histories for p.steps
    steps of p.batch_size histories. -> (params on the host, last loss).

    Host spans (under `train.algorithms`): `seq.batch` stages every
    step's histories on the device, `seq.dispatch` enqueues the steps,
    `seq.wait` waits for the last one and carries the job's counters."""
    spec = BlockSpec.parse(p.block_spec)
    if jax.process_count() > 1:
        raise ValueError("the block stack trains on one host")
    if (seqs == PAD).any():
        raise ValueError(
            "a history holds PAD: the block stack trains on whole "
            "histories (padding and packing are not supported)")
    if int(seqs.max()) >= spec.vocab_size:
        raise ValueError(
            f"item id {int(seqs.max())} outside the {spec.vocab_size} "
            "vocabulary rows the specification holds")
    steps, batch = p.steps, p.batch_size
    with tracing.span("seq.batch", steps=steps, histories=batch):
        order = epoch_order(len(seqs), steps, batch, p.seed)
        # a batch an array: no program's shapes hold the step count
        batches = [jax.device_put(seqs[rows]) for rows in order]
    optimizer, step = make_train_step(spec, p.learning_rate)
    params = init_params(spec, p.seed)
    opt_state = optimizer.init(params)
    losses, counters = [], []
    with tracing.span("seq.dispatch", steps=steps):
        for s in range(steps):
            params, opt_state, loss, aux = step(
                params, opt_state, batches[s])
            losses.append(loss)
            counters.append(aux)
            if lifecycle is not None and s % 16 == 15:
                lifecycle.check_preemption(s)
                lifecycle.heartbeat(s, steps)
    with tracing.span("seq.wait") as sp:
        losses = np.asarray(jax.device_get(losses), np.float64)
        counters = jax.device_get(counters)
        counts = np.stack([c["counts"] for c in counters])  # steps, L, B, held
        dropped = int(sum(np.sum(c["dropped"]) for c in counters))
        if dropped:
            raise AssertionError(
                f"{dropped} routed tokens found no row: the expert layer "
                "must drop none")
        per_expert = counts.sum(axis=2)                  # a step, a layer
        sp.update(
            tokens_per_step=batch * (seqs.shape[1] - 1),
            loss_first=repr(float(losses[0])),
            loss_last=repr(float(losses[-1])),
            expert_tokens_min=int(per_expert.min()),
            expert_tokens_mean=repr(float(per_expert.mean())),
            expert_tokens_max=int(per_expert.max()),
            # the fullest held expert over the mean one, a step and layer;
            # as a ratio of sums, so a layer that sent none here counts 0
            expert_load_max_over_mean=repr(float(
                per_expert.max(axis=-1).sum()
                / max(per_expert.mean(axis=-1).sum(), 1e-9))),
            dropped_tokens=dropped,
            **band_counters(spec, seqs.shape[1] - 1))
        params = jax.device_get(params)
    return params, float(losses[-1])
