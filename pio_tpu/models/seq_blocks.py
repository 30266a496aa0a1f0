"""The sequence engine's configurable block stack: a decoder-only
language model over item histories, built from a block specification.

`SequenceParams.block_spec` carries the specification: the keys of a
published `config.json`, plus what one rank of a deployment holds of it
(`experts_held` of `num_experts_routed`, `vocab_size` rows of the
vocabulary). With a specification, `train_sequence_model` trains this
stack in place of the SASRec-style encoder of models/sequence.py:

  x_0 = E[ids];  h = x + Attn_l(RMSNorm(x));  x' = h + FFN_l(RMSNorm(h))
  logits = RMSNorm(x_L) W_head^T;  loss = mean next-item cross-entropy

What a specification chooses, by its keys (`BlockSpec.parse` refuses by
name what the stack does not compute):

 * attention: grouped-query heads (`head_dim`, `num_key_value_heads`,
   `layer_types` of window and full layers, rotary positions by layer
   kind, RoPE or YaRN), or latent attention (`q_lora_rank`,
   `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`:
   low-rank query and key-value paths with an RMSNorm each, rotary
   positions on the `qk_rope_head_dim` dimensions only, one rope key
   shared by all heads). Both end in ops/attention.py
   `banded_flash_attention` (Pallas forward and backward);
 * the second half of a layer: a dense SwiGLU of `intermediate_size` in
   the first `first_k_dense_replace` layers, else ops/moe.py
   `held_moe_ffn` (dropless top-k, the held experts' part by a grouped
   matrix product), one history at a time, with `n_shared_experts`
   shared experts (a dense SwiGLU every token takes) beside it;
 * the router: softmax top-k, or with `topk_method: noaux_tc` sigmoid
   scores, selection on score + bias, weights from the unbiased score
   times `routed_scaling_factor`. The bias is a leaf of the parameter
   tree (`router_bias`, so it is persisted with the model) that takes no
   gradient: the train step moves it after the optimizer, `b +=
   ROUTER_BIAS_RATE * sign(mean(c) - c)` over the step's token counts c
   of every routed expert;
 * a multi-token-prediction module (`num_nextn_predict_layers: 1`):
   u_i = [RMSNorm_h(x_L,i) | RMSNorm_e(E[t_i+1])] W_eh, one more layer
   of the kind above, its own final norm, the shared embedding and head;
   it predicts t_i+2, and loss = CE_main + MTP_LOSS_WEIGHT * CE_mtp. A
   history then has one more id (S + 2 for S trained positions);
 * precision: float32 master weights and Adam state, bfloat16 operands,
   float32 accumulation, float32 residual stream, norms and loss;
 * memory: every layer's two halves (the module's too) are recomputed in
   the backward pass (`jax.checkpoint` at their boundaries), but for the
   attention forward kernel: its output o and the rows' log-sum-exp,
   (B, Hq, S) float32, cross the attention half's checkpoint, so the
   kernel runs once a layer and step; the loss is computed over chunks
   of tokens so the (tokens, vocabulary) logits never exist whole.

One chip. Histories are whole (no PAD inside a row): packing and padding
of short histories, the experts' exchange across chips and a cache for
serving (for latent attention: the compressed key-value cache) are not
here (ROADMAP R1, R4).
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
from jax.extend.core import Var
from jax.extend.core.primitives import name_p, remat_p

from pio_tpu.ops.attention import (
    KEPT_RESIDUALS,
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
# what no published config.json has a key for (DeepSeek-V3's report gives
# both); a specification that states another value is refused
ROUTER_BIAS_RATE = 0.001   # a step's move of a router's bias
MTP_LOSS_WEIGHT = 0.3      # the prediction module's loss beside the main one


# keys of architectures this stack has no code for: refused, not ignored
_NOT_COMPUTED = ("index_topk", "index_n_heads", "index_head_dim",
                 "layers_block_type", "mamba_n_heads", "mamba_d_state",
                 "mamba_expand", "linear_conv_kernel_dim")


@dataclass(frozen=True)
class BlockSpec:
    hidden_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]        # one kind per layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int                       # of q.k and of p.v alike
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
    # latent attention (kv_lora_rank 0: grouped-query heads)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the second half of a layer
    dense_layers: int = 0               # leading layers with a dense MLP
    intermediate_size: int = 0          # its width
    n_shared_experts: int = 0
    scoring: str = "softmax"            # "sigmoid" with a router bias
    routed_scaling_factor: float = 1.0
    # the multi-token-prediction module
    mtp_layers: int = 0

    @classmethod
    def parse(cls, spec: str | dict) -> "BlockSpec":
        c = json.loads(spec) if isinstance(spec, str) else dict(spec)
        n_layers = c["num_hidden_layers"]
        latent = bool(c.get("kv_lora_rank"))
        nope, rope_dim, v_dim = (c.get(k, 0) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        scoring = ("sigmoid" if c.get("topk_method") == "noaux_tc"
                   else "softmax")
        wrong = {
            "hidden_act": c.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(c.get("attention_bias", False)),
            "tie_word_embeddings": bool(c.get("tie_word_embeddings", False)),
            "mlp_layer_types": any(
                t != "sparse" for t in
                c.get("mlp_layer_types", [])[:n_layers]),
            "n_group": c.get("n_group", 1) > 1 or c.get("topk_group", 1) > 1,
            "topk_method": c.get("topk_method", "greedy") not in (
                "greedy", "noaux_tc"),
            "scoring_func": c.get("scoring_func", scoring) != scoring,
            "router_bias_update_rate": c.get(
                "router_bias_update_rate", ROUTER_BIAS_RATE
            ) != ROUTER_BIAS_RATE,
            "mtp_loss_weight": c.get(
                "mtp_loss_weight", MTP_LOSS_WEIGHT) != MTP_LOSS_WEIGHT,
            "num_nextn_predict_layers":
                c.get("num_nextn_predict_layers", 0) > 1,
            "partial_rotary_factor": c.get("partial_rotary_factor", 1) != 1,
            # latent attention as computed here: a low-rank query path,
            # every head its own keys and values, q.k as wide as p.v
            "q_lora_rank": latent and not c.get("q_lora_rank"),
            "num_key_value_heads": latent and c.get(
                "num_key_value_heads", c["num_attention_heads"]
            ) != c["num_attention_heads"],
            "v_head_dim": latent and nope + rope_dim != v_dim,
            "rope_scaling": latent and c.get("rope_scaling") is not None,
            "first_k_dense_replace":
                c.get("first_k_dense_replace", 0) > n_layers,
            **{k: True for k in _NOT_COMPUTED if c.get(k)},
        }
        if any(wrong.values()):
            raise ValueError(
                "block specification asks for what this stack does not "
                f"compute: {sorted(k for k, v in wrong.items() if v)}")
        kinds = tuple(c["layer_types"][:n_layers]) if "layer_types" in c \
            or not latent else ("full_attention",) * n_layers
        if len(kinds) != n_layers or set(kinds) - {
                "sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types {kinds} for {n_layers} layers")
        if latent and "sliding_attention" in kinds:
            raise ValueError("layer_types: latent attention has no window")
        n_held = c["num_experts"] if "num_experts" in c \
            else c["n_routed_experts"]
        held = tuple(c.get("experts_held", (0, n_held)))
        if held[1] - held[0] != n_held:
            raise ValueError(
                f"experts_held {held} is not num_experts "
                f"{n_held} experts")
        ropes = c.get("rope_parameters") or {
            "full_attention": {"rope_type": "default",
                               "rope_theta": c["rope_theta"]}}
        return cls(
            hidden_size=c["hidden_size"], num_hidden_layers=n_layers,
            layer_types=kinds,
            num_attention_heads=c["num_attention_heads"],
            num_key_value_heads=c.get("num_key_value_heads",
                                      c["num_attention_heads"]),
            head_dim=v_dim if latent else c["head_dim"],
            sliding_window=c.get("sliding_window") or 0,
            rope=tuple(sorted(
                (kind, tuple(sorted(ropes[kind].items())))
                for kind in set(kinds))),
            rms_norm_eps=c["rms_norm_eps"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_experts_routed=c.get("num_experts_routed", n_held),
            experts_held=held,
            num_experts_per_tok=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"], vocab_size=c["vocab_size"],
            initializer_range=c.get("initializer_range", 0.02),
            embedding_initializer_range=c.get(
                "embedding_initializer_range",
                c.get("initializer_range", 0.02)),
            q_lora_rank=c["q_lora_rank"] if latent else 0,
            kv_lora_rank=c["kv_lora_rank"] if latent else 0,
            qk_nope_head_dim=nope if latent else 0,
            qk_rope_head_dim=rope_dim if latent else 0,
            v_head_dim=v_dim if latent else 0,
            dense_layers=c.get("first_k_dense_replace", 0),
            intermediate_size=c.get("intermediate_size", 0),
            n_shared_experts=c.get("n_shared_experts") or 0,
            scoring=scoring,
            routed_scaling_factor=float(c.get("routed_scaling_factor", 1.0)),
            mtp_layers=c.get("num_nextn_predict_layers", 0))

    @property
    def experts(self) -> HeldExperts:
        return HeldExperts(self.num_experts_routed, self.num_experts_per_tok,
                           self.experts_held, self.norm_topk_prob, MOE_TILE,
                           self.scoring, self.routed_scaling_factor)

    @property
    def router_bias(self) -> bool:
        """Whether a router selects on score + bias (`noaux_tc`)."""
        return self.scoring == "sigmoid"

    @property
    def rope_dim(self) -> int:
        """The dimensions of a head that rotate."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

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
    """kind -> (cos, sin), each (seq_len, rope_dim / 2) float32."""
    out = {}
    for kind, items in spec.rope:
        inv, scale = rope_inv_freq(dict(items), spec.rope_dim)
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

def _layer_shapes(spec: BlockSpec, dense: bool) -> dict:
    d, f, h = (spec.hidden_size, spec.moe_intermediate_size,
               spec.num_attention_heads)
    if spec.kv_lora_rank:
        rq, rkv = spec.q_lora_rank, spec.kv_lora_rank
        dn, dr, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
        layer = {"wq_a": (d, rq), "q_norm": (rq,), "wq_b": (rq, h * (dn + dr)),
                 "wkv_a": (d, rkv + dr), "kv_norm": (rkv,),
                 "wkv_b": (rkv, h * (dn + dv)), "wo": (h * dv, d)}
    else:
        hq = h * spec.head_dim
        hkv = spec.num_key_value_heads * spec.head_dim
        layer = {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d)}
    layer.update({"norm1": (d,), "norm2": (d,)})
    if dense:
        i = spec.intermediate_size
        layer.update({"mlp_gate": (d, i), "mlp_up": (d, i),
                      "mlp_down": (i, d)})
        return layer
    n_held = spec.experts.n_held
    layer.update({"router": (d, spec.num_experts_routed),
                  "w_gate": (n_held, d, f), "w_up": (n_held, d, f),
                  "w_down": (n_held, f, d)})
    if spec.router_bias:
        layer["router_bias"] = (spec.num_experts_routed,)
    if spec.n_shared_experts:
        fs = spec.n_shared_experts * f
        layer.update({"shared_gate": (d, fs), "shared_up": (d, fs),
                      "shared_down": (fs, d)})
    return layer


def param_shapes(spec: BlockSpec) -> dict:
    d = spec.hidden_size
    shapes = {"embed": (spec.vocab_size, d), "head": (spec.vocab_size, d),
              "final_norm": (d,),
              "layers": [_layer_shapes(spec, n < spec.dense_layers)
                         for n in range(spec.num_hidden_layers)]}
    if spec.mtp_layers:
        shapes["mtp"] = {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                         "final_norm": (d,),
                         "layer": _layer_shapes(spec, False)}
    return shapes


def expert_layers(params: dict, spec: BlockSpec) -> list[dict]:
    """The layers that hold a router, in the order of the step's
    counters: the stack's, then the prediction module's."""
    layers = list(params["layers"][spec.dense_layers:])
    return layers + ([params["mtp"]["layer"]] if spec.mtp_layers else [])


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
            jnp.zeros(s, jnp.float32) if path[-1].key == "router_bias" else
            jnp.ones(s, jnp.float32) if len(s) == 1 else
            scale(path) * jax.random.normal(k, s, jnp.float32)
            for (path, s), k in zip(leaves, keys)])

    return make


def init_params(spec: BlockSpec, seed: int) -> dict:
    """normal(0, initializer_range) matrices (the embedding's rows
    normal(0, embedding_initializer_range)), unit norm gains and zero
    router biases, float32, a pure function of (spec, seed)."""
    return _init_program(spec)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _attention_half(lp, x, cos, sin, *, spec: BlockSpec, kind: str):
    if spec.kv_lora_rank:
        return _latent_attention_half(lp, x, cos, sin, spec=spec)
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


def _latent_attention_half(lp, x, cos, sin, *, spec: BlockSpec):
    """Latent attention: queries through a rank-`q_lora_rank` latent,
    keys and values through one of `kv_lora_rank`, an RMSNorm on each;
    a head is [nope | rope] wide, the rope part rotates, and the keys'
    rope part is one vector a position, the same for every head."""
    b, s, d = x.shape
    h, dn, dr, dv = (spec.num_attention_heads, spec.qk_nope_head_dim,
                     spec.qk_rope_head_dim, spec.v_head_dim)
    rkv, eps = spec.kv_lora_rank, spec.rms_norm_eps
    with jax.named_scope("seq.attn.latent"):
        y = rms_norm(x, lp["norm1"], eps).astype(COMPUTE)

        def down(w):
            return jnp.dot(y, w.astype(COMPUTE),
                           preferred_element_type=jnp.float32)

        def up(c, w, width):
            return jnp.einsum(
                "bsr,rhk->bhsk", c.astype(COMPUTE),
                w.astype(COMPUTE).reshape(c.shape[-1], h, width),
                preferred_element_type=jnp.float32)

        q = up(rms_norm(down(lp["wq_a"]), lp["q_norm"], eps),
               lp["wq_b"], dn + dr)
        kv_a = down(lp["wkv_a"])
        kv = up(rms_norm(kv_a[..., :rkv], lp["kv_norm"], eps),
                lp["wkv_b"], dn + dv)
        k_pe = apply_rope(kv_a[:, None, :, rkv:], cos, sin)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], cos, sin)],
            axis=-1).astype(COMPUTE)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, h, s, dr))],
            axis=-1).astype(COMPUTE)
        v = kv[..., dn:].astype(COMPUTE)
    with jax.named_scope("seq.attn.full"):
        # q.k is qk_nope + qk_rope wide and p.v v_head_dim: equal here
        # (BlockSpec.parse holds them to it), so the one kernel serves
        o = banded_flash_attention(q, k, v, None, None,
                                   ATTN_BLOCK, ATTN_BLOCK)
    with jax.named_scope("seq.attn.proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o,
                         lp["wo"].astype(COMPUTE).reshape(h, dv, d),
                         preferred_element_type=jnp.float32)
    return x + out


def _swiglu(y, gate, up, down):
    """(T, d) in COMPUTE -> (T, d) float32: (silu(y Wg) * (y Wu)) Wd."""
    def dot(a, w):
        return jnp.dot(a, w.astype(COMPUTE),
                       preferred_element_type=jnp.float32)

    return dot((jax.nn.silu(dot(y, gate)) * dot(y, up)).astype(COMPUTE), down)


def _experts_half(lp, h, *, spec: BlockSpec):
    """One history (S, d): -> (h + its held experts' part (+ the shared
    expert's), counters)."""
    with jax.named_scope("seq.moe.route"):
        y = rms_norm(h, lp["norm2"], spec.rms_norm_eps)
    out, aux = held_moe_ffn(
        {k: lp[k] for k in ("router", "w_gate", "w_up", "w_down",
                            "router_bias") if k in lp},
        y, spec.experts, COMPUTE)
    if spec.n_shared_experts:
        with jax.named_scope("seq.moe.shared"):
            out = out + _swiglu(y.astype(COMPUTE), lp["shared_gate"],
                                lp["shared_up"], lp["shared_down"])
    return h + out, aux


def _dense_half(lp, h, *, spec: BlockSpec):
    """One history (S, d) through a dense layer's SwiGLU."""
    with jax.named_scope("seq.mlp.dense"):
        y = rms_norm(h, lp["norm2"], spec.rms_norm_eps).astype(COMPUTE)
        return h + _swiglu(y, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])


def _layer(lp, x, table, *, spec: BlockSpec, kind: str, dense: bool):
    """One layer, each half recomputed in the backward pass, the second
    a history at a time. -> (x', the router's counters or None).

    The attention half keeps, beside its input x, what the attention
    forward kernel alone can make (ops/attention.py KEPT_RESIDUALS): o
    (B, Hq, S, D) in COMPUTE and the rows' log-sum-exp (B, Hq, S) float32,
    B * Hq * S * (2 D + 4) bytes a layer, so the backward pass does not
    run the kernel again; the norm, the projections and the rotations are
    recomputed."""
    x = jax.checkpoint(
        partial(_attention_half, spec=spec, kind=kind),
        policy=jax.checkpoint_policies.save_only_these_names(
            *KEPT_RESIDUALS))(lp, x, *table)
    if dense:
        return jax.lax.map(
            jax.checkpoint(partial(_dense_half, lp, spec=spec)), x), None
    return jax.lax.map(
        jax.checkpoint(partial(_experts_half, lp, spec=spec)), x)


def hidden_states(params, ids, spec: BlockSpec):
    """ids (B, S) int32 -> (x_L (B, S, d) float32, counters of the
    layers that route: `counts` (layers, B, held experts), `dropped`
    (layers, B), with a router bias `counts_all` (layers, B, routed))."""
    tables = rope_tables(spec, ids.shape[1])
    with jax.named_scope("seq.embed"):
        x = params["embed"][ids]
    counters = []
    for n, (lp, kind) in enumerate(zip(params["layers"], spec.layer_types)):
        x, aux = _layer(lp, x, tables[kind], spec=spec, kind=kind,
                        dense=n < spec.dense_layers)
        if aux is not None:
            counters.append(aux)
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *counters)


def _mtp_join(mp, x, e, *, spec: BlockSpec):
    u = jnp.concatenate(
        [rms_norm(x, mp["hnorm"], spec.rms_norm_eps),
         rms_norm(e, mp["enorm"], spec.rms_norm_eps)], axis=-1)
    return jnp.dot(u.astype(COMPUTE), mp["eh_proj"].astype(COMPUTE),
                   preferred_element_type=jnp.float32)


def mtp_hidden_states(params, x, next_ids, spec: BlockSpec):
    """The prediction module: x (B, S, d) the stack's output before its
    final norm, next_ids (B, S) the ids one position on. -> (its hidden
    states (B, S, d), its router's counters)."""
    mp = params["mtp"]
    with jax.named_scope("seq.embed"):
        e = params["embed"][next_ids]
    u = jax.checkpoint(partial(_mtp_join, spec=spec))(
        {k: mp[k] for k in ("hnorm", "enorm", "eh_proj")}, x, e)
    kind = spec.layer_types[-1]
    return _layer(mp["layer"], u, rope_tables(spec, x.shape[1])[kind],
                  spec=spec, kind=kind, dense=False)


def head_loss(params, x, targets, spec: BlockSpec, final_norm=None):
    """Mean cross-entropy of the untied head over the targets that are
    not PAD, the logits made a chunk of tokens at a time. `final_norm`:
    another gain than the stack's (the prediction module's own)."""
    with jax.named_scope("seq.head_loss"):
        d = x.shape[-1]
        gain = params["final_norm"] if final_norm is None else final_norm
        xn = rms_norm(x, gain, spec.rms_norm_eps
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
    With a prediction module tokens (B, S + 2): position i of the S
    also predicts tokens[:, i + 2] through the module, the counters gain
    the module's router as their last layer and `losses` (main, module),
    and the loss is main + MTP_LOSS_WEIGHT * module. The function the
    train step differentiates."""
    s = tokens.shape[1] - 1 - spec.mtp_layers
    x, counters = hidden_states(params, tokens[:, :s], spec)
    loss = head_loss(params, x, tokens[:, 1:s + 1], spec)
    if not spec.mtp_layers:
        return loss, counters
    with jax.named_scope("seq.mtp"):
        x2, aux = mtp_hidden_states(params, x, tokens[:, 1:s + 1], spec)
        loss2 = head_loss(params, x2, tokens[:, 2:], spec,
                          params["mtp"]["final_norm"])
    counters = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b[None]]), counters, aux)
    counters["losses"] = jnp.stack([loss, loss2])
    return loss + MTP_LOSS_WEIGHT * loss2, counters


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
            if spec.router_bias:
                params = balance_routers(params, counters["counts_all"],
                                         spec)
        return params, opt_state, loss, counters

    return optimizer, step


def bias_step(counts, rate: float):
    """The aux-loss-free balancing rule: (..., routed) token counts of a
    step -> what is added to the router's bias: +rate for an expert under
    the mean load, -rate for one over it."""
    counts = counts.astype(jnp.float32)
    return rate * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)


def balance_routers(params, counts_all, spec: BlockSpec):
    """params with every router's bias moved by `bias_step` of its
    counts (counts_all: (routers, B, routed), in `expert_layers`' order).
    The bias takes no gradient, so the optimizer left it where it was."""
    moves = bias_step(counts_all.sum(axis=1), ROUTER_BIAS_RATE)

    def moved(lp, move):
        return {**lp, "router_bias": lp["router_bias"] + move}

    n_stack = spec.num_hidden_layers - spec.dense_layers
    layers = list(params["layers"])
    for i in range(n_stack):
        n = spec.dense_layers + i
        layers[n] = moved(layers[n], moves[i])
    out = {**params, "layers": layers}
    if spec.mtp_layers:
        out["mtp"] = {**params["mtp"], "layer": moved(
            params["mtp"]["layer"], moves[n_stack])}
    return out


def history_ids(spec: BlockSpec, positions: int) -> int:
    """The ids of a history that trains `positions` positions: each one's
    next id, and with a prediction module the one after it."""
    return positions + 1 + spec.mtp_layers


def epoch_order(n: int, steps: int, batch: int, seed: int) -> np.ndarray:
    """(steps, batch) history indices: a seeded permutation of the
    histories, taken in order and begun again when it runs out, so
    `steps * batch == n` trains on every history once."""
    order = np.random.default_rng(seed).permutation(n)
    return order[np.arange(steps * batch) % n].reshape(steps, batch)


def tiles_used_share(counts: np.ndarray, held: HeldExperts,
                     positions: int) -> float:
    """Tiles of the sorted buffer that hold a group, which are the tiles
    the grouped products and the move into the buffer visit, over the
    buffer's tiles. counts (..., held experts): a history's tokens per
    held expert; the mean over everything before. An empty group owns
    one tile."""
    tm = held.tile_rows
    used = np.maximum(-(-counts // tm), 1).sum(axis=-1)
    return float(used.mean() / (held.row_capacity(positions) // tm))


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


def attention_counters(jaxpr) -> dict:
    """What a traced step program holds of the attention forward, read
    off its equations (through every jaxpr they hold): the calls of the
    forward kernel, and the bytes of the arrays named KEPT_RESIDUALS that
    a checkpoint's backward pass takes in, which are the ones kept."""
    found = {"attn_fwd_kernels": 0, "attn_residual_bytes": 0}

    def walk(jaxpr, named):
        def is_named(v):
            return isinstance(v, Var) and v in named

        for eqn in jaxpr.eqns:
            if eqn.primitive is name_p:
                if eqn.params["name"] in KEPT_RESIDUALS:
                    named.add(eqn.outvars[0])
            elif eqn.primitive is jax.lax.reduce_precision_p:
                # jax passes a kept array that the forward pass reads too
                # through one that changes nothing
                if is_named(eqn.invars[0]):
                    named.add(eqn.outvars[0])
            elif eqn.primitive is remat_p:
                found["attn_residual_bytes"] += sum(
                    v.aval.size * v.aval.dtype.itemsize
                    for v in eqn.invars if is_named(v))
            elif (eqn.primitive.name == "pallas_call"
                  and eqn.params["name"] == "flash_attention_fwd"):
                found["attn_fwd_kernels"] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, set())

    walk(jaxpr, set())
    return found


@lru_cache(maxsize=None)
def step_attention_counters(spec: BlockSpec, learning_rate: float,
                            batch_shape: tuple) -> dict:
    """`attention_counters` of the train step's program for batches of
    that shape, read once a process: the step is traced here, on shapes
    alone, and its first call then finds the trace made."""
    optimizer, step = make_train_step(spec, learning_rate)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), param_shapes(spec),
        is_leaf=lambda x: isinstance(x, tuple))
    traced = step.trace(params, jax.eval_shape(optimizer.init, params),
                        jax.ShapeDtypeStruct(batch_shape, jnp.int32))
    return attention_counters(traced.jaxpr.jaxpr)


def train_lm(seqs: np.ndarray, p, lifecycle=None):
    """Train the block stack on (N, `history_ids`) whole histories for
    p.steps steps of p.batch_size histories. -> (params on the host,
    last loss).

    Host spans (under `train.algorithms`): `seq.batch` stages every
    step's histories on the device, `seq.init` makes the step and the
    initial parameters and optimizer state (a process's first job traces
    and loads the initialisers here), `seq.dispatch` enqueues the steps
    (the first job gets the step's program ready, and reads
    `step_attention_counters` off its trace), `seq.wait` waits for the
    last one and carries the job's counters, `seq.d2h` brings the
    parameters to the host."""
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
    positions = seqs.shape[1] - 1 - spec.mtp_layers
    if positions < 1:
        raise ValueError(f"histories of {seqs.shape[1]} ids train nothing")
    steps, batch = p.steps, p.batch_size
    with tracing.span("seq.batch", steps=steps, histories=batch):
        order = epoch_order(len(seqs), steps, batch, p.seed)
        # a batch an array: no program's shapes hold the step count
        batches = [jax.device_put(seqs[rows]) for rows in order]
    with tracing.span("seq.init"):
        optimizer, step = make_train_step(spec, p.learning_rate)
        params = init_params(spec, p.seed)
        opt_state = optimizer.init(params)
    losses, counters = [], []
    with tracing.span("seq.dispatch", steps=steps):
        program = step_attention_counters(spec, p.learning_rate,
                                          batches[0].shape)
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
        held = spec.experts
        sp.update(
            tokens_per_step=batch * positions,
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
            # choices sent to held experts over the held experts' share of
            # all choices: 1.0 is what a balanced router sends this rank
            expert_tokens_held_share=repr(float(
                per_expert.sum() / (steps * counts.shape[1] * batch
                                    * positions * held.top_k
                                    * held.n_held / held.n_routed))),
            # a history's share of the worst-case buffer that holds rows;
            # only every choice of every token held fills it
            expert_tiles_used_share=repr(tiles_used_share(
                counts, held, positions)),
            dropped_tokens=dropped, **program)
        if "sliding_attention" in spec.layer_types:
            sp.update(**band_counters(spec, positions))
        if spec.mtp_layers:
            parts = np.stack([c["losses"] for c in counters]).astype(
                np.float64)
            sp.update(loss_main_first=repr(float(parts[0, 0])),
                      loss_mtp_first=repr(float(parts[0, 1])),
                      loss_main_last=repr(float(parts[-1, 0])),
                      loss_mtp_last=repr(float(parts[-1, 1])))
        if spec.router_bias:
            sp.update(router_bias_abs_max=repr(float(max(
                np.abs(bias).max() for bias in jax.device_get(
                    [lp["router_bias"]
                     for lp in expert_layers(params, spec)])))))
    with tracing.span("seq.d2h") as sp:
        params = jax.device_get(params)
        sp["bytes"] = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    return params, float(losses[-1])
