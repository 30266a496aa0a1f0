"""The sequence engine's configurable block stack: a decoder-only
language model over item histories, built from a block specification.

`SequenceParams.block_spec` carries the specification: the keys of a
published `config.json`, plus what one rank of a deployment holds of it
(`experts_held` of `num_experts_routed`, `vocab_size` rows of the
vocabulary). With a specification, `train_sequence_model` trains this
stack in place of the SASRec-style encoder of models/sequence.py:

  x_0 = E[ids];  h = x + Attn_l(RMSNorm(x));  x' = h + FFN_l(RMSNorm(h))
  logits = RMSNorm(x_L) W_head^T;  loss = mean next-item cross-entropy

With `total_ut_steps` (T) the stack is a looped one: the L layers run T
times over one set of parameters, every pass ends in an exit, and a
learned gate gives each token a distribution over the T exits:

  x = E[ids]
  for t = 1..T, the same parameters every t:
      for l = 1..L:
          h = x + N1b_l(Attn_l(N1a_l(x)))
          x = h + N2b_l(SwiGLU_l(N2a_l(h)))
      y_t = RMSNorm_final(x);  x = y_t         (the normed state goes on)
      CE_t[i] = -log softmax(y_t[i] W_head^T)[target_i]
      lam_t[i] = sigmoid(y_t[i] . w_gate + b_gate)
  p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j) (1 < t < T);
  p_T = prod_{j<T}(1 - lam_j)                  (the rest of the mass)
  loss = mean_i [ sum_t p_t[i] CE_t[i] - EXIT_ENTROPY_WEIGHT * H(p[i]) ]

With `hybrid_override_pattern` the stack is one of single-mixer blocks:
block l is the mixer its letter of the pattern names, with a pre-norm and
a residual add, and there are no two halves:

  x = E[ids];  x <- x + Mixer_l(RMSNorm_l(x));  logits = RMSNorm(x_L) W_head^T
  "M", Mamba-2, u the normed input, H heads of P, G groups of N:
      [z | xBC | dt] = u W_in            (H P | H P + 2 G N | H columns)
      xBC_t <- silu(sum_k w_k xBC_{t-K+1+k} + b)    (K taps, a channel)
      [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
      out = RMSNorm_groups(y * silu(z)) W_out       (G groups, one gain)
    the scan is ops/ssd.py `ssd_scan`, computed in chunks of `chunk_size`;
    the convolution and the norm are ops/ssm_rows.py's passes over u W_in
    where it lies, if the widths are whole lane tiles;
  "E": the expert layer below (sigmoid scores, selection on score + bias,
    weights from the unbiased scores, normalised, times the factor), an
    expert and the shared expert `W_down relu(W_up v)^2`: no gate;
  "*": grouped-query attention, full and causal, no rotary positions.

With `num_attention_heads_per_layer`, `rope_parameters[kind].
partial_rotary_factor`, `use_qk_norm` and `gating` a layer's attention
half is the gated grouped-query one, kind k of layer l from
`layer_types`, H_k query heads over G key-value heads of D, y =
RMSNorm(x):

  q = y W_q -> (S, H_k, D);  k = y W_k, v = y W_v -> (S, G, D)
  q <- RMSNorm_D(q) g_q;  k <- RMSNorm_D(k) g_k    (a head's own D)
  q, k <- the first R_k = factor_k D dimensions rotate as halves (pairs
          (i, i + R_k / 2)), frequencies over R_k; R_k .. D pass
  o_h = softmax(q_h k_g^T / sqrt(D) + mask_k) v_g,   g = h // (H_k / G)
  gamma = softplus(y W_g) -> (S, H_k);  o_h <- gamma_h o_h
  h = x + concat_h(o_h) W_o

and `mlp_layer_types` says which layers' second half is dense (a leading
run of "dense") and which routes ("sparse").

What a specification chooses, by its keys (`BlockSpec.parse` refuses by
name what the stack does not compute):

 * attention: grouped-query heads (`head_dim`, `num_key_value_heads`,
   `layer_types` of window and full layers, rotary positions by layer
   kind, RoPE or YaRN; by layer kind too the number of query heads,
   `num_attention_heads_per_layer`: one count a kind, whole groups of
   the key-value heads, and the width that rotates,
   `rope_parameters[kind].partial_rotary_factor`; `use_qk_norm`: an
   RMSNorm over a head's own dimensions on every query and key head
   before it rotates, leaves `q_head_norm` / `k_head_norm`; `gating`
   true or "per-head": a gate a head on the kernel's output, leaf
   `w_gate_heads` (d, H_k), the product made in float32 and rounded once
   as W_o's operand), or latent attention (`q_lora_rank`,
   `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`:
   low-rank query and key-value paths with an RMSNorm each, rotary
   positions on the `qk_rope_head_dim` dimensions only, one rope key
   shared by all heads). Both end in ops/attention.py
   `banded_flash_attention` (one Pallas kernel forward, one backward);
 * the second half of a layer: a dense SwiGLU of `intermediate_size`, in
   every layer of a specification without expert keys (no `num_experts`
   / `n_routed_experts`: no router, no held experts) and in the first
   `first_k_dense_replace` layers of one with them (or the leading run
   of "dense" in its `mlp_layer_types`); else ops/moe.py
   `held_moe_ffn` (dropless top-k, the held experts' part by a grouped
   matrix product), one history at a time, with `n_shared_experts`
   shared experts (a dense SwiGLU every token takes; or one of
   `shared_expert_intermediate_size`) beside it;
 * the router: softmax top-k, or with `topk_method: noaux_tc` sigmoid
   scores, selection on score + bias, weights from the unbiased score
   times `routed_scaling_factor` (or `moe_routed_scaling_factor`). The
   bias is a leaf of the parameter tree (`router_bias`, so it is
   persisted with the model) that takes no
   gradient: the train step moves it after the optimizer, `b +=
   ROUTER_BIAS_RATE * sign(mean(c) - c)` over the step's token counts c
   of every routed expert;
 * a multi-token-prediction module (`num_nextn_predict_layers: 1`):
   u_i = [RMSNorm_h(x_L,i) | RMSNorm_e(E[t_i+1])] W_eh, one more layer
   of the kind above, its own final norm, the shared embedding and head;
   it predicts t_i+2, and loss = CE_main + MTP_LOSS_WEIGHT * CE_mtp. A
   history then has one more id (S + 2 for S trained positions);
 * the loop (`total_ut_steps`: T, the equations above): a Python loop
   over T whose body is the L layers, so the step's program holds the
   stack T times (the faster of the two on the chip: `looped_states`)
   and the weights' gradients add up over the passes; a layer then has
   four norms (`norm1_post` / `norm2_post`
   on each half's output before the residual add), the final norm is
   inside the loop, and the loss is the one over the T exits: a head
   loss a pass and token, weighted by the exit gate's distribution
   (`exit_gate`, `exit_bias`: a Linear(d, 1) on the normed state), less
   the entropy term. Only the (T, B, S) losses and gate logits leave the
   loop. Serving runs the T passes and reads exit T
   (`early_exit_threshold` 1: no early exit; a lower one is refused);
 * single-mixer blocks (`hybrid_override_pattern`, the equations above;
   `mamba_num_heads`, `mamba_head_dim`, `ssm_state_size`, `n_groups`,
   `conv_kernel`, `chunk_size`, `use_conv_bias`; `mlp_hidden_act: relu2`
   and `moe_shared_expert_intermediate_size` for the experts;
   `attention_rope: false`, which such a specification has to state). A
   block is one `jax.checkpoint`, an "E" or "M" block a history at a
   time; what crosses it beside the block's input: attention's o and
   log-sum-exp, and the scan's chunk states (B, S / chunk, H, P, N)
   float32 (ops/ssd.py KEPT_STATES), so the backward pass does not run
   the carry over the chunks again. A mixer's own parameters are drawn as
   its family draws them (`init_params`). Serving runs the whole scan a
   query;
 * precision: float32 master weights and Adam state, bfloat16 operands,
   float32 accumulation, float32 residual stream, norms and loss; of a
   scan also softplus, the decays' running sums, the carried states and
   their recurrence;
 * memory: every layer's two halves (the module's too; a looped stack's
   layer as one) are recomputed in the backward pass (`jax.checkpoint`
   at their boundaries), but for the attention forward kernel: its output o and the rows' log-sum-exp,
   (B, Hq, S) float32, cross the attention half's checkpoint, so the
   kernel runs once a layer and step; the loss is computed over chunks
   of tokens so the (tokens, vocabulary) logits never exist whole.

One chip. Histories are whole (no PAD inside a row): packing and padding
of short histories, the experts' exchange across chips, a cache for
serving (for latent attention: the compressed key-value cache), early
exit at serve time and the exit gate's second training stage are not
here (ROADMAP R1, R4, R15); nor, for a scan, recurrent state kept a user
at serve time, a state reset and a convolution cut at a packed
history's boundaries, a plain-MLP ("-") block, or a tied head (R3, R4 e).
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

from pio_tpu.ops import ssm_rows
from pio_tpu.ops.attention import (
    KEPT_RESIDUALS,
    band_blocks,
    band_pairs,
    banded_flash_attention,
)
from pio_tpu.ops.moe import HeldExperts, held_moe_ffn
from pio_tpu.ops.ssd import KEPT_STATES, ssd_scan
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
# the looped family's report, first training stage (config.json has no key)
EXIT_ENTROPY_WEIGHT = 0.1  # beta: the exit distribution's entropy in the loss


# keys of architectures this stack has no code for: refused, not ignored.
# The state-space layers computed are the ones `hybrid_override_pattern`
# places, with `mamba_num_heads`, `mamba_head_dim`, `ssm_state_size`,
# `n_groups`, `conv_kernel` and `chunk_size`; other families' keys for
# such layers stay here
_NOT_COMPUTED = ("index_topk", "index_n_heads", "index_head_dim",
                 "layers_block_type", "mamba_n_heads", "mamba_d_state",
                 "mamba_expand", "linear_conv_kernel_dim")
# the kinds of block a `hybrid_override_pattern` may place: a Mamba-2
# mixer, routed experts beside a shared one, grouped-query attention
BLOCK_KINDS = "ME*"


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
    # the looped stack (`total_ut_steps`): passes over the one set of
    # layers, each with its exit; 0 where the stack is not a looped one.
    # A looped layer has the two post-norms, and the loss is the one
    # over the exits, with its gate
    loop_steps: int = 0
    # a stack of single-mixer blocks (`hybrid_override_pattern`): one kind
    # a block, "M", "E" or "*" (BLOCK_KINDS); () where a layer is the two
    # halves above. Its attention has no rotary positions
    block_kinds: tuple[str, ...] = ()
    # the Mamba-2 mixer: heads of head_dim, a state of `ssm_state_size`
    # a head and head dimension, `n_groups` groups that share B and C
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 0
    conv_kernel: int = 0
    chunk_size: int = 0
    use_conv_bias: bool = True
    time_step: tuple[float, float, float] = (0.001, 0.1, 0.0001)  # min, max, floor
    # the experts' and the shared expert's form: "swiglu" (three
    # matrices) or "relu2" (`W_down relu(W_up x)^2`, two)
    expert_act: str = "swiglu"
    shared_intermediate_size: int = 0   # the shared expert's width
    # grouped-query heads that differ by layer kind
    # (`num_attention_heads_per_layer`): kind -> query heads, and
    # (`rope_parameters[kind].partial_rotary_factor`) kind -> the leading
    # dimensions of a head that rotate; () where every kind has
    # `num_attention_heads` heads that rotate whole
    heads_by_kind: tuple[tuple[str, int], ...] = ()
    rotary_by_kind: tuple[tuple[str, int], ...] = ()
    attn_gate: bool = False             # `gating`: a gate a head on o
    head_norms: bool = False            # `use_qk_norm`: RMSNorm of q, k heads

    @classmethod
    def parse(cls, spec: str | dict) -> "BlockSpec":
        c = json.loads(spec) if isinstance(spec, str) else dict(spec)
        n_layers = c["num_hidden_layers"]
        # a pattern of single-mixer blocks, of which the first n_layers
        pattern = c.get("hybrid_override_pattern")
        hybrid = pattern is not None
        blocks = tuple(pattern[:n_layers]) if hybrid else ()
        latent = bool(c.get("kv_lora_rank"))
        nope, rope_dim, v_dim = (c.get(k, 0) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        # the pattern's family routes as `noaux_tc` does and has no key
        scoring = ("sigmoid" if hybrid or c.get("topk_method") == "noaux_tc"
                   else "softmax")
        # no expert keys at all: every layer a dense SwiGLU
        experts_key = next((k for k in ("num_experts", "n_routed_experts")
                            if k in c), None)
        looped = "total_ut_steps" in c
        # `mlp_layer_types`: a leading run of "dense", then "sparse"
        mlp_kinds = list(c.get("mlp_layer_types", ()))[:n_layers]
        lead = next((n for n, t in enumerate(mlp_kinds) if t != "dense"),
                    len(mlp_kinds))
        n_dense = 0 if hybrid else (
            c.get("first_k_dense_replace", lead) if experts_key
            else n_layers)
        # grouped-query heads by layer kind: query heads, rotating width
        grouped = not (hybrid or latent)
        per_layer = c.get("num_attention_heads_per_layer")
        layer_kinds = list(c.get("layer_types", ()))[:n_layers]
        heads: dict = {}
        for kind, count in zip(layer_kinds, per_layer or ()):
            heads.setdefault(kind, set()).add(count)
        ropes_in = c.get("rope_parameters") or {}
        factors = {
            kind: ropes_in[kind]["partial_rotary_factor"]
            for kind in set(layer_kinds)
            if "partial_rotary_factor" in ropes_in.get(kind, {})}
        top_factor = c.get("partial_rotary_factor", 1)
        gating = c.get("gating", False)
        shared_key = next((k for k in (
            "moe_shared_expert_intermediate_size",
            "shared_expert_intermediate_size") if c.get(k)), None)
        scale_keys = [k for k in ("routed_scaling_factor",
                                  "moe_routed_scaling_factor") if k in c]
        # the feed-forward activation, under either of its keys: relu2
        # (two matrices, no gate) in experts and shared experts alone
        acts = {k: c[k] for k in ("hidden_act", "mlp_hidden_act") if k in c}
        act = c.get("mlp_hidden_act", c.get("hidden_act", "silu"))
        computed = ("silu",) if n_dense else ("silu", "relu2")
        wrong = {
            **{k: v != act or v not in computed for k, v in acts.items()},
            "mamba_hidden_act": c.get("mamba_hidden_act", "silu") != "silu",
            "attention_bias": bool(c.get("attention_bias", False)),
            "mamba_proj_bias": bool(c.get("mamba_proj_bias", False)),
            "use_bias": bool(c.get("use_bias", False)),
            "mlp_bias": bool(c.get("mlp_bias", False)),
            # single-mixer blocks as computed here: the three kinds, the
            # attention among them without rotary positions and stated so,
            # a scan's heads in whole groups
            "hybrid_override_pattern": hybrid and (
                len(blocks) != n_layers or bool(set(blocks) - set(BLOCK_KINDS))),
            "attention_rope": hybrid == bool(c.get("attention_rope", True)),
            "layer_types": hybrid and "layer_types" in c,
            "n_groups": "M" in blocks and bool(
                c["mamba_num_heads"] % c["n_groups"]),
            "tie_word_embeddings": bool(c.get("tie_word_embeddings", False)),
            # a dense layer after a sparse one, a count of leading dense
            # layers that `first_k_dense_replace` gives otherwise, or
            # expert keys and no sparse layer for them
            "mlp_layer_types": bool(set(mlp_kinds) - {"dense", "sparse"})
                or "dense" in mlp_kinds[lead:]
                or bool(lead) and (hybrid or looped or n_dense != lead or (
                    experts_key is not None and lead == n_layers)),
            # one count a kind, of whole groups of key-value heads
            "num_attention_heads_per_layer": per_layer is not None and (
                not grouped or looped or len(per_layer) < n_layers
                or any(len(v) > 1 or next(iter(v)) % c.get(
                    "num_key_value_heads", 1) for v in heads.values())),
            # `gating`: a gate a head (true or "per-head"), on grouped-
            # query heads
            "gating": gating not in (False, True, "per-head")
                or bool(gating) and not grouped,
            "gating_types": bool(set(c.get("gating_types", ())) - {
                "per_head"}) or "gating_types" in c and not gating,
            "use_qk_norm": bool(c.get("use_qk_norm")) and not grouped,
            "moe_apply_router_weight_on_input": bool(c.get(
                "moe_apply_router_weight_on_input", False)),
            "moe_router_logit_softcapping": bool(c.get(
                "moe_router_logit_softcapping", 0)),
            "moe_routed_scaling_factor": len(
                {float(c[k]) for k in scale_keys}) > 1,
            "n_group": c.get("n_group", 1) > 1 or c.get("topk_group", 1) > 1,
            "topk_method": c.get("topk_method", "greedy") not in (
                "greedy", "noaux_tc"),
            "scoring_func": c.get("scoring_func", scoring) != scoring,
            "router_bias_update_rate": c.get(
                "router_bias_update_rate", ROUTER_BIAS_RATE
            ) != ROUTER_BIAS_RATE,
            "mtp_loss_weight": c.get(
                "mtp_loss_weight", MTP_LOSS_WEIGHT) != MTP_LOSS_WEIGHT,
            # a rotating width by layer kind: every kind states its
            # own (an even number of a head's dimensions) and the
            # top-level key, the model's default, is one of them;
            # without them the top-level key is 1
            "partial_rotary_factor": top_factor != 1 and not (
                grouped and factors and set(factors) == set(layer_kinds)
                and top_factor in factors.values()),
            "rope_parameters": bool(factors) and (
                not grouped or looped or any(
                    f <= 0 or f > 1 or (f * c["head_dim"]) % 2
                    for f in factors.values())),
            # latent attention as computed here: a low-rank query path,
            # every head its own keys and values, q.k as wide as p.v
            "q_lora_rank": latent and not c.get("q_lora_rank"),
            "num_key_value_heads": latent and c.get(
                "num_key_value_heads", c["num_attention_heads"]
            ) != c["num_attention_heads"],
            "v_head_dim": latent and nope + rope_dim != v_dim,
            "rope_scaling": latent and c.get("rope_scaling") is not None,
            "first_k_dense_replace": c.get("first_k_dense_replace", 0) > (
                0 if hybrid else n_layers),
            "intermediate_size": experts_key is None
                and not c.get("intermediate_size"),
            # the looped stack as computed here: dense layers of
            # grouped-query heads, every pass to its end, no module
            "total_ut_steps": looped and (c["total_ut_steps"] < 1 or hybrid),
            "early_exit_threshold": c.get("early_exit_threshold", 1) < 1,
            "exit_entropy_weight": c.get(
                "exit_entropy_weight", EXIT_ENTROPY_WEIGHT
            ) != EXIT_ENTROPY_WEIGHT,
            experts_key or "num_experts": looped and experts_key is not None,
            "kv_lora_rank": (looped or hybrid) and latent,
            # a prediction module's layer is an expert layer
            "num_nextn_predict_layers": c.get(
                "num_nextn_predict_layers", 0) > (
                    0 if looped or hybrid or experts_key is None else 1),
            **{k: True for k in _NOT_COMPUTED if c.get(k)},
        }
        if any(wrong.values()):
            raise ValueError(
                "block specification asks for what this stack does not "
                f"compute: {sorted(k for k, v in wrong.items() if v)} "
                "(state-space layers are computed for the family of "
                "`hybrid_override_pattern` alone: Mamba-2 mixers by "
                "`mamba_num_heads`, `mamba_head_dim`, `ssm_state_size`, "
                "`n_groups`, `conv_kernel`, `chunk_size`)")
        if hybrid:
            kinds = ()
        elif "layer_types" in c or not latent:
            kinds = tuple(c["layer_types"][:n_layers])
        else:
            kinds = ("full_attention",) * n_layers
        if not hybrid and (len(kinds) != n_layers or set(kinds) - {
                "sliding_attention", "full_attention"}):
            raise ValueError(f"layer_types {kinds} for {n_layers} layers")
        if latent and "sliding_attention" in kinds:
            raise ValueError("layer_types: latent attention has no window")
        n_held = c[experts_key] if experts_key else 0
        held = tuple(c.get("experts_held", (0, n_held)))
        if held[1] - held[0] != n_held:
            raise ValueError(
                f"experts_held {held} is not num_experts "
                f"{n_held} experts")
        ropes = {} if hybrid else c.get("rope_parameters") or {
            "full_attention": {"rope_type": "default",
                               "rope_theta": c["rope_theta"]}}
        # a shared expert by its width alone is one shared expert
        n_shared = (c.get("n_shared_experts")
                    or int(shared_key is not None)) if n_held else 0
        head_dim = v_dim if latent else c["head_dim"]
        mamba = {k: c[k] for k in (
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
            "conv_kernel", "chunk_size")} if "M" in blocks else {}
        return cls(
            hidden_size=c["hidden_size"], num_hidden_layers=n_layers,
            layer_types=kinds,
            num_attention_heads=c["num_attention_heads"],
            num_key_value_heads=c.get("num_key_value_heads",
                                      c["num_attention_heads"]),
            head_dim=head_dim,
            sliding_window=c.get("sliding_window") or 0,
            rope=tuple(sorted(
                (kind, tuple(sorted(ropes[kind].items())))
                for kind in set(kinds))),   # none where kinds is ()
            rms_norm_eps=c["rms_norm_eps"] if "rms_norm_eps" in c
            else c["layer_norm_epsilon"],
            moe_intermediate_size=c["moe_intermediate_size"] if n_held else 0,
            num_experts_routed=c.get("num_experts_routed", n_held),
            experts_held=held,
            num_experts_per_tok=c["num_experts_per_tok"] if n_held else 0,
            norm_topk_prob=bool(n_held and c["norm_topk_prob"]),
            vocab_size=c["vocab_size"],
            initializer_range=c.get("initializer_range", 0.02),
            embedding_initializer_range=c.get(
                "embedding_initializer_range",
                c.get("initializer_range", 0.02)),
            q_lora_rank=c["q_lora_rank"] if latent else 0,
            kv_lora_rank=c["kv_lora_rank"] if latent else 0,
            qk_nope_head_dim=nope if latent else 0,
            qk_rope_head_dim=rope_dim if latent else 0,
            v_head_dim=v_dim if latent else 0,
            dense_layers=n_dense,
            intermediate_size=c.get("intermediate_size", 0),
            n_shared_experts=c.get("n_shared_experts") or n_shared,
            scoring=scoring,
            routed_scaling_factor=float(
                c[scale_keys[0]] if scale_keys else 1.0),
            mtp_layers=c.get("num_nextn_predict_layers", 0),
            loop_steps=c.get("total_ut_steps", 0),
            block_kinds=blocks, **mamba,
            use_conv_bias=bool(c.get("use_conv_bias", True)),
            time_step=tuple(float(c.get(k, v)) for k, v in (
                ("time_step_min", 0.001), ("time_step_max", 0.1),
                ("time_step_floor", 0.0001))),
            expert_act="relu2" if act == "relu2" else "swiglu",
            shared_intermediate_size=(
                c[shared_key] if shared_key
                else n_shared * c["moe_intermediate_size"]
            ) if n_shared else 0,
            heads_by_kind=tuple(sorted(
                (kind, next(iter(v))) for kind, v in heads.items())),
            rotary_by_kind=tuple(sorted(
                (kind, int(f * head_dim)) for kind, f in factors.items())),
            attn_gate=bool(gating), head_norms=bool(c.get("use_qk_norm")))

    @property
    def experts(self) -> HeldExperts:
        return HeldExperts(self.num_experts_routed, self.num_experts_per_tok,
                           self.experts_held, self.norm_topk_prob, MOE_TILE,
                           self.scoring, self.routed_scaling_factor,
                           self.expert_act)

    @property
    def router_bias(self) -> bool:
        """Whether a router selects on score + bias (`noaux_tc`)."""
        return self.scoring == "sigmoid"

    @property
    def rope_dim(self) -> int:
        """The dimensions of a head that rotate, where every layer kind
        has the same (`rotary_dims` where not)."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    def rotary_dims(self, kind: str) -> int:
        """The leading dimensions of a head that rotate in a layer of
        that kind; the rest pass."""
        return dict(self.rotary_by_kind).get(kind, self.rope_dim)

    def q_heads(self, kind: str) -> int:
        """Query heads of a layer of that kind."""
        return dict(self.heads_by_kind).get(kind, self.num_attention_heads)

    def window(self, kind: str) -> int | None:
        return self.sliding_window if kind == "sliding_attention" else None

    @property
    def router_blocks(self) -> tuple[int, ...]:
        """The stack's layers that hold a router, by index."""
        if self.block_kinds:
            return tuple(n for n, k in enumerate(self.block_kinds)
                         if k == "E")
        if not self.experts.n_held:
            return ()
        return tuple(range(self.dense_layers, self.num_hidden_layers))

    @property
    def mamba_inner(self) -> int:
        """Channels of a Mamba-2 mixer's x, z and y."""
        return self.mamba_num_heads * self.mamba_head_dim


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
    """kind -> (cos, sin), each (seq_len, rotary_dims(kind) / 2)
    float32: the inverse frequencies, and YaRN's correction range, are
    over the dimensions that rotate."""
    out = {}
    for kind, items in spec.rope:
        inv, scale = rope_inv_freq(dict(items), spec.rotary_dims(kind))
        angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv[None]
        out[kind] = (np.float32(np.cos(angle) * scale),
                     np.float32(np.sin(angle) * scale))
    return out


def apply_rope(x, cos, sin):
    """x: (B, H, S, D) float32; cos and sin (S, R / 2): the first R
    dimensions of D rotate, their two halves as pairs (i, i + R / 2),
    and the dimensions R .. D pass."""
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:2 * half]
    turned = [a * cos - b * sin, b * cos + a * sin]
    if 2 * half < x.shape[-1]:
        turned.append(x[..., 2 * half:])
    return jnp.concatenate(turned, axis=-1)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _expert_shapes(spec: BlockSpec) -> dict:
    """A router, the held experts and the shared expert: three matrices
    an expert, or two with `expert_act` "relu2" (no gate)."""
    d, f = spec.hidden_size, spec.moe_intermediate_size
    n_held = spec.experts.n_held
    gated = spec.expert_act == "swiglu"
    shapes = {"router": (d, spec.num_experts_routed),
              "w_up": (n_held, d, f), "w_down": (n_held, f, d)}
    if gated:
        shapes["w_gate"] = (n_held, d, f)
    if spec.router_bias:
        shapes["router_bias"] = (spec.num_experts_routed,)
    if spec.n_shared_experts:
        fs = spec.shared_intermediate_size
        shapes.update({"shared_up": (d, fs), "shared_down": (fs, d)})
        if gated:
            shapes["shared_gate"] = (d, fs)
    return shapes


def _block_shapes(spec: BlockSpec, kind: str) -> dict:
    """One single-mixer block of a pattern stack: its norm and its
    mixer's parameters."""
    d = spec.hidden_size
    if kind == "E":
        return {"norm": (d,), **_expert_shapes(spec)}
    if kind == "*":
        hq = spec.num_attention_heads * spec.head_dim
        hkv = spec.num_key_value_heads * spec.head_dim
        return {"norm": (d,), "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
                "wo": (hq, d)}
    di, h = spec.mamba_inner, spec.mamba_num_heads
    conv = di + 2 * spec.n_groups * spec.ssm_state_size   # x, B and C
    shapes = {"norm": (d,), "in_proj": (d, di + conv + h),   # [z | xBC | dt]
              "conv_w": (spec.conv_kernel, conv), "dt_bias": (h,),
              "A_log": (h,), "D": (h,), "ssm_norm": (di,),
              "out_proj": (di, d)}
    if spec.use_conv_bias:
        shapes["conv_b"] = (conv,)
    return shapes


def _layer_shapes(spec: BlockSpec, dense: bool, kind: str) -> dict:
    """One layer of the two halves; its attention is of `kind`, which
    says how many query heads it has."""
    d, h = spec.hidden_size, spec.q_heads(kind)
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
        # `w_gate` is the experts' and `q_norm` the latent path's
        if spec.attn_gate:
            layer["w_gate_heads"] = (d, h)
        if spec.head_norms:
            layer.update({"q_head_norm": (spec.head_dim,),
                          "k_head_norm": (spec.head_dim,)})
    layer.update({"norm1": (d,), "norm2": (d,)})
    if spec.loop_steps:
        layer.update({"norm1_post": (d,), "norm2_post": (d,)})
    if dense:
        i = spec.intermediate_size
        layer.update({"mlp_gate": (d, i), "mlp_up": (d, i),
                      "mlp_down": (i, d)})
        return layer
    layer.update(_expert_shapes(spec))
    return layer


def param_shapes(spec: BlockSpec) -> dict:
    d = spec.hidden_size
    shapes = {"embed": (spec.vocab_size, d), "head": (spec.vocab_size, d),
              "final_norm": (d,),
              "layers": [_block_shapes(spec, kind) for kind in
                         spec.block_kinds] if spec.block_kinds else [
                  _layer_shapes(spec, n < spec.dense_layers, kind)
                  for n, kind in enumerate(spec.layer_types)]}
    if spec.mtp_layers:
        shapes["mtp"] = {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                         "final_norm": (d,),
                         "layer": _layer_shapes(spec, False,
                                                spec.layer_types[-1])}
    if spec.loop_steps:
        shapes.update({"exit_gate": (d, 1), "exit_bias": (1,)})
    return shapes


def expert_layers(params: dict, spec: BlockSpec) -> list[dict]:
    """The layers that hold a router, in the order of the step's
    counters: the stack's, then the prediction module's."""
    layers = [params["layers"][n] for n in spec.router_blocks]
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

    def uniform(k, s, lo: float, hi: float):
        return jax.random.uniform(k, s, jnp.float32, lo, hi)

    dt_min, dt_max, dt_floor = spec.time_step
    taps = (spec.conv_kernel or 1) ** -0.5
    # a Mamba-2 mixer's own, as its family's code draws them: with
    # normal(0, initializer_range) every head would forget in a few
    # positions
    mamba = {
        # decay rates A = -exp(A_log) uniform in [-16, -1]
        "A_log": lambda k, s: jnp.log(uniform(k, s, 1.0, 16.0)),
        # softplus(dt_bias) log-uniform in [dt_min, dt_max], floored
        "dt_bias": lambda k, s: _softplus_inverse(jnp.maximum(jnp.exp(
            uniform(k, s, math.log(dt_min), math.log(dt_max))), dt_floor)),
        # a depthwise convolution's default: uniform in +- 1 / sqrt(taps)
        "conv_w": lambda k, s: uniform(k, s, -taps, taps),
        "conv_b": lambda k, s: uniform(k, s, -taps, taps),
    }

    def leaf(path, s, k):
        name = path[-1].key
        if name in mamba:
            return mamba[name](k, s)
        if name in ("router_bias", "exit_bias"):
            return jnp.zeros(s, jnp.float32)
        if len(s) == 1:                   # norm gains, and a mixer's D
            return jnp.ones(s, jnp.float32)
        return scale(path) * jax.random.normal(k, s, jnp.float32)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            leaf(path, s, k) for (path, s), k in zip(leaves, keys)])

    return make


def _softplus_inverse(y):
    """x with softplus(x) = y, for y > 0."""
    return y + jnp.log(-jnp.expm1(-y))


def init_params(spec: BlockSpec, seed: int) -> dict:
    """normal(0, initializer_range) matrices (the embedding's rows
    normal(0, embedding_initializer_range)), unit norm gains, zero
    router biases and a zero exit bias; of a Mamba-2 mixer A uniform in
    [-16, -1], softplus(dt_bias) log-uniform in `time_step`, D 1 and the
    convolution uniform in +- 1 / sqrt(taps). float32, a pure function
    of (spec, seed)."""
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
    hq, hkv, dh = (spec.q_heads(kind), spec.num_key_value_heads,
                   spec.head_dim)
    with jax.named_scope("seq.attn.proj"):
        y = rms_norm(x, lp["norm1"], spec.rms_norm_eps).astype(COMPUTE)

        def heads(w, n, gain=None):
            t = jnp.einsum("bsd,dhk->bhsk", y,
                           w.astype(COMPUTE).reshape(d, n, dh),
                           preferred_element_type=jnp.float32)
            # `use_qk_norm`: a head normed over its own dimensions,
            # before it rotates
            return t if gain is None else rms_norm(
                t, lp[gain], spec.rms_norm_eps)

        def rotated(t):     # a pattern stack's attention: no rotation
            return t if cos is None else apply_rope(t, cos, sin)

        norms = ("q_head_norm", "k_head_norm") if spec.head_norms else (
            None, None)
        q = rotated(heads(lp["wq"], hq, norms[0])).astype(COMPUTE)
        k = rotated(heads(lp["wk"], hkv, norms[1])).astype(COMPUTE)
        v = heads(lp["wv"], hkv).astype(COMPUTE)
    window = spec.window(kind)
    with jax.named_scope("seq.attn.window" if window else "seq.attn.full"):
        o = banded_flash_attention(q, k, v, window, None,
                                   ATTN_BLOCK, ATTN_BLOCK)
    if spec.attn_gate:
        # `gating`: o_h <- softplus(y W_g)_h * o_h, the product in
        # float32 on the kernel's o, rounded once as W_o's operand
        with jax.named_scope("seq.attn.gate"):
            gate = jax.nn.softplus(jnp.dot(
                y, lp["w_gate_heads"].astype(COMPUTE),
                preferred_element_type=jnp.float32))        # (B, S, H)
            o = (o.astype(jnp.float32)
                 * gate.transpose(0, 2, 1)[..., None]).astype(COMPUTE)
    with jax.named_scope("seq.attn.proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o,
                         lp["wo"].astype(COMPUTE).reshape(hq, dh, d),
                         preferred_element_type=jnp.float32)
        if spec.loop_steps:
            out = rms_norm(out, lp["norm1_post"], spec.rms_norm_eps)
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


def _dot(a, w):
    """a in COMPUTE times a stored weight, accumulated in float32."""
    return jnp.dot(a, w.astype(COMPUTE), preferred_element_type=jnp.float32)


def _swiglu(y, gate, up, down):
    """(T, d) in COMPUTE -> (T, d) float32: (silu(y Wg) * (y Wu)) Wd."""
    return _dot((jax.nn.silu(_dot(y, gate)) * _dot(y, up)).astype(COMPUTE),
                down)


def _relu2_ffn(y, up, down):
    """(T, d) in COMPUTE -> (T, d) float32: relu(y Wu)^2 Wd, no gate."""
    return _dot(jnp.square(jax.nn.relu(_dot(y, up))).astype(COMPUTE), down)


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
            yc = y.astype(COMPUTE)
            if spec.expert_act == "relu2":
                out = out + _relu2_ffn(yc, lp["shared_up"],
                                       lp["shared_down"])
            else:
                out = out + _swiglu(yc, lp["shared_gate"], lp["shared_up"],
                                    lp["shared_down"])
    return h + out, aux


def _dense_half(lp, h, *, spec: BlockSpec):
    """One history (S, d) through a dense layer's SwiGLU."""
    with jax.named_scope("seq.mlp.dense"):
        y = rms_norm(h, lp["norm2"], spec.rms_norm_eps).astype(COMPUTE)
        out = _swiglu(y, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
        if spec.loop_steps:
            out = rms_norm(out, lp["norm2_post"], spec.rms_norm_eps)
        return h + out


def causal_conv(x, w, bias=None):
    """A causal depthwise convolution over positions: x (B, S, C), w
    (taps, C), the last tap on the current position ->
    out[t] = sum_k w[k] x[t - (taps - 1) + k] (+ bias), as shifted adds."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(w[k] * padded[:, k:k + s] for k in range(taps))
    return out if bias is None else out + bias


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """RMSNorm_groups(y * silu(z)) * gain: the gate first, then each of
    `groups` equal groups of channels normed by its own mean square."""
    gated = y * jax.nn.silu(z)
    by_group = gated.reshape(*gated.shape[:-1], groups, -1)
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return normed.reshape(gated.shape) * gain


def _mamba_block(lp, x, *, spec: BlockSpec):
    """x (B, S, d) -> x + the Mamba-2 mixer of its norm: in_proj to
    [z | xBC | dt], a causal convolution and SiLU over xBC, the scan
    (ops/ssd.py) of x by dt, B, C with A = -exp(A_log) and the skip D,
    the gated group norm, out_proj. Where the shapes are whole lane tiles
    the convolution and the norm are ops/ssm_rows.py's passes over
    in_proj's output where it lies; else the functions above, on slices."""
    b, s, _ = x.shape
    h, p, g, n = (spec.mamba_num_heads, spec.mamba_head_dim, spec.n_groups,
                  spec.ssm_state_size)
    di, taps = spec.mamba_inner, lp["conv_w"].shape[0]
    widths = (di, g * n, g * n)                           # x, B, C
    rows = ssm_rows.takes(s, taps, *widths, di // g)
    with jax.named_scope("seq.ssm.proj"):
        y = rms_norm(x, lp["norm"], spec.rms_norm_eps).astype(COMPUTE)
        zxbcdt = jnp.dot(y, lp["in_proj"].astype(COMPUTE),
                         preferred_element_type=jnp.float32)
        dt = zxbcdt[..., -h:]
    with jax.named_scope("seq.ssm.conv"):
        if rows:
            xs, b_mat, c_mat = ssm_rows.conv_silu(
                zxbcdt, lp["conv_w"], lp.get("conv_b", jnp.zeros(sum(widths))),
                di, widths, COMPUTE)
        else:
            xbc = jax.nn.silu(causal_conv(
                zxbcdt[..., di:-h], lp["conv_w"], lp.get("conv_b")))
            xs, b_mat, c_mat = (v.astype(COMPUTE) for v in jnp.split(
                xbc, (di, di + g * n), axis=-1))
    with jax.named_scope("seq.ssm.scan"):
        out = ssd_scan(
            xs.reshape(b, s, h, p), jax.nn.softplus(dt + lp["dt_bias"]),
            -jnp.exp(lp["A_log"]), b_mat.reshape(b, s, g, n),
            c_mat.reshape(b, s, g, n), lp["D"], spec.chunk_size)
    with jax.named_scope("seq.ssm.proj"):
        out = out.reshape(b, s, di)
        if rows:
            out = ssm_rows.gated_norm(out, zxbcdt, lp["ssm_norm"], g,
                                      spec.rms_norm_eps, COMPUTE)
        else:
            out = gated_group_norm(out, zxbcdt[..., :di], lp["ssm_norm"], g,
                                   spec.rms_norm_eps).astype(COMPUTE)
        return x + jnp.dot(out, lp["out_proj"].astype(COMPUTE),
                           preferred_element_type=jnp.float32)


def _block(lp, x, *, spec: BlockSpec, kind: str):
    """One single-mixer block of a pattern stack, x <- x + Mixer(RMSNorm(
    x)), recomputed in the backward pass (an "E" or "M" block a history
    at a time: what the recomputation holds at once is one history's) but
    for what only a kernel or the scan can make: attention's o and
    log-sum-exp, and the scan's chunk states (B, S / chunk, H, P, N)
    float32. -> (x', the router's counters or None)."""
    keep = jax.checkpoint_policies.save_only_these_names(
        *KEPT_RESIDUALS, KEPT_STATES)
    # a block's one norm, under the key its mixer's code reads
    if kind == "E":
        return jax.lax.map(jax.checkpoint(partial(
            _experts_half, {**lp, "norm2": lp["norm"]}, spec=spec)), x)
    if kind == "M":
        one = jax.checkpoint(partial(_mamba_block, lp, spec=spec),
                             policy=keep)
        return jax.lax.map(lambda x_b: one(x_b[None])[0], x), None
    return jax.checkpoint(
        partial(_attention_half, spec=spec, kind="full_attention"),
        policy=keep)({**lp, "norm1": lp["norm"]}, x, None, None), None


def _layer(lp, x, table, *, spec: BlockSpec, kind: str, dense: bool):
    """One layer, each half recomputed in the backward pass, the second
    a history at a time. -> (x', the router's counters or None).

    The attention half keeps, beside its input x, what the attention
    forward kernel alone can make (ops/attention.py KEPT_RESIDUALS): o
    (B, Hq, S, D) in COMPUTE and the rows' log-sum-exp (B, Hq, S) float32,
    B * Hq * S * (2 D + 4) bytes a layer, so the backward pass does not
    run the kernel again; the norm, the projections and the rotations are
    recomputed.

    A looped stack keeps a layer's residuals `loop_steps` times, so its
    layer is one checkpoint over both halves: x, o and the log-sum-exp
    are kept and the dense half's input is made again with the rest (the
    same work once more as the two checkpoints recompute, B * S * d * 4
    bytes a layer and pass fewer)."""
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS)
    if spec.loop_steps:
        def both(lp, x, cos, sin):
            return jax.lax.map(
                jax.checkpoint(partial(_dense_half, lp, spec=spec)),
                _attention_half(lp, x, cos, sin, spec=spec, kind=kind))

        return jax.checkpoint(both, policy=keep)(lp, x, *table), None
    x = jax.checkpoint(partial(_attention_half, spec=spec, kind=kind),
                       policy=keep)(lp, x, *table)
    if dense:
        return jax.lax.map(
            jax.checkpoint(partial(_dense_half, lp, spec=spec)), x), None
    return jax.lax.map(
        jax.checkpoint(partial(_experts_half, lp, spec=spec)), x)


def _stack(params, x, tables, spec: BlockSpec):
    """x (B, S, d) through the L layers once -> (x', the counters of the
    layers that route, one entry each)."""
    counters = []
    kinds = spec.block_kinds or spec.layer_types
    for n, (lp, kind) in enumerate(zip(params["layers"], kinds)):
        if spec.block_kinds:
            x, aux = _block(lp, x, spec=spec, kind=kind)
        else:
            x, aux = _layer(lp, x, tables[kind], spec=spec, kind=kind,
                            dense=n < spec.dense_layers)
        if aux is not None:
            counters.append(aux)
    return x, counters


def hidden_states(params, ids, spec: BlockSpec):
    """ids (B, S) int32 -> (x_L (B, S, d) float32, counters of the
    layers that route: `counts` (layers, B, held experts), `dropped`
    (layers, B), with a router bias `counts_all` (layers, B, routed);
    {} where no layer routes). One pass: a looped stack goes through
    `looped_states`."""
    tables = rope_tables(spec, ids.shape[1])
    with jax.named_scope("seq.embed"):
        x = params["embed"][ids]
    x, counters = _stack(params, x, tables, spec)
    if not counters:
        return x, {}
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *counters)


def looped_states(params, ids, spec: BlockSpec, at_exit=None):
    """The stack `loop_steps` times over its one set of parameters, the
    final norm at every pass's end and its output the next pass's input:
    ids (B, S) -> (y_T (B, S, d) float32, normed; what `at_exit(y_t)`
    gave at every exit, stacked over the passes). A Python loop over the
    passes: the step's program holds the stack `loop_steps` times (each
    layer with its checkpoint, as `_layer` has it). The exit is
    recomputed in the backward pass from the last layer's output, the
    one array it keeps.

    On the v5e at the published widths (T 4, L 4, 2 x 8,192 tokens) one
    `jax.lax.scan` over the passes read 1.9-2.2 % slower a step on each
    of three seeds (it stacks the kept x, o and log-sum-exp and adds the
    head's gradient up pass by pass), at 1.04 GB less of peak memory and
    a warm job 18.6 s shorter from the compile cache (PERF.md section 6,
    PR 41)."""
    tables = rope_tables(spec, ids.shape[1])
    with jax.named_scope("seq.embed"):
        x = params["embed"][ids]

    @jax.checkpoint
    def exit_of(x):
        with jax.named_scope("seq.head_loss"):
            y = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        return y, None if at_exit is None else at_exit(y)

    exits = []
    with jax.named_scope("seq.loop"):
        for _ in range(spec.loop_steps):
            x, out = exit_of(_stack(params, x, tables, spec)[0])
            exits.append(out)
        if at_exit is None:
            return x, None
        return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *exits)


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


def _token_chunks(xn, targets):
    """(tokens, d) normed states and their targets -> (the states in
    chunks of at most LOSS_CHUNK tokens (chunks, chunk, d), the targets
    alike, the targets flat), padded to whole chunks with PAD targets,
    which every loss masks."""
    d = xn.shape[-1]
    tgt = targets.reshape(-1)
    chunk = min(LOSS_CHUNK, xn.shape[0])
    pad = (-xn.shape[0]) % chunk
    if pad:
        xn = jnp.pad(xn, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad))           # PAD targets: masked
    return xn.reshape(-1, chunk, d), tgt.reshape(-1, chunk), tgt


def _chunk_ce(head, x_c, t_c):
    """One chunk's cross-entropies (chunk,), 0 at a PAD target. The head
    is rounded inside the chunk: its gradient then adds up over the
    chunks in float32, not in bfloat16."""
    logits = jax.lax.dot_general(
        x_c, head.astype(COMPUTE), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, t_c[:, None], axis=1)[:, 0]
    return jnp.where(t_c != PAD, ce, 0.0)


def head_loss(params, x, targets, spec: BlockSpec, final_norm=None):
    """Mean cross-entropy of the untied head over the targets that are
    not PAD, the logits made a chunk of tokens at a time. `final_norm`:
    another gain than the stack's (the prediction module's own)."""
    with jax.named_scope("seq.head_loss"):
        gain = params["final_norm"] if final_norm is None else final_norm
        x_chunks, t_chunks, tgt = _token_chunks(
            rms_norm(x, gain, spec.rms_norm_eps
                     ).astype(COMPUTE).reshape(-1, x.shape[-1]), targets)
        head = params["head"]

        @jax.checkpoint
        def chunk_sum(carry, xs):
            return carry + jnp.sum(_chunk_ce(head, *xs)), None

        total, _ = jax.lax.scan(chunk_sum, jnp.float32(0.0),
                                (x_chunks, t_chunks))
        return total / jnp.maximum(jnp.sum(tgt != PAD), 1)


def token_losses(params, y, targets):
    """The untied head's cross-entropy of every token, (B, S) float32 (0
    at a PAD target), from a state y (B, S, d) that is normed already,
    in `head_loss`'s chunks."""
    with jax.named_scope("seq.head_loss"):
        x_chunks, t_chunks, _ = _token_chunks(
            y.astype(COMPUTE).reshape(-1, y.shape[-1]), targets)
        head = params["head"]
        ce = jax.lax.map(
            jax.checkpoint(lambda xs: _chunk_ce(head, *xs)),
            (x_chunks, t_chunks))
        return ce.reshape(-1)[:targets.size].reshape(targets.shape)


def exit_gate_logits(params, y):
    """The exit gate on normed states y (..., d): z = y . w_gate + b_gate,
    float32 throughout (a sum of d products a token, no matrix unit)."""
    return jnp.sum(y * params["exit_gate"][:, 0], axis=-1) \
        + params["exit_bias"][0]


def exit_probabilities(gate_logits):
    """(T, ...) gate logits z_t -> p_t, with lam = sigmoid(z): p_t =
    lam_t prod_{j<t}(1 - lam_j), and the last exit takes what mass is
    left: p_T = prod_{j<T}(1 - lam_j) (z_T is not read). 1 - lam is
    sigmoid(-z), so the masses sum to 1 to float32's last digits."""
    stay = jnp.cumprod(jax.nn.sigmoid(-gate_logits), axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    leave = jax.nn.sigmoid(gate_logits).at[-1].set(1.0)
    return before * leave


def exit_loss(params, tokens, spec: BlockSpec):
    """A looped stack's loss over its exits: tokens (B, S + 1) -> (mean
    over the tokens of sum_t p_t CE_t - EXIT_ENTROPY_WEIGHT * H(p),
    counters: `exit_losses` (T,) the mean CE_t, `exit_mass` (T,) the mean
    p_t, `exit_entropy` the mean H(p))."""
    targets = tokens[:, 1:]

    def at_exit(y):
        with jax.named_scope("seq.exit"):
            z = exit_gate_logits(params, y)
        return token_losses(params, y, targets), z

    _, (ce, z) = looped_states(params, tokens[:, :-1], spec, at_exit)
    with jax.named_scope("seq.loop"), jax.named_scope("seq.exit"):
        p = exit_probabilities(z)
        # a mass of 0 adds 0 to the entropy
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
        kept = targets != PAD
        n = jnp.maximum(jnp.sum(kept), 1)

        def mean(a):
            return jnp.sum(jnp.where(kept, a, 0.0), axis=(-2, -1)) / n

        loss = mean(jnp.sum(p * ce, axis=0) - EXIT_ENTROPY_WEIGHT * entropy)
        return loss, {"exit_losses": mean(ce), "exit_mass": mean(p),
                      "exit_entropy": mean(entropy)}


def loss_and_counters(params, tokens, spec: BlockSpec):
    """tokens (B, S + 1): inputs tokens[:, :-1], targets tokens[:, 1:].
    With a prediction module tokens (B, S + 2): position i of the S
    also predicts tokens[:, i + 2] through the module, the counters gain
    the module's router as their last layer and `losses` (main, module),
    and the loss is main + MTP_LOSS_WEIGHT * module. A looped stack's
    loss is `exit_loss`. The function the train step differentiates."""
    if spec.loop_steps:
        return exit_loss(params, tokens, spec)
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
    """Next-item logits (B, vocab) after the last position of ids; of a
    looped stack the last exit's, after every pass (no early exit)."""
    if spec.loop_steps:
        xn = looped_states(params, ids, spec)[0][:, -1]
    else:
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

    layers = list(params["layers"])
    for i, n in enumerate(spec.router_blocks):
        layers[n] = moved(layers[n], moves[i])
    out = {**params, "layers": layers}
    if spec.mtp_layers:
        out["mtp"] = {**params["mtp"], "layer": moved(
            params["mtp"]["layer"], moves[len(spec.router_blocks)])}
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


def group_tiles(counts: np.ndarray, tile_rows: int) -> np.ndarray:
    """Tiles of the sorted buffer each group owns: its rows rounded up
    to whole tiles, an empty group one tile."""
    return np.maximum(-(-counts // tile_rows), 1)


def tiles_used_share(counts: np.ndarray, held: HeldExperts,
                     positions: int) -> float:
    """Tiles of the sorted buffer that hold a group, which are the tiles
    the grouped products and the move into the buffer visit, over the
    buffer's tiles. counts (..., held experts): a history's tokens per
    held expert; the mean over everything before. An empty group owns
    one tile."""
    tm = held.tile_rows
    used = group_tiles(counts, tm).sum(axis=-1)
    return float(used.mean() / (held.row_capacity(positions) // tm))


def tile_fill(counts: np.ndarray, held: HeldExperts) -> float:
    """Rows that hold a (token, held expert) choice over the rows of the
    tiles the grouped products visit (`tiles_used_share`'s tiles): what
    is left is the padding of each group to whole tiles. counts (...,
    held experts): a history's tokens per held expert; over everything
    before, as a ratio of sums."""
    tm = held.tile_rows
    return float(counts.sum() / (group_tiles(counts, tm).sum() * tm))


def head_counters(spec: BlockSpec) -> dict:
    """Query heads and rotating dimensions of a head, by the kind of
    attention layer the stack has: what the attention kernels' shapes
    and the rotation's width are a layer kind at a time."""
    kinds = set(spec.layer_types) or (
        {"full_attention"} if "*" in spec.block_kinds else set())
    out = {}
    for kind, label in (("full_attention", "full"),
                        ("sliding_attention", "window")):
        if kind in kinds:
            out[f"attn_q_heads_{label}"] = spec.q_heads(kind)
            out[f"attn_rotary_dims_{label}"] = (
                0 if spec.block_kinds else spec.rotary_dims(kind))
    return out


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
    """What a traced step program holds of the attention layers, read
    off its equations (through every jaxpr they hold): the calls of the
    forward kernel; the calls of the backward kernel
    `flash_attention_bwd`, which are the layer applications (one a layer
    and pass); the backward kernels, which are every other kernel of the
    name `flash_attention_*` (one a layer application, two while dq and
    dk / dv had a kernel each); and the bytes of the arrays named KEPT_RESIDUALS
    that a checkpoint's backward pass takes in, which are the ones kept.
    Of a program with state-space scans, likewise: `ssm_state_bytes`,
    the chunk states the forward pass names KEPT_STATES (a Mamba-2 block
    runs a history at a time, so its checkpoint lies inside a loop over
    the histories and the array kept is the loop's: counted where it is
    named, outside every recomputation, times the loops around it), and
    the calls of the scan's kernels `ssd_chunk_fwd` / `ssd_chunk_bwd`, a
    loop's body once (the forward kernel also in a block's
    recomputation), and `ssm_conv_kernels` / `ssm_norm_kernels`, the
    calls either way of ops/ssm_rows.py's passes (`ssm_conv_silu_*`,
    `ssm_gated_norm_*`: 0 where a block's shapes sent it to the plain
    functions); a program without a scan has none of the five keys."""
    found = {"attn_fwd_kernels": 0, "attn_bwd_kernels": 0,
             "attn_residual_bytes": 0, "layer_applications": 0}
    scans = {"ssm_fwd_kernels": 0, "ssm_bwd_kernels": 0,
             "ssm_conv_kernels": 0, "ssm_norm_kernels": 0,
             "ssm_state_bytes": 0}
    counts = {**found, **scans}

    def walk(jaxpr, named, times, recomputed):
        def is_named(v):
            return isinstance(v, Var) and v in named

        for eqn in jaxpr.eqns:
            if eqn.primitive is name_p:
                out = eqn.outvars[0]
                if eqn.params["name"] in KEPT_RESIDUALS:
                    named.add(out)
                elif eqn.params["name"] == KEPT_STATES and not recomputed:
                    counts["ssm_state_bytes"] += (
                        times * out.aval.size * out.aval.dtype.itemsize)
            elif eqn.primitive is jax.lax.reduce_precision_p:
                # jax passes a kept array that the forward pass reads too
                # through one that changes nothing
                if is_named(eqn.invars[0]):
                    named.add(eqn.outvars[0])
            elif eqn.primitive is remat_p:
                counts["attn_residual_bytes"] += sum(
                    v.aval.size * v.aval.dtype.itemsize
                    for v in eqn.invars if is_named(v))
            elif eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                if name == "flash_attention_fwd":
                    counts["attn_fwd_kernels"] += 1
                elif name.startswith("flash_attention_"):
                    counts["attn_bwd_kernels"] += 1
                    counts["layer_applications"] += (
                        name == "flash_attention_bwd")
                elif name in ("ssd_chunk_fwd", "ssd_chunk_bwd"):
                    counts[f"ssm_{name[-3:]}_kernels"] += 1
                elif name.startswith("ssm_conv_silu_"):
                    counts["ssm_conv_kernels"] += 1
                elif name.startswith("ssm_gated_norm_"):
                    counts["ssm_norm_kernels"] += 1
            inside = times * eqn.params.get("length", 1) if (
                eqn.primitive.name == "scan") else times
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, set(), inside,
                     recomputed or eqn.primitive is remat_p)

    walk(jaxpr, set(), 1, False)
    has_scan = counts["ssm_state_bytes"] or counts["ssm_fwd_kernels"]
    return {k: v for k, v in counts.items() if k in found or has_scan}


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


def _expert_counters(counters: list, spec: BlockSpec,
                     positions: int) -> dict:
    """The `seq.wait` labels only a stack with routed layers has, from
    its steps' counters (`counts`: (routers, B, held) a step)."""
    counts = np.stack([c["counts"] for c in counters])  # steps, L, B, held
    dropped = int(sum(np.sum(c["dropped"]) for c in counters))
    if dropped:
        raise AssertionError(
            f"{dropped} routed tokens found no row: the expert layer "
            "must drop none")
    steps, _, batch, _ = counts.shape
    per_expert = counts.sum(axis=2)                  # a step, a layer
    held = spec.experts
    return dict(
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
        # rows that hold a choice over the rows of those tiles
        expert_tile_fill=repr(tile_fill(counts, held)),
        dropped_tokens=dropped)


def _exit_counters(counters: list) -> dict:
    """The `seq.wait` labels only a looped stack has, from its steps'
    counters; a list is the T exits', as JSON."""
    def exits(step: dict, key: str) -> str:
        return json.dumps([float(v) for v in step[key]])

    mass = np.stack([c["exit_mass"] for c in counters]).astype(np.float64)
    return dict(
        loss_exit_first=exits(counters[0], "exit_losses"),
        loss_exit_last=exits(counters[-1], "exit_losses"),
        exit_mass_last=exits(counters[-1], "exit_mass"),
        # the passes a token takes to its exit, by the gate's masses
        exit_expected_steps=repr(float(
            (mass * np.arange(1, mass.shape[1] + 1)).sum(axis=1).mean())),
        exit_entropy_last=repr(float(counters[-1]["exit_entropy"])))


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
    n_scans = spec.block_kinds.count("M")
    if n_scans and positions % spec.chunk_size:
        raise ValueError(
            f"chunk_size {spec.chunk_size} does not divide the {positions} "
            "positions of a history: the scan runs over whole chunks")
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
        # what every stack has
        sp.update(tokens_per_step=batch * positions,
                  loss_first=repr(float(losses[0])),
                  loss_last=repr(float(losses[-1])),
                  loop_steps=spec.loop_steps or 1, **program,
                  **head_counters(spec))
        if "counts" in counters[0]:
            sp.update(**_expert_counters(counters, spec, positions))
        if "sliding_attention" in spec.layer_types:
            sp.update(**band_counters(spec, positions))
        if n_scans:
            # ssm_state_bytes and the kernels' counts came with `program`
            sp.update(ssm_blocks=n_scans,
                      ssm_chunks=positions // spec.chunk_size,
                      block_kinds=" ".join(
                          f"{k}{spec.block_kinds.count(k)}"
                          for k in BLOCK_KINDS))
        if spec.loop_steps:
            sp.update(**_exit_counters(counters))
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
