"""The plain reference of the block stack with latent attention, a
leading dense layer, a shared expert beside sigmoid-routed experts and a
multi-token-prediction module (`model_type` `glm4_moe_lite`): float32,
`jax.numpy`, `jax.default_matmul_precision("highest")`, no kernel, no
import of the program. It computes one expert-parallel rank's share, as
the program does: the router is as wide as published, the held experts'
part of each layer's result (plus the shared expert's, which every rank
computes whole) goes on to the next layer, and the logits and the loss
are over the slice of the vocabulary.

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and `W` on the right:

    x_0 = E[ids]
    layer:  h = x + Attn(RMSNorm_1(x));  x' = h + FFN(RMSNorm_2(h))
    logits = RMSNorm_f(x_L) W_head^T                      (untied)

Attn (latent), y the normed input, H heads:
    c_q = RMSNorm_q(y W_qa);  q = c_q W_qb  -> H x [q_nope | q_pe]
    [c_kv | k_pe] = y W_kva;  c_kv = RMSNorm_kv(c_kv)
    c_kv W_kvb -> H x [k_nope | v]
    q_h = [q_nope_h | RoPE(q_pe_h)],  k_h = [k_nope_h | RoPE(k_pe)]
    (the one k_pe for every head; RoPE theta on all qk_rope_head_dim
    dimensions, in halves, unscaled); causal softmax of
    q_h k_h^T / sqrt(qk_nope + qk_rope);  o = concat_h(p_h v_h) W_o.

FFN, the first `first_k_dense_replace` layers: (silu(z W_g) * (z W_u)) W_d
of width `intermediate_size`. The others, z the normed input:
    s = sigmoid(z W_r)            (every routed expert, float32)
    choice = top-k of (s + b);  w = scaling * s[choice] / (sum s[choice] + 1e-20)
    out = sum over the chosen HELD e of w_e FFN_e(z) + FFN_shared(z)
b is the router's bias (`router_bias`, no gradient). `bias_after` is the
rule that moves it after a step: b + rate * sign(mean(c) - c), c the
step's token counts over all routed experts (`routed_counts`).

Prediction module (`params["mtp"]`), x_L the stack's output before its
final norm, t the ids:
    u_i = [RMSNorm_h(x_L,i) | RMSNorm_e(E[t_i+1])] W_eh
    one expert layer as above (its own attention, router, bias, experts)
    logits2_i = RMSNorm_m(.) W_head^T       (the shared embedding and head)
    loss = CE(logits_i, t_i+1) + weight * CE(logits2_i, t_i+2)
both means over the positions; tokens are (B, S + 2).

Blocks, so that the published widths fit a chip: a layer one history at
a time, attention a block of queries at a time, experts one at a time
over every token (dense, times the routing weight, which is 0 for a
token not routed there), the loss a chunk of tokens at a time; each
layer is recomputed in the backward pass.

`faults` turns the reference into a faulty one, for setting and testing
the limits of benchmark/harness/check_latent.py: {"score": "softmax"},
{"routed_scaling": 1.0}, {"top_k": 3}, {"weights_biased": True} (weights
taken from s + b), {"kv_norm": False}, {"rope_nope": True} (the first
qk_rope dimensions of q_nope and k_nope rotate too), {"k_pe": "per_head"}
(head h reads k_pe rolled by h), {"scale_dim": 192}, {"shared": False},
{"mtp_target": 1} (the module predicts t_i+1), {"mtp_weight": 0.1},
{"accumulate": "bfloat16"} (every product rounds its operands and its
result to bfloat16: the precision below the one the configuration
states).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0
BIAS_RATE = 0.001
MTP_WEIGHT = 0.3


def _dot(a, b, dims, faults):
    if faults.get("accumulate") == "bfloat16":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.bfloat16).astype(jnp.float32)
    return jax.lax.dot_general(a, b, dims, precision="highest",
                               preferred_element_type=jnp.float32)


def _matmul(a, b, faults):
    """(..., k) x (k, n)."""
    return _dot(a, b, (((a.ndim - 1,), (0,)), ((), ())), faults)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope_tables(theta: float, dim: int, seq_len: int):
    """(cos, sin), (seq_len, dim / 2) float32, unscaled."""
    inv_freq = float(theta) ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate(x, cos, sin):
    """x (..., S, D): x * [cos, cos] + rotate_half(x) * [sin, sin]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# the two halves of a layer
# ---------------------------------------------------------------------------

def attention(q, k, v, scale: float, faults, q_block: int = 256):
    """Causal. q, k (B, H, S, Dqk), v (B, H, S, Dv) -> (B, H, S, Dv)."""
    b, h, s, _ = q.shape
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        rows = i * q_block + jnp.arange(q_block)
        q_i = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, axis=2)
        scores = _dot(q_i, k, (((3,), (3,)), ((0, 1), (0, 1))),
                      faults) * scale
        keep = cols[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _dot(probs, v, (((3,), (2,)), ((0, 1), (0, 1))), faults)

    out = jax.lax.map(block, jnp.arange((s + pad) // q_block))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, s + pad, -1)[:, :, :s]


def latent_attention(lp, y, cos, sin, cfg: dict, faults):
    """y (B, S, d) normed -> (B, S, d): the attention half's addend."""
    b, s, _ = y.shape
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]

    def heads(x, width):
        return x.reshape(b, s, h, width).transpose(0, 2, 1, 3)

    c_q = rms_norm(_matmul(y, lp["wq_a"], faults), lp["q_norm"], eps)
    q = heads(_matmul(c_q, lp["wq_b"], faults), dn + dr)
    kv_a = _matmul(y, lp["wkv_a"], faults)
    c_kv = kv_a[..., :rkv]
    if faults.get("kv_norm", True):
        c_kv = rms_norm(c_kv, lp["kv_norm"], eps)
    kv = heads(_matmul(c_kv, lp["wkv_b"], faults), dn + dv)
    q_nope, q_pe = q[..., :dn], rotate(q[..., dn:], cos, sin)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_pe = jnp.broadcast_to(kv_a[:, None, :, rkv:], (b, h, s, dr))
    if faults.get("k_pe") == "per_head":
        k_pe = jnp.stack([jnp.roll(k_pe[:, i], i, axis=-1)
                          for i in range(h)], axis=1)
    k_pe = rotate(k_pe, cos, sin)
    if faults.get("rope_nope"):
        q_nope = jnp.concatenate(
            [rotate(q_nope[..., :dr], cos, sin), q_nope[..., dr:]], -1)
        k_nope = jnp.concatenate(
            [rotate(k_nope[..., :dr], cos, sin), k_nope[..., dr:]], -1)
    scale = 1.0 / math.sqrt(faults.get("scale_dim", dn + dr))
    o = attention(jnp.concatenate([q_nope, q_pe], -1),
                  jnp.concatenate([k_nope, k_pe], -1), v, scale, faults)
    return _matmul(o.transpose(0, 2, 1, 3).reshape(b, s, h * dv),
                   lp["wo"], faults)


def swiglu(z, gate, up, down, faults):
    return _matmul(jax.nn.silu(_matmul(z, gate, faults))
                   * _matmul(z, up, faults), down, faults)


def routing(z, router, bias, cfg: dict, faults):
    """(T, d) -> (weights (T, E) float32: w_e for the experts in a
    token's top k, 0 elsewhere; chosen (T, E) bool)."""
    top_k = faults.get("top_k", cfg["num_experts_per_tok"])
    logits = _matmul(z, router, faults)
    if faults.get("score", "sigmoid") == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    biased = s + jax.lax.stop_gradient(bias)
    _, ids = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(biased if faults.get("weights_biased") else s,
                            ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * faults.get("routed_scaling", cfg["routed_scaling_factor"])
    rows = jnp.arange(z.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, ids].set(w),
            jnp.zeros(s.shape, bool).at[rows, ids].set(True))


def moe_layer(lp, z, cfg: dict, held: tuple[int, int], faults=None,
              shared: bool = True):
    """The held experts' part for (T, d) tokens (`w_*` hold the experts
    [held[0], held[1]) only), plus the shared expert's where `shared`.
    held = (0, E) with every expert's matrices is the whole layer."""
    faults = faults or {}
    weights, _ = routing(z, lp["router"], lp["router_bias"], cfg, faults)
    weights = weights[:, held[0]:held[1]]

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, w = e
        return out + w[:, None] * swiglu(z, gate, up, down, faults), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(z),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    if shared and faults.get("shared", True) and cfg.get("n_shared_experts"):
        out = out + swiglu(z, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"], faults)
    return out


def _held(cfg: dict) -> tuple[int, int]:
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def _layer(lp, x, cos, sin, *, cfg, dense, faults):
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    h = x + latent_attention(lp, rms_norm(x, lp["norm1"], eps), cos, sin,
                             cfg, faults)
    z = rms_norm(h, lp["norm2"], eps).reshape(b * s, d)
    if dense:
        out = swiglu(z, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], faults)
    else:
        out = moe_layer(lp, z, cfg, _held(cfg), faults)
    return h + out.reshape(b, s, d)


def _layer_by_history(lp, x, cos, sin, *, cfg, dense, faults):
    """`_layer` a history at a time (no arithmetic changes), recomputed
    in the backward pass: what a layer keeps alive is one history's."""
    one = jax.checkpoint(partial(_layer, cfg=cfg, dense=dense, faults=faults))
    return jax.lax.map(lambda x_b: one(lp, x_b[None], cos, sin)[0], x)


def _tables(cfg: dict, seq_len: int):
    return rope_tables(cfg["rope_theta"], cfg["qk_rope_head_dim"], seq_len)


def hidden_states(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> x_L (B, S, d), before the final norm."""
    faults = faults or {}
    cos, sin = _tables(cfg, ids.shape[1])
    x = params["embed"][ids]
    for n, lp in enumerate(params["layers"]):
        x = _layer_by_history(
            lp, x, cos, sin, cfg=cfg, faults=faults,
            dense=n < cfg.get("first_k_dense_replace", 0))
    return x


def mtp_hidden_states(params, x, next_ids, cfg: dict, faults=None):
    """x (B, S, d) the stack's output, next_ids (B, S) -> (B, S, d)."""
    faults = faults or {}
    mp, eps = params["mtp"], cfg["rms_norm_eps"]
    u = _matmul(jnp.concatenate(
        [rms_norm(x, mp["hnorm"], eps),
         rms_norm(params["embed"][next_ids], mp["enorm"], eps)], -1),
        mp["eh_proj"], faults)
    cos, sin = _tables(cfg, x.shape[1])
    return _layer_by_history(mp["layer"], u, cos, sin, cfg=cfg, dense=False,
                             faults=faults)


def _head_logits(x, gain, head, cfg, faults):
    return _matmul(rms_norm(x, gain, cfg["rms_norm_eps"]), head.T, faults)


def logits(params, tokens, cfg: dict, faults=None):
    """tokens (B, S + 1) -> (main logits (B, S, rows) from tokens[:, :S],
    the module's (B, S, rows), which read tokens[:, 1:] too). Small sizes
    only."""
    faults = faults or {}
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:, :-1], cfg, faults)
        x2 = mtp_hidden_states(params, x, tokens[:, 1:], cfg, faults)
        return (_head_logits(x, params["final_norm"], params["head"], cfg,
                             faults),
                _head_logits(x2, params["mtp"]["final_norm"], params["head"],
                             cfg, faults))


def _chunked_ce(x, gain, head, targets, cfg, faults, chunk):
    d = x.shape[-1]
    xn = rms_norm(x, gain, cfg["rms_norm_eps"]).reshape(-1, d)
    tgt = targets.reshape(-1)
    chunk = min(chunk, xn.shape[0])
    pad = (-xn.shape[0]) % chunk
    xn = jnp.pad(xn, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))

    @jax.checkpoint
    def add_chunk(total, xs):
        x_c, t_c = xs
        lg = _dot(x_c, head, (((1,), (1,)), ((), ())), faults)
        ce = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, t_c[:, None], axis=1)[:, 0]
        return total + jnp.sum(jnp.where(t_c != PAD, ce, 0.0)), None

    total, _ = jax.lax.scan(
        add_chunk, jnp.float32(0.0),
        (xn.reshape(-1, chunk, d), tgt.reshape(-1, chunk)))
    return total / jnp.maximum(jnp.sum(tgt != PAD), 1)


def losses(params, tokens, cfg: dict, faults=None, chunk: int = 2048):
    """tokens (B, S + 2) -> (main, module): mean cross-entropy of
    tokens[:, 1:S+1] given tokens[:, :S], and of tokens[:, 2:] through the
    prediction module."""
    faults = faults or {}
    s = tokens.shape[1] - 2
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:, :s], cfg, faults)
        main = _chunked_ce(x, params["final_norm"], params["head"],
                           tokens[:, 1:s + 1], cfg, faults, chunk)
        x2 = mtp_hidden_states(params, x, tokens[:, 1:s + 1], cfg, faults)
        ahead = faults.get("mtp_target", 2)
        module = _chunked_ce(x2, params["mtp"]["final_norm"], params["head"],
                             tokens[:, ahead:s + ahead], cfg, faults, chunk)
    return main, module


def loss(params, tokens, cfg: dict, faults=None, chunk: int = 2048):
    """-> (main + weight * module, (main, module)): for
    `jax.value_and_grad(..., has_aux=True)`."""
    main, module = losses(params, tokens, cfg, faults, chunk)
    weight = (faults or {}).get("mtp_weight",
                                cfg.get("mtp_loss_weight", MTP_WEIGHT))
    return main + weight * module, (main, module)


# ---------------------------------------------------------------------------
# the router's bias
# ---------------------------------------------------------------------------

def routed_counts(params, tokens, cfg: dict, faults=None):
    """Tokens of a step by routed expert, for every router in order (the
    stack's expert layers, then the module's): (routers, E) int32."""
    faults = faults or {}
    s = tokens.shape[1] - 2
    eps = cfg["rms_norm_eps"]
    cos, sin = _tables(cfg, s)
    counts = []

    def through(lp, x, dense):
        h = x + latent_attention(lp, rms_norm(x, lp["norm1"], eps), cos, sin,
                                 cfg, faults)
        if not dense:
            z = rms_norm(h, lp["norm2"], eps).reshape(-1, x.shape[-1])
            _, chosen = routing(z, lp["router"], lp["router_bias"], cfg,
                                faults)
            counts.append(jnp.sum(chosen, axis=0, dtype=jnp.int32))
        return _layer(lp, x, cos, sin, cfg=cfg, dense=dense, faults=faults)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:, :s]]
        for n, lp in enumerate(params["layers"]):
            x = through(lp, x, n < cfg.get("first_k_dense_replace", 0))
        mp = params["mtp"]
        u = _matmul(jnp.concatenate(
            [rms_norm(x, mp["hnorm"], eps),
             rms_norm(params["embed"][tokens[:, 1:s + 1]], mp["enorm"], eps)],
            -1), mp["eh_proj"], faults)
        through(mp["layer"], u, False)
    return jnp.stack(counts)


def bias_after(bias, counts, rate: float = BIAS_RATE):
    """The aux-loss-free rule: (E,) bias and a step's (E,) counts ->
    the bias the next step routes with."""
    counts = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float64) + rate * np.sign(
        counts.mean() - counts)
