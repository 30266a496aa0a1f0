"""The plain reference of the sequence engine's block stack: float32,
`jax.numpy`, `jax.default_matmul_precision("highest")`, no kernel, no
import of the program. It computes one expert-parallel rank's share, as
the program does: the router is as wide as published, the held experts'
part of each layer's result goes on to the next layer, and the logits and
the loss are over the slice of the vocabulary.

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g, for layer l of kind k_l:

    x_0 = E[ids]                    (no scaling, no learned positions)
    h   = x + Attn_l(RMSNorm_1(x))
    x'  = h + MoE_l(RMSNorm_2(h))
    logits = RMSNorm_f(x_L) W_head^T           (untied)
    loss = mean next-token cross-entropy over the targets

Attn: q = y W_q as (Hq, D), k = y W_k, v = y W_v as (Hkv, D), no biases;
rotary positions on q and k (theta from the configuration; "default" on
sliding layers, YaRN on full layers: frequencies blended by the usual
ramp, cos and sin times `attention_factor`); query head i reads key-value
head i // (Hq / Hkv); s_ij = q_i . k_j / sqrt(D), kept where j <= i, and
on sliding layers also i - j < window; softmax over j; o = concat W_o.

MoE: r = y W_r (every expert); p = softmax(r); T_k = the k largest;
w_e = p_e / sum_{T_k} p (`norm_topk_prob`); out = sum over the HELD e in
T_k of w_e * W_down,e(silu(y W_gate,e) * (y W_up,e)).

Departures from the published model, each because `config.json` has no
key for it (the configuration file lists them under `assumed`): no query
/ key norm, no auxiliary load-balancing loss, no multi-token-prediction
head, weights normal(0, 0.02).

Blocks, so that the published widths fit a chip: attention a block of
queries at a time, experts one at a time over every token (dense, times
the routing weight, which is 0 for a token not routed there), the loss a
chunk of tokens at a time; each layer is recomputed in the backward pass.

`faults` turns the reference into a faulty one, for setting and testing
the limits of benchmark/harness/check_sequence.py: {"top_k": 7},
{"norm_topk": False}, {"window": 1025}, {"kv_head": "i//4"} (query head
i reads key-value head (i // (group / 2)) mod Hkv), {"rope_full":
"default"}, {"accumulate": "bfloat16"} (every product rounds its
operands and its result to bfloat16: the precision below the one the
configuration states).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0


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


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def rope_tables(rope: dict, head_dim: int, seq_len: int):
    """(cos, sin), (seq_len, head_dim / 2) float32, for a `rope_parameters`
    entry of type "default" or "yarn"."""
    exponent = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    theta = float(rope["rope_theta"])
    inv_freq = theta ** -exponent
    scale = 1.0
    if rope["rope_type"] == "yarn":
        factor = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def dim_of(rotations):      # the pair that turns this often in orig
            return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
        # pairs below `low` keep their frequency, pairs above `high` are
        # interpolated (divided by the factor), a linear blend between
        inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
        scale = float(rope.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)
    elif rope["rope_type"] != "default":
        raise ValueError(rope["rope_type"])
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angle) * scale, jnp.float32),
            jnp.asarray(np.sin(angle) * scale, jnp.float32))


def rotate(x, cos, sin):
    """x (B, H, S, D): x * [cos, cos] + rotate_half(x) * [sin, sin]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# the two halves of a layer
# ---------------------------------------------------------------------------

def attention(q, k, v, window, faults, q_block: int = 256):
    """q (B, Hq, S, D), k / v (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if faults.get("kv_head") == "i//4":
        kv_of = (np.arange(hq) // max(group // 2, 1)) % hkv
    else:
        kv_of = np.arange(hq) // group
    k, v = k[:, kv_of], v[:, kv_of]
    if window is not None:
        window = faults.get("window", window)
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        rows = i * q_block + jnp.arange(q_block)
        q_i = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, axis=2)
        scores = _dot(q_i, k, (((3,), (3,)), ((0, 1), (0, 1))),
                      faults) / math.sqrt(d)
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep = keep & (rows[:, None] - cols[None, :] < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _dot(probs, v, (((3,), (2,)), ((0, 1), (0, 1))), faults)

    out = jax.lax.map(block, jnp.arange((s + pad) // q_block))
    return jnp.moveaxis(out, 0, 2).reshape(b, hq, s + pad, d)[:, :, :s]


def routing_weights(y, router, top_k: int, norm: bool, faults):
    """(T, d) -> (T, E) float32: w_e for the experts in a token's top k,
    0 elsewhere."""
    top_k = faults.get("top_k", top_k)
    probs = jax.nn.softmax(_matmul(y, router, faults), axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if faults.get("norm_topk", norm):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, ids].set(weights)


def moe_layer(y, router, w_gate, w_up, w_down, top_k: int, norm: bool,
              held: tuple[int, int], faults=None):
    """The held experts' part for (T, d) tokens; `w_*` hold the experts
    [held[0], held[1]) only. held = (0, E) with every expert's matrices is
    the whole layer."""
    faults = faults or {}
    weights = routing_weights(y, router, top_k, norm, faults)[
        :, held[0]:held[1]]

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, w = e
        hidden = jax.nn.silu(_matmul(y, gate, faults)) * _matmul(
            y, up, faults)
        return out + w[:, None] * _matmul(hidden, down, faults), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y),
                          (w_gate, w_up, w_down, weights.T))
    return out


def _layer(lp, x, cos, sin, *, cfg, kind, faults):
    b, s, d = x.shape
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, lp["norm1"], eps)

    def heads(w, n):
        return _matmul(y, w, faults).reshape(b, s, n, dh).transpose(
            0, 2, 1, 3)

    q = rotate(heads(lp["wq"], hq), cos, sin)
    k = rotate(heads(lp["wk"], hkv), cos, sin)
    v = heads(lp["wv"], hkv)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = attention(q, k, v, window, faults)
    h = x + _matmul(o.transpose(0, 2, 1, 3).reshape(b, s, hq * dh),
                    lp["wo"], faults)
    y2 = rms_norm(h, lp["norm2"], eps).reshape(b * s, d)
    held = tuple(cfg.get("experts_held", (0, cfg["num_experts"])))
    out = moe_layer(y2, lp["router"], lp["w_gate"], lp["w_up"],
                    lp["w_down"], cfg["num_experts_per_tok"],
                    cfg["norm_topk_prob"], held, faults)
    return h + out.reshape(b, s, d)


def hidden_states(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> x_L (B, S, d)."""
    faults = faults or {}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    tables = {}
    for kind in set(kinds):
        rope = dict(cfg["rope_parameters"][kind])
        if kind == "full_attention" and "rope_full" in faults:
            rope["rope_type"] = faults["rope_full"]
        tables[kind] = rope_tables(rope, cfg["head_dim"], ids.shape[1])
    x = params["embed"][ids]
    for lp, kind in zip(params["layers"], kinds):
        x = jax.checkpoint(partial(_layer, cfg=cfg, kind=kind,
                                   faults=faults))(lp, x, *tables[kind])
    return x


def logits(params, ids, cfg: dict, faults=None):
    """(B, S, vocabulary rows): small sizes only."""
    faults = faults or {}
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, ids, cfg, faults)
        xn = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        return _matmul(xn, params["head"].T, faults)


def loss(params, tokens, cfg: dict, faults=None, chunk: int = 2048):
    """tokens (B, S + 1) -> mean cross-entropy of tokens[:, 1:] given
    tokens[:, :-1], over the targets that are not PAD."""
    faults = faults or {}
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:, :-1], cfg, faults)
        d = x.shape[-1]
        xn = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]
                      ).reshape(-1, d)
        tgt = tokens[:, 1:].reshape(-1)
        chunk = min(chunk, xn.shape[0])
        pad = (-xn.shape[0]) % chunk
        xn = jnp.pad(xn, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad))

        @jax.checkpoint
        def add_chunk(total, xs):
            x_c, t_c = xs
            lg = _dot(x_c, params["head"], (((1,), (1,)), ((), ())), faults)
            ce = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
                lg, t_c[:, None], axis=1)[:, 0]
            return total + jnp.sum(jnp.where(t_c != PAD, ce, 0.0)), None

        total, _ = jax.lax.scan(
            add_chunk, jnp.float32(0.0),
            (xn.reshape(-1, chunk, d), tgt.reshape(-1, chunk)))
        return total / jnp.maximum(jnp.sum(tgt != PAD), 1)
