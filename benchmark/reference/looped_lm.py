"""The plain reference of the looped block stack (`model_type` `ouro`): a
dense decoder whose L layers run T = `total_ut_steps` times with one set
of weights, an exit after every pass, and a learned gate that gives each
token a distribution over the T exits. float32, `jax.numpy`,
`jax.default_matmul_precision("highest")`, no kernel, no import of the
program, the passes a Python loop (no scan over them).

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and `W` on the right:

    x = E[ids]
    for t = 1..T, the same parameters every t:
        for l = 1..L:
            h = x + N1b_l(Attn_l(N1a_l(x)))
            x = h + N2b_l(SwiGLU_l(N2a_l(h)))
        y_t = RMSNorm_final(x);  x = y_t
        CE_t[i] = -log softmax(y_t[i] W_head^T)[target_i]     (untied)
        lam_t[i] = sigmoid(y_t[i] . w_gate + b_gate)
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j) (1 < t < T)
    p_T = prod_{j<T}(1 - lam_j)
    loss = mean_i [ sum_t p_t[i] CE_t[i] - beta * H(p[i]) ]

Attn: H heads of `head_dim` (as many key-value heads), RoPE theta on all
of a head in halves, unscaled; causal softmax of q k^T / sqrt(head_dim);
o W_o. SwiGLU: (silu(z W_g) * (z W_u)) W_d of width `intermediate_size`.
N1b / N2b are RMSNorms on each half's output before the residual add
(`norm1_post`, `norm2_post`). H(p) = -sum_t p_t log p_t, beta 0.1.

Blocks, so that the published widths fit a chip: a layer one history at
a time, attention a block of query rows at a time (a masked softmax
over the whole row of keys), the losses a chunk of tokens at a time;
each is recomputed in the backward pass (`jax.checkpoint`: the same
arithmetic once more, nothing else), because sixteen layer applications
of two 8,192-token histories do not keep their float32 intermediates in
16 GB otherwise.

`faults` {"operands": "bfloat16"} is no fault: the same arithmetic at
the precision the configuration states (every product rounds its
operands to bfloat16 and keeps its sum in float32; the exit gate's
product stays in float32, as the configuration says), which
benchmark/harness/check_loop.py holds the timed step's losses against
beside the float32 ones. Every other key turns the reference into a
faulty one, for setting and testing that module's limits:
{"accumulate": "bfloat16"} (every product rounds its operands and its
result to bfloat16: the precision below the one the configuration
states), {"loop_steps": n}
(n passes for T), {"final_norm": "outside"} (a pass hands on its
un-normed state; the norm feeds the exit alone), {"post_norms": False},
{"last_exit": "gated"} (p_T = lam_T prod_{j<T}(1 - lam_j): the mass does
not sum to 1), {"entropy_sign": -1} (+ beta * H), {"layer_grads": "last
pass"} (the layers' weights take their gradient from pass T alone).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0
ENTROPY_WEIGHT = 0.1


def _dot(a, b, dims, faults):
    if faults.get("accumulate") == "bfloat16":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.bfloat16).astype(jnp.float32)
    if faults.get("operands") == "bfloat16":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32)
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


def attention(q, k, v, faults, q_block: int = 256):
    """Causal, (B, H, S, D) each -> (B, H, S, D): a block of query rows
    against every key, masked, so (B, H, S, S) never exists."""
    b, h, s, d = q.shape
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
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _dot(probs, v, (((3,), (2,)), ((0, 1), (0, 1))), faults)

    out = jax.lax.map(block, jnp.arange((s + pad) // q_block))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, s + pad, d)[:, :, :s]


def _layer(lp, x, cos, sin, *, cfg, faults):
    b, s, _ = x.shape
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    post = faults.get("post_norms", True)

    def heads(w):
        return _matmul(y, w, faults).reshape(b, s, h, dh).transpose(0, 2, 1, 3)

    y = rms_norm(x, lp["norm1"], eps)
    o = attention(rotate(heads(lp["wq"]), cos, sin),
                  rotate(heads(lp["wk"]), cos, sin), heads(lp["wv"]), faults)
    a = _matmul(o.transpose(0, 2, 1, 3).reshape(b, s, h * dh), lp["wo"],
                faults)
    x = x + (rms_norm(a, lp["norm1_post"], eps) if post else a)
    z = rms_norm(x, lp["norm2"], eps)
    m = _matmul(jax.nn.silu(_matmul(z, lp["mlp_gate"], faults))
                * _matmul(z, lp["mlp_up"], faults), lp["mlp_down"], faults)
    return x + (rms_norm(m, lp["norm2_post"], eps) if post else m)


def _layer_by_history(lp, x, cos, sin, *, cfg, faults):
    """`_layer` a history at a time (no arithmetic changes), recomputed
    in the backward pass: what a layer keeps alive is one history's."""
    one = jax.checkpoint(partial(_layer, cfg=cfg, faults=faults))
    return jax.lax.map(lambda x_b: one(lp, x_b[None], cos, sin)[0], x)


def _token_ce(y, head, targets, faults, chunk):
    """(B, S, d) normed states -> the head's cross-entropy a token."""
    d = y.shape[-1]
    flat, tgt = y.reshape(-1, d), targets.reshape(-1)
    chunk = min(chunk, flat.shape[0])
    pad = (-flat.shape[0]) % chunk
    flat = jnp.pad(flat, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))

    @jax.checkpoint
    def of_chunk(xs):
        x_c, t_c = xs
        lg = _dot(x_c, head, (((1,), (1,)), ((), ())), faults)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, t_c[:, None], axis=1)[:, 0]

    ce = jax.lax.map(of_chunk, (flat.reshape(-1, chunk, d),
                                tgt.reshape(-1, chunk)))
    return ce.reshape(-1)[:targets.size].reshape(targets.shape)


def passes(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> [y_1, ..., y_T]: the normed state at every exit."""
    faults = faults or {}
    cos, sin = rope_tables(cfg["rope_theta"], cfg["head_dim"], ids.shape[1])
    steps = faults.get("loop_steps", cfg["total_ut_steps"])
    x = params["embed"][ids]
    out = []
    for t in range(steps):
        layers = params["layers"]
        if faults.get("layer_grads") == "last pass" and t < steps - 1:
            layers = jax.lax.stop_gradient(layers)
        for lp in layers:
            x = _layer_by_history(lp, x, cos, sin, cfg=cfg, faults=faults)
        y = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        if faults.get("final_norm") != "outside":
            x = y
        out.append(y)
    return out


def exit_probabilities(lam, faults=None):
    """(T, ...) gate values -> (T, ...) the distribution over the exits."""
    left, p = jnp.ones_like(lam[0]), []
    for t in range(len(lam) - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    gated = (faults or {}).get("last_exit") == "gated"
    return jnp.stack(p + [lam[-1] * left if gated else left])


def logits(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> the last exit's logits (B, S, rows). Small sizes
    only."""
    with jax.default_matmul_precision("highest"):
        y = passes(params, ids, cfg, faults)[-1]
        return _matmul(y, params["head"].T, faults or {})


def _token_mean(a, kept):
    """(..., B, S) -> the mean over the tokens that count, kept (B, S)."""
    return jnp.sum(jnp.where(kept, a, 0.0), axis=(-2, -1)) \
        / jnp.maximum(jnp.sum(kept), 1)


def exit_objective(z, ce, kept, beta, faults=None):
    """Gate logits z and cross-entropies ce, (T, B, S) each, the tokens
    that count (B, S) -> (mean_i [ sum_t p_t CE_t - beta H(p) ], the
    exits' mean masses (T,))."""
    p = exit_probabilities(jax.nn.sigmoid(z), faults)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    return (_token_mean(jnp.sum(p * ce, axis=0) - beta * entropy, kept),
            _token_mean(p, kept))


def loss(params, tokens, cfg: dict, faults=None, chunk: int = 2048):
    """tokens (B, S + 1) -> (loss, (the exits' mean cross-entropies (T,),
    the exits' mean masses (T,), `gate_terms`)): for
    `jax.value_and_grad(..., has_aux=True)`.

    `gate_terms`: the gate's gradient is a sum over tokens and exits of
    terms g_t[i] y_t[i] (the bias's: g_t[i]), g = d loss / d z, whose
    signs differ: in some seeds the sum all but cancels, and an error
    relative to it says nothing. {"exit_gate", "exit_bias"} give the size
    each sum would have if its terms were unrelated, sqrt(sum |term|^2),
    which benchmark/harness/check_loop.py holds the error of the gate's
    gradient against."""
    faults = faults or {}
    targets = tokens[:, 1:]
    kept = targets != PAD
    beta = faults.get("entropy_sign", 1) * cfg.get(
        "exit_entropy_weight", ENTROPY_WEIGHT)
    with jax.default_matmul_precision("highest"):
        ys = passes(params, tokens[:, :-1], cfg, faults)
        ce = jnp.stack([_token_ce(y, params["head"], targets, faults, chunk)
                        for y in ys])
        # the stated precision keeps the gate's product in float32
        exact = {k: v for k, v in faults.items() if k != "operands"}
        z = jnp.stack([_matmul(y, params["exit_gate"], exact)[..., 0]
                       + params["exit_bias"][0] for y in ys])
        value, mass = exit_objective(z, ce, kept, beta, faults)
        g, y2 = jax.lax.stop_gradient((
            jax.grad(lambda z: exit_objective(z, ce, kept, beta, faults)[0])(
                z), jnp.stack([jnp.sum(y * y, axis=-1) for y in ys])))
        terms = {"exit_gate": jnp.sqrt(jnp.sum(g * g * y2)),
                 "exit_bias": jnp.sqrt(jnp.sum(g * g))}
        return value, (_token_mean(ce, kept), mass, terms)
