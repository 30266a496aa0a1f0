"""The plain reference of the block stack of single-mixer blocks
(`model_type` `nemotron_h`): Mamba-2 mixers, relu^2 experts beside a
shared one, grouped-query attention without rotary positions. float32,
`jax.numpy`, `jax.default_matmul_precision("highest")`, no kernel, no
chunked algebra, no import of the program. It computes one
expert-parallel rank's share, as the program does: the router is as wide
as published, the held experts' part of a block's result (plus the
shared expert's, which every rank computes whole) goes on to the next
block, and the logits and the loss are over the slice of the vocabulary.

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and `W` on the right,
block l of kind `hybrid_override_pattern[l]`:

    x_0 = E[ids];   x <- x + Mixer_l(RMSNorm_l(x));
    logits = RMSNorm_f(x_L) W_head^T                      (untied)

M, Mamba-2, u the normed input, H heads of P, G groups of N:
    [z | xBC | dt] = u W_in           (4,096 | 4,096 + 2 G N | H columns)
    xBC_t <- silu(sum_k w_k xBC_{t-3+k} + b)    (4 taps, causal, a channel)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T   (a P x N state a head,
    y_t = h_t C_t + D x_t                         h_0 = 0; H / G heads
                                                  share a group's B, C)
    out = RMSNorm_groups(y * silu(z)) W_out       (G groups, one gain)
  The scan is `lax.scan` over positions, a position at a time.

E, v the normed input:
    s = sigmoid(v W_r);  choice = top-k of (s + b)
    w = scaling * s[choice] / (sum s[choice] + 1e-20)
    out = sum over the chosen HELD e of w_e W_down,e relu(v W_up,e)^2
          + W_sdown relu(v W_sup)^2            (the shared expert)
  b is the router's bias (`router_bias`, no gradient); the rule that
  moves it after a step is latent_moe_lm.py's `bias_after`.

*, u the normed input: causal softmax of q_h k_h^T / sqrt(head_dim)
  with q = u W_q, k = u W_k, v = u W_v, key-value heads repeated, no
  rotation; out = concat_h(p_h v_h) W_o.

Departures from the published description: none of the mathematics;
attention without rotary positions, the order [z | xBC | dt], the gate
before the norm and sigmoid routing with a bias are the family's code
where config.json has no key (the configuration file's `assumed`).

Blocks, so that the published widths fit a chip, none of which changes
the arithmetic: a block one history at a time, the scan's positions in
segments that the backward pass recomputes (the recurrence inside a
segment is still a position at a time), attention a block of queries at
a time, experts one at a time over every token (dense, times the routing
weight, which is 0 for a token not routed there), the loss a chunk of
tokens at a time.

`faults` turns the reference into a faulty one, for setting and testing
the limits of benchmark/harness/check_ssm.py. A fault is a number (SOUND
has the sound values), so that it may be an argument of a compiled
program and one program serve every fault; every choice below is a
`where` or a `cond` on it. With chunk = `chunk_size`, which the sound
reference never reads:
{"state_reset": 1} (the state starts from zero every chunk positions: a
dropped chunk carry), {"carry_bf16": 1} (the state rounded to bfloat16
every chunk positions: the carry in bfloat16), {"decay_bf16": 1} (the
running sum of dt A inside a chunk kept in bfloat16, a step's decay the
exponential of its difference), {"conv_shift": 1} (the convolution one
tap late) or -1 (one tap early: not causal), {"softplus": 0},
{"dt_bias": 0}, {"skip": 0} (no D x), {"one_group": 1} (the gated norm
over all channels as one group), {"norm_before_gate": 1}, {"relu2": 0}
(relu for relu^2), {"routed_scaling": 1.0}, {"shared": 0},
{"select_biased": 0} (top-k of the unbiased scores),
{"accumulate_bf16": 1} (every product rounds its operands and its result
to bfloat16: the precision below the one the configuration states).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

PAD = 0
SEGMENT = 128       # positions the backward pass recomputes at a time
# the sound reference; `routed_scaling` None: the configuration's
SOUND = {"state_reset": 0.0, "carry_bf16": 0.0, "decay_bf16": 0.0,
         "conv_shift": 0.0, "softplus": 1.0, "dt_bias": 1.0, "skip": 1.0,
         "one_group": 0.0, "norm_before_gate": 0.0, "relu2": 1.0,
         "routed_scaling": None, "shared": 1.0, "select_biased": 1.0,
         "accumulate_bf16": 0.0}


def with_faults(cfg: dict, faults=None) -> dict:
    """SOUND with `faults` laid over it, every value a number."""
    unknown = set(faults or {}) - set(SOUND)
    if unknown:
        raise ValueError(f"no such fault: {sorted(unknown)}")
    out = {**SOUND, **(faults or {})}
    if out["routed_scaling"] is None:
        out["routed_scaling"] = float(cfg["routed_scaling_factor"])
    return out


def _on(flag):
    return flag > 0.5


def _round(x):
    """x at bfloat16's precision. Not a cast there and back: the
    compiler may drop that pair (`xla_allow_excess_precision`)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot(a, b, dims, faults):
    def rounded(a, b):
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.bfloat16).astype(jnp.float32)

    def sound(a, b):
        return jax.lax.dot_general(a, b, dims, precision="highest",
                                   preferred_element_type=jnp.float32)

    flag = faults["accumulate_bf16"]
    if isinstance(flag, (int, float)):
        return rounded(a, b) if _on(flag) else sound(a, b)
    return jax.lax.cond(_on(flag), rounded, sound, a, b)


def _matmul(a, b, faults):
    """(..., k) x (k, n)."""
    return _dot(a, b, (((a.ndim - 1,), (0,)), ((), ())), faults)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


# ---------------------------------------------------------------------------
# M: the Mamba-2 mixer
# ---------------------------------------------------------------------------

def convolution(x, w, bias, shift: int = 0):
    """x (S, C), w (taps, C): out_t = sum_k w_k x_{t-(taps-1)+k-shift} +
    bias, as shifted adds (positions before the first read zero).
    `shift` is a Python int: 0 is the model's convolution."""
    taps, s = w.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k + shift            # positions behind t
        if back >= 0:
            moved = jnp.pad(x, ((back, 0), (0, 0)))[:s]
        else:
            moved = jnp.pad(x, ((0, -back), (0, 0)))[-back:]
        out = out + w[k] * moved
    return out if bias is None else out + bias


def scan(x, dt, a, b_mat, c_mat, d, faults, chunk: int = 128):
    """The recurrence over one history, a position at a time. x (S, H,
    P), dt (S, H), a (H,), b_mat, c_mat (S, G, N), d (H,) -> (S, H, P).
    `chunk` is read by the faults alone (the carried `cum` too: the
    faulty running sum of dt A, begun again every chunk)."""
    s, h, p = x.shape
    g, n = b_mat.shape[1:]
    rep = h // g

    def step(carry, inp):
        state, cum = carry
        t, x_t, dt_t, b_t, c_t = inp
        first = t % chunk == 0
        state = jnp.where(first & _on(faults["state_reset"]), 0.0, state)
        state = jnp.where(first & _on(faults["carry_bf16"]), _round(state),
                          state)
        b_h, c_h = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        da = dt_t * a
        cum = jnp.where(first, 0.0, cum)
        cum_next = _round(cum + da)
        da = jnp.where(_on(faults["decay_bf16"]), cum_next - cum, da)
        state = (jnp.exp(da)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y = (jnp.sum(state * c_h[:, None, :], axis=-1)
             + faults["skip"] * d[:, None] * x_t)
        return (state, cum_next), y

    seg = math.gcd(SEGMENT, s)

    @jax.checkpoint
    def segment(carry, inps):
        return jax.lax.scan(step, carry, inps)

    inps = (jnp.arange(s), x, dt, b_mat, c_mat)
    _, ys = jax.lax.scan(
        segment, (jnp.zeros((h, p, n), jnp.float32),
                  jnp.zeros((h,), jnp.float32)),
        tuple(v.reshape(s // seg, seg, *v.shape[1:]) for v in inps))
    return ys.reshape(s, h, p)


def gated_norm(y, z, gain, groups: int, eps: float, faults):
    def normed(v, groups):
        by_group = v.reshape(*v.shape[:-1], groups, -1)
        return (by_group * jax.lax.rsqrt(jnp.mean(
            by_group * by_group, axis=-1, keepdims=True) + eps)
                ).reshape(v.shape)

    gated = y * jax.nn.silu(z)
    sound = jnp.where(_on(faults["one_group"]), normed(gated, 1),
                      normed(gated, groups)) * gain
    return jnp.where(_on(faults["norm_before_gate"]),
                     normed(y, groups) * gain * jax.nn.silu(z), sound)


def mamba_mixer(lp, u, cfg: dict, faults):
    """u (S, d) normed -> (S, d): the mixer's addend."""
    s = u.shape[0]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    di = h * p
    zxbcdt = _matmul(u, lp["in_proj"], faults)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:-h], zxbcdt[:, -h:]
    conv = partial(convolution, xbc, lp["conv_w"], lp.get("conv_b"))
    shift = faults["conv_shift"]
    xbc = jax.nn.silu(jnp.where(
        shift > 0.5, conv(1), jnp.where(shift < -0.5, conv(-1), conv(0))))
    dt = dt + faults["dt_bias"] * lp["dt_bias"]
    dt = jnp.where(_on(faults["softplus"]), jax.nn.softplus(dt), dt)
    y = scan(xbc[:, :di].reshape(s, h, p), dt, -jnp.exp(lp["A_log"]),
             xbc[:, di:di + g * n].reshape(s, g, n),
             xbc[:, di + g * n:].reshape(s, g, n), lp["D"], faults,
             cfg["chunk_size"])
    y = gated_norm(y.reshape(s, di), z, lp["ssm_norm"], g,
                   cfg["layer_norm_epsilon"], faults)
    return _matmul(y, lp["out_proj"], faults)


# ---------------------------------------------------------------------------
# E: routed experts beside a shared one
# ---------------------------------------------------------------------------

def relu2_ffn(v, up, down, faults):
    hidden = jax.nn.relu(_matmul(v, up, faults))
    hidden = jnp.where(_on(faults["relu2"]), jnp.square(hidden), hidden)
    return _matmul(hidden, down, faults)


def routing(v, router, bias, cfg: dict, faults):
    """(T, d) -> (weights (T, E) float32: w_e for the experts in a
    token's top k, 0 elsewhere; chosen (T, E) bool)."""
    s = jax.nn.sigmoid(_matmul(v, router, faults))
    _, ids = jax.lax.top_k(
        s + faults["select_biased"] * jax.lax.stop_gradient(bias),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * faults["routed_scaling"]
    rows = jnp.arange(v.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, ids].set(w),
            jnp.zeros(s.shape, bool).at[rows, ids].set(True))


def held(cfg: dict) -> tuple[int, int]:
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def expert_mixer(lp, v, cfg: dict, faults, share=None, shared: bool = True):
    """The held experts' part for (T, d) tokens (`w_*` hold the experts
    [share[0], share[1]) only), plus the shared expert's where `shared`.
    share = (0, E) with every expert's matrices is the whole block."""
    lo, hi = share or held(cfg)
    weights, _ = routing(v, lp["router"], lp["router_bias"], cfg, faults)

    @jax.checkpoint
    def add_expert(out, e):
        up, down, w = e
        return out + w[:, None] * relu2_ffn(v, up, down, faults), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(v),
                          (lp["w_up"], lp["w_down"], weights[:, lo:hi].T))
    if shared:
        out = out + faults["shared"] * relu2_ffn(
            v, lp["shared_up"], lp["shared_down"], faults)
    return out


# ---------------------------------------------------------------------------
# *: grouped-query attention, no rotation
# ---------------------------------------------------------------------------

def attention_mixer(lp, u, cfg: dict, faults, q_block: int = 256):
    """u (S, d) normed -> (S, d)."""
    s = u.shape[0]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])

    def heads(w, count):
        return _matmul(u, w, faults).reshape(s, count, dh).transpose(1, 0, 2)

    q = heads(lp["wq"], hq)
    k = jnp.repeat(heads(lp["wk"], hkv), hq // hkv, axis=0)
    v = jnp.repeat(heads(lp["wv"], hkv), hq // hkv, axis=0)
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        rows = i * q_block + jnp.arange(q_block)
        q_i = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, axis=1)
        scores = _dot(q_i, k, (((2,), (2,)), ((0,), (0,))),
                      faults) / math.sqrt(dh)
        keep = cols[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _dot(probs, v, (((2,), (1,)), ((0,), (0,))), faults)

    out = jax.lax.map(block, jnp.arange((s + pad) // q_block))  # (nb,H,qb,D)
    o = out.transpose(0, 2, 1, 3).reshape(s + pad, hq * dh)[:s]
    return _matmul(o, lp["wo"], faults)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def block(lp, x, kind: str, cfg: dict, faults):
    """One history x (S, d) through one block."""
    u = rms_norm(x, lp["norm"], cfg["layer_norm_epsilon"])
    mixer = {"M": mamba_mixer, "E": expert_mixer, "*": attention_mixer}[kind]
    return x + mixer(lp, u, cfg, faults)


def hidden_states(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> x_L (B, S, d), before the final norm; a block a
    history at a time, recomputed in the backward pass."""
    faults = with_faults(cfg, faults)
    x = params["embed"][ids]
    for lp, kind in zip(params["layers"], kinds(cfg)):
        one = jax.checkpoint(partial(block, kind=kind, cfg=cfg,
                                     faults=faults))
        x = jax.lax.map(lambda x_b, lp=lp, one=one: one(lp, x_b), x)
    return x


def logits(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> (B, S, rows). Small sizes only."""
    faults = with_faults(cfg, faults)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, ids, cfg, faults)
        return _matmul(rms_norm(x, params["final_norm"],
                                cfg["layer_norm_epsilon"]),
                       params["head"].T, faults)


def loss(params, tokens, cfg: dict, faults=None, chunk: int = 2048):
    """tokens (B, S + 1) -> mean cross-entropy of tokens[:, 1:] given
    tokens[:, :-1], the logits a chunk of tokens at a time."""
    faults = with_faults(cfg, faults)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:, :-1], cfg, faults)
        d = x.shape[-1]
        xn = rms_norm(x, params["final_norm"],
                      cfg["layer_norm_epsilon"]).reshape(-1, d)
        tgt = tokens[:, 1:].reshape(-1)
        chunk = min(chunk, xn.shape[0])
        pad = (-xn.shape[0]) % chunk
        xn = jnp.pad(xn, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad))
        head = params["head"]

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


# ---------------------------------------------------------------------------
# the router's bias
# ---------------------------------------------------------------------------

def routed_counts(params, tokens, cfg: dict, faults=None):
    """Tokens of a step by routed expert, for every E block in order:
    (routers, E) int32."""
    faults = with_faults(cfg, faults)
    counts = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:, :-1]]
        for lp, kind in zip(params["layers"], kinds(cfg)):
            if kind == "E":
                v = rms_norm(x, lp["norm"], cfg["layer_norm_epsilon"])
                _, chosen = routing(v.reshape(-1, v.shape[-1]), lp["router"],
                                    lp["router_bias"], cfg, faults)
                counts.append(jnp.sum(chosen, axis=0, dtype=jnp.int32))
            x = jax.lax.map(
                lambda x_b, lp=lp, kind=kind: block(lp, x_b, kind, cfg,
                                                    faults), x)
    return jnp.stack(counts)
