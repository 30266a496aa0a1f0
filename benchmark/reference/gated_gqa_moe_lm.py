"""The plain reference of the block stack of gated grouped-query layers
(`model_type` `laguna`): query-head counts and a rotating width that
differ by layer kind, an RMSNorm on every query and key head, a gate a
head on attention's output, a leading dense layer, then sigmoid-routed
experts beside a shared one. float32, `jax.numpy`,
`jax.default_matmul_precision("highest")`, no kernel, no import of the
program. It computes one expert-parallel rank's share, as the program
does: the router is as wide as published, the held experts' part of a
layer's result (plus the shared expert's, which every rank computes
whole) goes on to the next layer, and the logits and the loss are over
the slice of the vocabulary.

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and `W` on the right,
layer l of kind k (`layer_types[l]`), H_k = `num_attention_heads_per_layer
[l]` query heads over G key-value heads of D, y = RMSNorm_1(x):

    q = y W_q -> (S, H_k, D);  k = y W_k, v = y W_v -> (S, G, D)
    q <- RMSNorm_D(q) * g_q;  k <- RMSNorm_D(k) * g_k   (a head's D, gains (D,))
    q, k <- the first R_k = partial_rotary_factor_k * D dimensions rotate
            as halves (pairs (i, i + R_k / 2)), inverse frequencies over
            R_k (YaRN's correction range too), cos and sin times
            attention_factor; the dimensions R_k .. D pass
    o_h = softmax(q_h k_g^T / sqrt(D), kept where j <= i, and on a
          sliding layer i - j < window) v_g,    g = h // (H_k / G)
    gamma = softplus(y W_g) -> (S, H_k);  o_h <- gamma_h * o_h
    h = x + concat_h(o_h) W_o
    layer < dense layers:  x' = h + SwiGLU_dense(RMSNorm_2(h))
    else, u = RMSNorm_2(h):
        s = sigmoid(u W_r);  choice = top-k of (s + b)
        w = scaling * s[choice] / (sum s[choice] + 1e-20)
        x' = h + sum over the chosen HELD e of w_e SwiGLU_e(u)
               + SwiGLU_shared(u)
    logits = RMSNorm_f(x_L) W_head^T                     (untied)

b is the router's bias (`router_bias`, no gradient); the rule that moves
it after a step is latent_moe_lm.py's `bias_after`.

Departures from the published description: none of the mathematics.
What config.json has no key for is the family's code as the
configuration file's `assumed` lists it (softplus on the gate, from the
normed input; the head norms before the rotation; sigmoid scores with a
selection bias, weights normalised; no gate on the shared expert), and
`faults` holds the other reading of each.

Blocks, so that the published widths fit a chip, none of which changes
the arithmetic: a layer one history at a time, recomputed in the
backward pass; attention a block of query rows at a time against every
key, masked; experts one at a time over every token (dense, times the
routing weight, which is 0 for a token not routed there); the loss a
chunk of tokens at a time.

`faults` turns the reference into a faulty one, for setting and testing
the limits of benchmark/harness/check_gated.py. A fault is a number
(SOUND has the sound values; None: the configuration's), so that it may
be an argument of a compiled program and one program serve every fault;
every choice below is a `where`, a `cond` or an index on it:
{"kv_group_full": 8} (query head h of a full layer reads key-value head
h // 8, for h // (H_full / G)), {"rotary_full": 1.0} (the whole head
rotates on a full layer), {"yarn_full": 0} (default RoPE for YaRN
there), {"theta_swap": 1} (each kind rotates by the other's theta),
{"window": 513}, {"gate": 0} (no gate), {"gate_softplus": 0} (sigmoid),
{"gate_normed": 0} (the gate reads x, not RMSNorm_1(x)), {"head_norms":
0}, {"shared": 0}, {"score_sigmoid": 0} (softmax over the experts),
{"top_k": 7}, {"routed_scaling": 1.0}, {"norm_topk": 0}, {"dense": 0}
(the leading dense layer given the experts' half, with the next layer's
router, experts and shared expert), {"operands_bf16": 1} (every product
rounds its operands to bfloat16 and sums in float32: the precision the
configuration states), {"accumulate_bf16": 1} (operands and result
rounded to bfloat16: the precision below it).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0
KINDS = ("full_attention", "sliding_attention")
# the sound reference; None: the configuration's own value
SOUND = {"kv_group_full": None, "rotary_full": None, "yarn_full": 1.0,
         "theta_swap": 0.0, "window": None, "gate": 1.0,
         "gate_softplus": 1.0, "gate_normed": 1.0, "head_norms": 1.0,
         "shared": 1.0, "score_sigmoid": 1.0, "top_k": None,
         "routed_scaling": None, "norm_topk": 1.0, "dense": 1.0,
         "operands_bf16": 0.0, "accumulate_bf16": 0.0}


def kinds(cfg: dict) -> list[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def q_heads(cfg: dict, kind: str) -> int:
    """Query heads of a layer of that kind."""
    per_layer = cfg.get("num_attention_heads_per_layer")
    if per_layer is None:
        return cfg["num_attention_heads"]
    return per_layer[cfg["layer_types"].index(kind)]


def rotary_factor(cfg: dict, kind: str) -> float:
    return float(cfg["rope_parameters"][kind].get(
        "partial_rotary_factor", cfg.get("partial_rotary_factor", 1)))


def dense_layers(cfg: dict) -> int:
    """The leading run of "dense" in `mlp_layer_types`."""
    types = list(cfg.get("mlp_layer_types", ()))[:cfg["num_hidden_layers"]]
    return next((n for n, t in enumerate(types) if t != "dense"), len(types))


def with_faults(cfg: dict, faults=None) -> dict:
    """SOUND with `faults` laid over it, every value a number."""
    unknown = set(faults or {}) - set(SOUND)
    if unknown:
        raise ValueError(f"no such fault: {sorted(unknown)}")
    out = {**SOUND, **(faults or {})}
    own = {"kv_group_full": q_heads(cfg, KINDS[0])
           // cfg["num_key_value_heads"],
           "rotary_full": rotary_factor(cfg, KINDS[0]),
           "window": cfg["sliding_window"],
           "top_k": cfg["num_experts_per_tok"],
           "routed_scaling": cfg.get("moe_routed_scaling_factor", 1.0)}
    return {k: float(own[k]) if v is None else v for k, v in out.items()}


def _on(flag):
    return flag > 0.5


def _round(x):
    """x at bfloat16's precision. Not a cast there and back: the
    compiler may drop that pair (`xla_allow_excess_precision`)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot(a, b, dims, faults):
    """One product, always float32 at the highest precision. At the
    stated precision its operands are rounded to bfloat16 first (their
    products are then exact in float32, and the sum is float32's: what a
    bfloat16 product that accumulates in float32 computes); the control
    rounds the result too. A `where` on the flags, not a branch: one
    product a call whatever the precision."""
    stated = _on(faults["operands_bf16"]) | _on(faults["accumulate_bf16"])
    out = jax.lax.dot_general(
        jnp.where(stated, _round(a), a), jnp.where(stated, _round(b), b),
        dims, precision="highest", preferred_element_type=jnp.float32)
    return jnp.where(_on(faults["accumulate_bf16"]), _round(out), out)


def _matmul(a, b, faults):
    """(..., k) x (k, n)."""
    return _dot(a, b, (((a.ndim - 1,), (0,)), ((), ())), faults)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def swiglu(v, gate, up, down, faults):
    return _matmul(jax.nn.silu(_matmul(v, gate, faults))
                   * _matmul(v, up, faults), down, faults)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def inv_frequencies(rope: dict, dims: int) -> tuple[np.ndarray, float]:
    """(inverse frequencies (dims / 2,), the factor on cos and sin) of a
    `rope_parameters` entry of type "default" or "yarn", over the `dims`
    dimensions that rotate."""
    exponent = np.arange(0, dims, 2, dtype=np.float64) / dims
    theta = float(rope["rope_theta"])
    inv_freq = theta ** -exponent
    if rope["rope_type"] == "default":
        return inv_freq, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(rope["rope_type"])
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):      # the pair that turns this often in orig
        return (dims * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
    # pairs below `low` keep their frequency, pairs above `high` are
    # interpolated (divided by the factor), a linear blend between
    inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
    return inv_freq, float(rope.get("attention_factor")
                           or 0.1 * math.log(factor) + 1.0)


def rotation(rope: dict, head_dim: int, dims: int, seq_len: int):
    """A head's rotation as three arrays over its whole width: (with
    (S, D), plus (S, D), partner (D,)), so that rotated = x * with +
    x[..., partner] * plus. Dimension j < dims / 2 pairs with j + dims /
    2: (a, b) -> (a cos - b sin, b cos + a sin); a dimension from `dims`
    on passes (with 1, plus 0, its own partner)."""
    inv_freq, scale = inv_frequencies(rope, dims)
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    cos, sin = np.cos(angle) * scale, np.sin(angle) * scale
    rest = head_dim - dims
    half = dims // 2
    return (np.concatenate([cos, cos, np.ones((seq_len, rest))],
                           axis=1).astype(np.float32),
            np.concatenate([-sin, sin, np.zeros((seq_len, rest))],
                           axis=1).astype(np.float32),
            np.concatenate([np.arange(half) + half, np.arange(half),
                            np.arange(dims, head_dim)]).astype(np.int32))


def rotation_readings(cfg: dict, kind: str, seq_len: int):
    """Every reading of a kind's rotation the faults can ask for, made
    here on the host in float64 and stacked: [the configuration's, each
    kind rotating by the other's theta, and of a full layer: the whole
    head rotating, default RoPE for YaRN]. A compiled program takes the
    stacks as arguments (`tables`): as constants they are its size."""
    dh = cfg["head_dim"]
    own = dict(cfg["rope_parameters"][kind])
    other = cfg["rope_parameters"].get(KINDS[1 - KINDS.index(kind)], own)
    dims = int(rotary_factor(cfg, kind) * dh)
    readings = [rotation(own, dh, dims, seq_len),
                rotation(dict(own, rope_theta=other["rope_theta"]), dh,
                         dims, seq_len)]
    if kind == KINDS[0]:
        readings += [rotation(own, dh, dh, seq_len),
                     rotation(dict(own, rope_type="default"), dh, dims,
                              seq_len)]
    return tuple(np.stack(part) for part in zip(*readings))


def rotation_tables(cfg: dict, seq_len: int) -> dict:
    """kind -> `rotation_readings`, for the kinds the layers have."""
    return {kind: rotation_readings(cfg, kind, seq_len)
            for kind in sorted(set(kinds(cfg)))}


def chosen_rotation(cfg: dict, kind: str, readings, faults):
    """The reading the flags ask for (one fault at a time)."""
    index = jnp.int32(_on(faults["theta_swap"]))
    if kind == KINDS[0]:
        whole = faults["rotary_full"] > rotary_factor(cfg, kind) + 1e-6
        plain = ~_on(jnp.asarray(faults["yarn_full"]))
        index = jnp.where(whole, 2, jnp.where(plain, 3, index))
    return tuple(jnp.asarray(part)[index] for part in readings)


def rotate(x, table):
    """x (H, S, D) by a `rotation`."""
    with_, plus, partner = table
    return x * with_ + jnp.take(x, partner, axis=-1) * plus


# ---------------------------------------------------------------------------
# the two halves of a layer
# ---------------------------------------------------------------------------

def attention(q, k, v, kv_of, window, faults, q_block: int = 128):
    """One history: q (H, S, D), k / v (G, S, D), query head h reads
    key-value head kv_of[h] -> (H, S, D). Causal; `window` None (full)
    or the number of keys a row sees, itself among them."""
    hq, s, d = q.shape
    k, v = jnp.take(k, kv_of, axis=0), jnp.take(v, kv_of, axis=0)
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        rows = i * q_block + jnp.arange(q_block)
        q_i = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, axis=1)
        scores = _dot(q_i, k, (((2,), (2,)), ((0,), (0,))),
                      faults) / math.sqrt(d)
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep = keep & (rows[:, None] - cols[None, :] < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _dot(probs, v, (((2,), (1,)), ((0,), (0,))), faults)

    out = jax.lax.map(block, jnp.arange((s + pad) // q_block))  # (nb,H,qb,D)
    return out.transpose(1, 0, 2, 3).reshape(hq, s + pad, d)[:, :s]


def attention_half(lp, x, kind: str, table, cfg: dict, faults):
    """x (S, d) -> h = x + the gated attention of its norm."""
    s = x.shape[0]
    hq, hkv, dh = q_heads(cfg, kind), cfg["num_key_value_heads"], cfg[
        "head_dim"]
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, lp["norm1"], eps)

    def heads(w, count, gain=None):
        t = _matmul(y, w, faults).reshape(s, count, dh).transpose(1, 0, 2)
        if gain is None:
            return t
        return jnp.where(_on(faults["head_norms"]),
                         rms_norm(t, lp[gain], eps), t)

    normed = cfg.get("use_qk_norm", False)
    q = rotate(heads(lp["wq"], hq, "q_head_norm" if normed else None), table)
    k = rotate(heads(lp["wk"], hkv, "k_head_norm" if normed else None), table)
    v = heads(lp["wv"], hkv)
    full = kind == KINDS[0]
    group = faults["kv_group_full"] if full else hq // hkv
    kv_of = jnp.minimum(jnp.floor(jnp.arange(hq) / group),
                        hkv - 1).astype(jnp.int32)
    o = attention(q, k, v, kv_of, None if full else faults["window"], faults)
    if cfg.get("gating"):
        z = _matmul(jnp.where(_on(faults["gate_normed"]), y, x),
                    lp["w_gate_heads"], faults)             # (S, H)
        gamma = jnp.where(_on(faults["gate_softplus"]), jax.nn.softplus(z),
                          jax.nn.sigmoid(z))
        o = o * jnp.where(_on(faults["gate"]), gamma, 1.0).T[:, :, None]
    return x + _matmul(o.transpose(1, 0, 2).reshape(s, hq * dh), lp["wo"],
                       faults)


def routing(u, router, bias, cfg: dict, faults):
    """(T, d) -> (weights (T, E) float32: w_e for the experts in a
    token's top k, 0 elsewhere; chosen (T, E) bool)."""
    k = cfg["num_experts_per_tok"]
    logits = _matmul(u, router, faults)
    s = jnp.where(_on(faults["score_sigmoid"]), jax.nn.sigmoid(logits),
                  jax.nn.softmax(logits, axis=-1))
    _, ids = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    taken = jnp.arange(k) < faults["top_k"]      # the largest come first
    w = jnp.take_along_axis(s, ids, axis=-1) * taken
    w = jnp.where(_on(faults["norm_topk"]),
                  w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), w)
    w = w * faults["routed_scaling"]
    rows = jnp.arange(u.shape[0])[:, None]
    return (jnp.zeros_like(s).at[rows, ids].set(w),
            jnp.zeros(s.shape, bool).at[rows, ids].set(
                jnp.broadcast_to(taken, ids.shape)))


def held(cfg: dict) -> tuple[int, int]:
    return tuple(cfg.get("experts_held", (0, cfg["num_experts"])))


def experts_half(lp, h, cfg: dict, faults, share=None, shared: bool = True):
    """h (T, d) -> (the held experts' part of the layer's addend (`w_*`
    hold the experts [share[0], share[1]) only), plus the shared
    expert's where `shared`; tokens by routed expert (E,) int32). share =
    (0, E) with every expert's matrices is the whole layer."""
    lo, hi = share or held(cfg)
    u = rms_norm(h, lp["norm2"], cfg["rms_norm_eps"])
    weights, chosen = routing(u, lp["router"], lp["router_bias"], cfg,
                              faults)

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, w = e
        return out + w[:, None] * swiglu(u, gate, up, down, faults), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weights[:, lo:hi].T))
    if shared:
        out = out + faults["shared"] * swiglu(
            u, lp["shared_gate"], lp["shared_up"], lp["shared_down"], faults)
    return out, jnp.sum(chosen, axis=0, dtype=jnp.int32)


def dense_half(lp, h, cfg: dict, faults):
    u = rms_norm(h, lp["norm2"], cfg["rms_norm_eps"])
    return swiglu(u, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], faults)


def layer(lp, x, kind: str, table, cfg: dict, faults, after=None):
    """One history x (S, d) through one layer -> (x', tokens by routed
    expert (E,), zeros of a dense layer). `after`: of a dense layer, the
    layer that follows it, whose router, experts and shared expert the
    fault {"dense": 0} gives this one."""
    h = attention_half(lp, x, kind, table, cfg, faults)
    if "router" in lp:
        out, counts = experts_half(lp, h, cfg, faults)
        return h + out, counts
    none = jnp.zeros(cfg["num_experts_routed"], jnp.int32)
    if after is None or "router" not in after:
        return h + dense_half(lp, h, cfg, faults), none
    wrong = dict(after, norm2=lp["norm2"])
    out = jax.lax.cond(
        _on(faults["dense"]),
        lambda: dense_half(lp, h, cfg, faults),
        lambda: experts_half(wrong, h, cfg, faults)[0])
    return h + out, none


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def hidden_states(params, ids, cfg: dict, faults=None, tables=None):
    """ids (B, S) -> (x_L (B, S, d), before the final norm; tokens by
    routed expert of every layer that routes (routers, E)); a layer a
    history at a time, recomputed in the backward pass. `tables`:
    `rotation_tables` of the length, made here if not given."""
    faults = with_faults(cfg, faults)
    layers = params["layers"]
    if tables is None:
        tables = rotation_tables(cfg, ids.shape[1])
    tables = {kind: chosen_rotation(cfg, kind, readings, faults)
              for kind, readings in tables.items()}
    x = params["embed"][ids]
    counts = []
    for n, (lp, kind) in enumerate(zip(layers, kinds(cfg))):
        after = layers[n + 1] if n + 1 < len(layers) else None
        one = jax.checkpoint(partial(layer, kind=kind, cfg=cfg))
        x, count = jax.lax.map(
            lambda x_b, lp=lp, one=one, kind=kind, after=after: one(
                lp, x_b, table=tables[kind], faults=faults, after=after), x)
        if "router" in lp:
            counts.append(jnp.sum(count, axis=0))
    return x, (jnp.stack(counts) if counts else None)


def logits(params, ids, cfg: dict, faults=None):
    """ids (B, S) -> (B, S, rows). Small sizes only."""
    faults = with_faults(cfg, faults)
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, ids, cfg, faults)
        return _matmul(rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
                       params["head"].T, faults)


def loss(params, tokens, cfg: dict, faults=None, chunk: int = 2048,
         tables=None):
    """tokens (B, S + 1) -> mean cross-entropy of tokens[:, 1:] given
    tokens[:, :-1], the logits a chunk of tokens at a time."""
    faults = with_faults(cfg, faults)
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(params, tokens[:, :-1], cfg, faults, tables)
        d = x.shape[-1]
        xn = rms_norm(x, params["final_norm"],
                      cfg["rms_norm_eps"]).reshape(-1, d)
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


def routed_counts(params, tokens, cfg: dict, faults=None, tables=None):
    """Tokens of a step by routed expert, for every layer that routes in
    order: (routers, E) int32."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, tokens[:, :-1], cfg, faults,
                             tables)[1]
