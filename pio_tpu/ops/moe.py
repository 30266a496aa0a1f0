"""Mixture-of-experts FFNs: two layers for two engines.

**The capacity-slot Switch layer** (`moe_ffn`, `moe_ffn_ep`; the SASRec-
style encoder of models/sequence.py, `moe_experts > 0`). Net-new beyond
the reference's capability set (SURVEY.md section 5 notes the reference
has no sequence models at all):
 * routing and dispatch are ONE-HOT MATMULS, not gathers: tokens are
   combined into per-expert capacity slots with a (tokens, experts*cap)
   dispatch matrix, einsums the MXU tiles well, and shapes stay static
   (capacity-dropped tokens pass through on the residual path, the
   standard Switch-Transformer treatment);
 * expert parallelism shards the EXPERT axis over mesh devices with
   `shard_map`: tokens are exchanged to their experts' devices via
   `jax.lax.all_to_all` over ICI, expert FFNs run local dense matmuls,
   and a second all_to_all returns expert outputs to the tokens' devices;
 * the router's load-balance auxiliary loss (mean fraction x mean prob
   per expert) keeps experts busy so capacity drops stay rare.
Single-device (ep=1) and expert-parallel paths compute the same function;
tests pin them together and pin top-1 routing against a per-token loop.

**One expert-parallel rank's part of a dropless top-k layer**
(`held_moe_ffn`; the block stack of models/seq_blocks.py). The layer is
told which experts it holds (`HeldExperts.held` of `n_routed`), routes
every token over ALL of them and computes what its own add, by one fused
grouped op (`grouped_swiglu`, or for experts of two matrices with relu^2
between them `grouped_relu2`; Pallas) over rows sorted by expert: no
capacity, nothing dropped, no exchange on one chip. The router scores by
softmax (top-k of the probabilities) or by sigmoid (`score="sigmoid"`:
selection on score + a bias that takes no gradient, weights from the
unbiased score, normalised and scaled: the aux-loss-free balancing of
`topk_method: noaux_tc`); with a bias it also counts the tokens of every
routed expert, which the bias rule of the train step needs. A shared
expert is the caller's (it is a dense FFN beside this layer).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 4
    d_model: int = 64
    d_ff: int = 128
    capacity_factor: float = 1.25  # slots per expert = cf * tokens/experts


def init_moe_params(key, cfg: MoEConfig) -> dict:
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / np.sqrt(cfg.d_model)
    s2 = 1.0 / np.sqrt(cfg.d_ff)
    return {
        "router": jax.random.normal(kr, (cfg.d_model, cfg.n_experts)) * s1,
        "w_in": jax.random.normal(
            k1, (cfg.n_experts, cfg.d_model, cfg.d_ff)) * s1,
        "b_in": jnp.zeros((cfg.n_experts, cfg.d_ff)),
        "w_out": jax.random.normal(
            k2, (cfg.n_experts, cfg.d_ff, cfg.d_model)) * s2,
        "b_out": jnp.zeros((cfg.n_experts, cfg.d_model)),
    }


def _capacity(n_tokens: int, n_experts: int, cf: float) -> int:
    return max(1, int(np.ceil(cf * n_tokens / n_experts)))


def _route(x, router, n_experts: int, capacity: int):
    """Top-1 routing -> (dispatch (T, E, C), combine (T, E, C), aux_loss).

    dispatch is a 0/1 tensor placing each kept token into its expert's
    next free capacity slot; combine carries the router probability for
    the weighted return path. Tokens beyond capacity have all-zero rows
    (they fall through on the residual connection)."""
    logits = x @ router                       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)       # (T,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    one_hot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)  # (T, E)
    # position of each token within its expert's queue (exclusive cumsum)
    pos = jnp.cumsum(one_hot, axis=0) - one_hot          # (T, E)
    pos = jnp.sum(pos * one_hot, axis=1)                 # (T,)
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)  # (T, C)
    dispatch = one_hot[:, :, None] * pos_oh[:, None, :]  # (T, E, C)
    dispatch = dispatch * keep[:, None, None]
    combine = dispatch * gate[:, None, None]

    # Switch-Transformer load-balance loss: E * sum_e f_e * P_e
    frac = one_hot.mean(axis=0)               # fraction routed per expert
    mean_prob = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def _expert_ffn(params, xs):
    """xs: (E, C, D) slots -> (E, C, D); one batched dense FFN per expert."""
    h = jnp.einsum("ecd,edf->ecf", xs, params["w_in"])
    h = jax.nn.relu(h + params["b_in"][:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    return out + params["b_out"][:, None, :]


def moe_ffn(params, x, cfg: MoEConfig):
    """Single-device MoE FFN. x: (T, D) -> (y (T, D), aux_loss)."""
    T = x.shape[0]
    cap = _capacity(T, cfg.n_experts, cfg.capacity_factor)
    dispatch, combine, aux = _route(x, params["router"], cfg.n_experts, cap)
    slots = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, D)
    outs = _expert_ffn(params, slots)
    y = jnp.einsum("tec,ecd->td", combine, outs)
    return y, aux


def moe_ffn_ep(params, x, cfg: MoEConfig, mesh: Mesh, axis: str = "data"):
    """Expert-parallel MoE FFN over `axis`: tokens sharded per device,
    experts sharded per device; two all_to_all collectives move capacity
    slots to and from the experts' home devices.

    x: (T, D) GLOBAL tokens (T divisible by mesh[axis]). The router is
    replicated; w_in/b_in/w_out/b_out are sharded on the expert axis.
    Returns (y (T, D), aux_loss).

    Capacity semantics: each device budgets cf * t_local / E slots per
    expert from ITS shard (Switch-style), vs moe_ffn's one global
    cf * T / E pool — so a skewed routing distribution can drop tokens
    here that the single-device path keeps. Equivalence with moe_ffn
    (which tests pin, up to float reassociation) holds exactly when no
    expert exceeds capacity on any device."""
    n_dev = mesh.shape[axis]
    if cfg.n_experts % n_dev != 0:
        raise ValueError(
            f"n_experts ({cfg.n_experts}) must divide over {n_dev} devices"
        )
    T = x.shape[0]
    if T % n_dev != 0:
        raise ValueError(
            f"token count ({T}) must divide over {n_dev} devices"
        )
    t_local = T // n_dev
    cap = _capacity(t_local, cfg.n_experts, cfg.capacity_factor)

    spec_tok = P(axis)                # tokens: leading dim sharded
    spec_exp = P(axis)                # expert tensors: expert dim sharded
    spec_rep = P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            {"router": spec_rep, "w_in": spec_exp, "b_in": spec_exp,
             "w_out": spec_exp, "b_out": spec_exp},
            spec_tok,
        ),
        out_specs=(spec_tok, spec_rep),
        check_vma=False,
    )
    def run(p_local, x_local):
        # local routing against ALL experts (router replicated)
        dispatch, combine, aux = _route(
            x_local, p_local["router"], cfg.n_experts, cap
        )
        slots = jnp.einsum("tec,td->ecd", dispatch, x_local)  # (E, C, D)
        # slots for expert e live on every device; all_to_all rotates the
        # expert axis so device k receives ITS experts' slots from every
        # device: (E, C, D) -> (E/n, n*C, D) after reshape
        e_loc = cfg.n_experts // n_dev
        shuffled = jax.lax.all_to_all(
            slots.reshape(n_dev, e_loc, cap, -1),
            axis, split_axis=0, concat_axis=0, tiled=False,
        )  # (n_dev, e_loc, cap, D): source-device major
        shuffled = jnp.moveaxis(shuffled, 0, 1).reshape(
            e_loc, n_dev * cap, -1
        )
        outs = _expert_ffn(
            {k: p_local[k] for k in ("w_in", "b_in", "w_out", "b_out")},
            shuffled,
        )  # (e_loc, n*cap, D)
        back = jnp.moveaxis(
            outs.reshape(e_loc, n_dev, cap, -1), 1, 0
        )  # (n_dev, e_loc, cap, D)
        returned = jax.lax.all_to_all(
            back, axis, split_axis=0, concat_axis=0, tiled=False,
        ).reshape(cfg.n_experts, cap, -1)
        y = jnp.einsum("tec,ecd->td", combine, returned)
        # aux averaged across devices (it is a mean statistic)
        aux = jax.lax.pmean(aux, axis)
        return y, aux

    shard_p = {
        "router": jax.device_put(
            params["router"], NamedSharding(mesh, spec_rep)),
        "w_in": jax.device_put(params["w_in"], NamedSharding(mesh, spec_exp)),
        "b_in": jax.device_put(params["b_in"], NamedSharding(mesh, spec_exp)),
        "w_out": jax.device_put(
            params["w_out"], NamedSharding(mesh, spec_exp)),
        "b_out": jax.device_put(
            params["b_out"], NamedSharding(mesh, spec_exp)),
    }
    xs = jax.device_put(x, NamedSharding(mesh, spec_tok))
    return run(shard_p, xs)


# ---------------------------------------------------------------------------
# One expert-parallel rank's part of a dropless top-k layer
# ---------------------------------------------------------------------------
#
# The layer is told which experts it holds (`held = [lo, hi)` of
# `n_routed`). It routes every token over ALL the experts (top-k of the
# softmax, weights optionally normalised over the k), and computes what
# its own experts add: the tokens' rows are sorted by expert into groups
# whose sizes are whatever the routing gave (no capacity, nothing
# dropped), each group padded to whole tiles, and the experts' FFN runs
# as one fused grouped op over the tiles (`grouped_swiglu`, Pallas
# kernels; a tile belongs to one expert): gate and up in one kernel with
# SwiGLU in its epilogue, the down product, and backward d hidden with
# SwiGLU's derivative in its epilogue, dx from both weights in one
# kernel, the three weight gradients in float32. The kernels take the
# weights as the parameters store them (float32 masters) and round a
# block after its DMA; backward they contract over the weights' last
# axis: no cast, concatenation or transpose of a (G, d, f) array, and no
# element-wise pass over an (M, f) array, is left outside them (PR 43).
# The buffer is sized for the most the held experts can be sent, but
# little follows its size: the groups are packed at its front,
# `tiles_used` tiles hold them, and the kernels (whose grids are
# `tiles_used` tiles long and which prefetch the tiles' experts) and the
# move into the buffer visit those tiles alone. The plan
# (`dispatch_plan`) is one sort of the choices by expert, one running
# count of each expert's tokens and a few numbers a tile: a tile's rows
# are a slice of the sorted order (so `rows_from_tokens` is a loop of
# `tiles_used` steps, each gathering one tile's token rows), and a
# choice's row is its group's first row plus the tokens before it that
# chose the expert (so `tokens_from_rows` gathers k rows a token).
# Nothing is scattered, forward or backward, and no scalar is gathered
# for every row or every choice: on this chip a scatter takes 0.3 ms to
# begin and 45-120 ns an update, a gathered row 5-8 ns (PR 34, PERF.md
# section 6). Rows of tiles behind `tiles_used` are never written and
# never added up. What is still done over the whole buffer: its zero
# fill before the moves (`_unwritten`), and over every choice the gather
# of the return; a group is padded to whole tiles of 512 rows; and a
# caller that maps the layer over histories adds the weights' gradients
# up once a history (ROADMAP S7b).
# What the absent experts would add is left out: on the chips of a
# deployment the exchange adds it, and on one chip the layer runs without
# its exchange.


@dataclass(frozen=True)
class HeldExperts:
    n_routed: int                  # the router's width: every expert
    top_k: int
    held: tuple[int, int]          # [lo, hi): the experts this rank holds
    norm_topk: bool = True         # weights divided by their sum over k
    tile_rows: int = 512           # rows of one tile of the grouped product
    score: str = "softmax"         # or "sigmoid": each expert scored alone
    scale: float = 1.0             # on the weights (`routed_scaling_factor`)
    activation: str = "swiglu"     # or "relu2": W_down relu(W_up x)^2, no gate

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    def row_capacity(self, n_tokens: int) -> int:
        """Rows of the sorted buffer: the most the held experts can be
        sent (every token's k choices all held), each group rounded up
        to whole tiles and never empty."""
        tm = self.tile_rows
        most = n_tokens * min(self.top_k, self.n_held)
        return -(-most // tm) * tm + self.n_held * tm


def route_top_k(logits, top_k: int, norm: bool, score: str = "softmax",
                bias=None, scale: float = 1.0):
    """(T, E) float32 router logits -> (expert ids (T, k) int32, weights
    (T, k) float32): the k largest scores, ties to the lower id. Scores
    are the softmax over the experts, or with `score="sigmoid"` each
    expert's own sigmoid. `bias` (E,) moves the selection only: the k
    largest of score + bias are chosen and weighed by their unbiased
    scores (no gradient reaches the bias). `norm` divides the weights by
    their sum over the k, `scale` multiplies them."""
    logits = logits.astype(jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router score {score!r}")
    chosen_by = scores if bias is None else (
        scores + jax.lax.stop_gradient(bias))
    _, ids = jax.lax.top_k(chosen_by, top_k)
    # a choice's own score by comparison, not `take_along_axis`: no scalar
    # is gathered, and none scattered when the gradient comes back
    weights = jnp.sum(jnp.where(
        ids[..., None] == jnp.arange(scores.shape[-1]),
        scores[..., None, :], 0.0), axis=-1)
    if norm and score == "sigmoid":    # sigmoids can all be tiny
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    elif norm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return ids.astype(jnp.int32), weights


def dispatch_plan(ids, cfg: HeldExperts) -> dict:
    """Where each (token, choice) goes in the buffer sorted by held
    expert, both ways, for ids (T, k) that name an expert at most once a
    token. -> order (T*k + tile,): the flat (token, choice) indices
    sorted by held expert, ties in their own order; tile_expert (M /
    tile,): the tiles' experts (the last expert's past the last group);
    tile_start, tile_valid (M / tile,): tile i's first `tile_valid[i]`
    rows hold the choices `order[tile_start[i]:][:tile_valid[i]]`, its
    other rows are padding; tiles_used (1,): the tiles that hold a group
    (an empty group owns one tile of padding), nothing behind them is
    written or added up; choice_row (T, k): the row of a choice, or M
    where its expert is not held; counts (n_held,): tokens per held
    expert; dropped (): choices of held experts that found no row (0 by
    construction; reported, asserted by the trainer)."""
    t, k = ids.shape
    lo, hi = cfg.held
    n_held, tm = cfg.n_held, cfg.tile_rows
    cap = cfg.row_capacity(t)
    n_tiles = cap // tm
    experts = jnp.arange(n_held, dtype=jnp.int32)
    local = jnp.where((ids >= lo) & (ids < hi), ids - lo, n_held)
    order = jnp.argsort(local.reshape(-1), stable=True).astype(jnp.int32)
    is_expert = local[:, :, None] == experts        # (T, k, n_held)
    chose = jnp.sum(is_expert, axis=1, dtype=jnp.int32)
    before = jnp.cumsum(chose, axis=0)              # (T, n_held)
    counts = before[-1]
    padded = jnp.maximum(-(-counts // tm) * tm, tm)
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    first_row = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.sum(pad_end[None, :] <= first_row[:, None], axis=1,
                dtype=jnp.int32), n_held - 1)
    # a tile's place in its group, from a handful of numbers an expert
    into = first_row - pad_start[tile_expert]
    tile_valid = jnp.clip(counts[tile_expert] - into, 0, tm)
    tile_start = (jnp.cumsum(counts) - counts)[tile_expert] + into
    tiles_used = jnp.minimum(pad_end[-1:] // tm, n_tiles).astype(jnp.int32)
    # a choice's row: its group's first, and the earlier tokens' of it
    row = (pad_start + before - chose)[:, None, :]  # (T, 1, n_held)
    choice_row = jnp.sum(jnp.where(is_expert, row, 0), axis=2)
    choice_row = jnp.where((local < n_held) & (choice_row < cap),
                           choice_row, cap)
    return {"order": jnp.concatenate([order, jnp.zeros(tm, jnp.int32)]),
            "tile_expert": tile_expert, "tile_start": tile_start,
            "tile_valid": tile_valid, "tiles_used": tiles_used,
            "choice_row": choice_row, "counts": counts,
            "dropped": jnp.sum(counts) - jnp.sum(choice_row < cap)}


def _tile(dim: int, most: int) -> int:
    """The largest divisor of `dim` that is a multiple of 128 and at most
    `most`; the whole of a dimension that has none (tiny test sizes)."""
    for t in range(most - most % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


# Weight blocks of one grid step, in bytes as the parameters store them:
# with the kernels' double buffering, the blocks' copies in the products'
# type and the accumulators a step stays under `_VMEM` of the chip's 128
# MiB. A grid step costs ~0.35 us and a block that spans the depth is
# fetched once an expert, not once a tile, so blocks are as wide and as
# deep as that allows: at the two cells' widths a product is one or two
# column blocks of full depth.
_WEIGHT_BLOCKS = 17 << 20
_VMEM = 100 << 20


def _interpret() -> bool:
    return jax.devices()[0].platform == "cpu"


def _swiglu(gate, up):
    """hidden, and what the backward pass reads of the two products."""
    return jax.nn.silu(gate) * up, gate, up


def _swiglu_back(d_hidden, gate, up):
    """d hidden -> (d gate, d up) of hidden = silu(gate) * up."""
    s = jax.nn.sigmoid(gate)
    return d_hidden * up * s * (1.0 + gate * (1.0 - s)), d_hidden * gate * s


def _tiles_kernel(into, n_rows: int, n_seen: int, n_acc: int, epilogue,
                  dims):
    """The body of `_over_tiles`: weight p times rows operand `into[p][0]`
    is added into accumulator `into[p][1]` a depth block at a time; at
    the last, `epilogue(*accumulators, *seen blocks)` in float32 gives
    the output blocks, as many of its values as there are outputs."""
    from jax.experimental import pallas as pl

    def kernel(te_ref, *refs):
        rows, refs = refs[:n_rows], refs[n_rows:]
        weights, refs = refs[:len(into)], refs[len(into):]
        seen, refs = refs[:n_seen], refs[n_seen:]
        outs, accs = refs[:-n_acc], refs[-n_acc:]
        kk, n_k = pl.program_id(2), pl.num_programs(2)

        @pl.when(kk == 0)
        def _init():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        sums = [None] * n_acc
        for w_ref, (r, a) in zip(weights, into):
            x = rows[r][...]
            # the stored block (a float32 master) is rounded here, after
            # its DMA: no copy of the weights in HBM
            part = jax.lax.dot_general(
                x, w_ref[0].astype(x.dtype), dims,
                preferred_element_type=jnp.float32)
            sums[a] = part if sums[a] is None else sums[a] + part
        for acc, total in zip(accs, sums):
            acc[...] += total

        @pl.when(kk == n_k - 1)
        def _emit():
            values = epilogue(*(acc[...] for acc in accs),
                              *(s[...].astype(jnp.float32) for s in seen))
            for out, value in zip(outs, values):
                out[...] = value.astype(out.dtype)

    return kernel


def _over_tiles(name: str, rows, weights, into, tile_expert, tiles_used,
                tm: int, *, transposed=False, seen=(), epilogue=None,
                n_out: int = 1):
    """Grouped products over the tiles that hold a group, with what
    follows them in the kernel's epilogue. rows: (M, K) arrays, every
    `tm` rows of one expert; weights: (G, K, N) arrays as the parameters
    store them, (G, N, K) contracted over their last axis with
    `transposed`; `into[p]` = (the rows operand weight p multiplies, the
    float32 accumulator its product is added into); seen: (M, N) arrays
    the epilogue reads beside the accumulators. -> the first `n_out`
    of the epilogue's values, (M, N) arrays in the rows' dtype. Column
    blocks are the grid's outer axis and the tiles its middle one, so
    an expert's consecutive tiles reuse a weight block that spans the
    depth without another DMA. The tiles' axis is `tiles_used` long (a
    grid bound read at run time): a tile behind the last group costs no
    step, and its rows of the results are never written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows[0].shape
    w = weights[0]
    n = w.shape[1] if transposed else w.shape[2]
    tk = _tile(k, 2304)
    tn = _tile(n, _WEIGHT_BLOCKS // (len(weights) * tk * w.dtype.itemsize))
    n_acc = 1 + max(a for _, a in into)

    def w_map(j, i, kk, te):
        return (te[i], j, kk) if transposed else (te[i], kk, j)

    outs = tuple(jax.ShapeDtypeStruct((m, n), rows[0].dtype)
                 for _ in range(n_out))
    return pl.pallas_call(
        _tiles_kernel(
            into, len(rows), len(seen), n_acc,
            epilogue or (lambda acc: (acc,)),
            (((1,), (1 if transposed else 0,)), ((), ()))),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, tiles_used[0], k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lambda j, i, kk, te: (i, kk))
                      for _ in rows]
            + [pl.BlockSpec((1, tn, tk) if transposed else (1, tk, tn),
                            w_map) for _ in weights]
            + [pl.BlockSpec((tm, tn), lambda j, i, kk, te: (i, j))
               for _ in seen],
            out_specs=[pl.BlockSpec((tm, tn), lambda j, i, kk, te: (i, j))
                       for _ in outs],
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_acc),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=_interpret(),
    )(tile_expert, *rows, *weights, *seen)


def _gmm(x, w, tile_expert, tiles_used, tm: int, transposed=False):
    """x (M, K) times each tile's w[e] (K, N), or with `transposed` its
    w[e] (N, K) over the last axis -> (M, N)."""
    return _over_tiles("moe_gmm", [x], [w], [(0, 0)], tile_expert,
                       tiles_used, tm, transposed=transposed)[0]


def _tgmm_kernel(te_ref, x_ref, dy_ref, o_ref):
    from jax.experimental import pallas as pl

    m = pl.program_id(2)

    @pl.when((m == 0) | (te_ref[jnp.maximum(m - 1, 0)] != te_ref[m]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += jax.lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _tgmm(x, dy, tile_expert, tiles_used, n_groups: int, tm: int):
    """The weights' gradient: each group's x^T dy, (G, K, N) float32,
    over the tiles that hold a group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n = x.shape[1], dy.shape[1]
    tk = _tile(k, 2304)
    # the float32 block is written as well as read: half the budget
    tn = _tile(n, _WEIGHT_BLOCKS // 2 // (tk * 4))
    return pl.pallas_call(
        _tgmm_kernel, name="moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(k // tk, n // tn, tiles_used[0]),
            in_specs=[pl.BlockSpec((tm, tk), lambda kk, j, mm, te: (mm, kk)),
                      pl.BlockSpec((tm, tn), lambda kk, j, mm, te: (mm, j))],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda kk, j, mm, te: (te[mm], kk, j))),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=_interpret(),
    )(tile_expert, x, dy)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, tiles_used, tile_rows: int):
    """Rows sorted by expert, times their expert's matrix. x: (M, K),
    every `tile_rows` rows of one expert, padding rows zero; w: (G, K,
    N), as stored: a block is rounded to x's dtype in the kernel;
    tile_expert: (M / tile_rows,) int32, not decreasing; tiles_used:
    (1,) int32, the tiles that hold a group. The tiles behind them are
    passed over, so the product's time follows the groups and not the
    buffer, and **their rows of the result are undefined**: nothing may
    read them (the layer's moves gather the rows of choices only). Every
    expert owns at least one tile (dispatch_plan sees to it), which is
    what lets the weight gradient write every expert's block. -> (M, N)
    in x's dtype, accumulated in float32."""
    return _gmm(x, w, tile_expert, tiles_used, tile_rows)


def _gmm_fwd(x, w, tile_expert, tiles_used, tile_rows):
    return (_gmm(x, w, tile_expert, tiles_used, tile_rows),
            (x, w, tile_expert, tiles_used))


def _gmm_bwd(tile_rows, res, dy):
    x, w, tile_expert, tiles_used = res
    dx = _gmm(dy, w, tile_expert, tiles_used, tile_rows, transposed=True)
    dw = _tgmm(x, dy, tile_expert, tiles_used, w.shape[0], tile_rows)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def _gate_up(rows, w_gate, w_up, tile_expert, tiles_used, tm, kept: bool):
    """SwiGLU of the rows' two products in their kernel's epilogue ->
    [hidden], and with `kept` gate and up beside it."""
    return _over_tiles(
        "moe_gmm_swiglu", [rows], [w_gate, w_up], [(0, 0), (0, 1)],
        tile_expert, tiles_used, tm, epilogue=_swiglu,
        n_out=3 if kept else 1)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped_swiglu(rows, w_gate, w_up, w_down, tile_expert, tiles_used,
                   tile_rows: int):
    """The held experts' FFN on rows sorted by expert: `(silu(rows
    W_gate,e) * (rows W_up,e)) W_down,e` a tile, as `grouped_matmul`
    takes rows, tiles and weights (w_gate, w_up: (G, d, f), w_down: (G,
    f, d), as stored) and leaves the rows behind `tiles_used`. Three
    products forward and six backward, SwiGLU and its derivative in
    their kernels' epilogues: nothing outside the kernels passes over
    the buffer or the weights. hidden, gate and up are the rows' dtype;
    the weights' gradients leave `moe_tgmm` in float32 and are returned
    in the weights' dtype. -> (M, d) in the rows' dtype."""
    tiles = tile_expert, tiles_used, tile_rows
    hidden, = _gate_up(rows, w_gate, w_up, *tiles, kept=False)
    return grouped_matmul(hidden, w_down, *tiles)


def _swiglu_fwd(rows, w_gate, w_up, w_down, tile_expert, tiles_used,
                tile_rows):
    tiles = tile_expert, tiles_used, tile_rows
    hidden, gate, up = _gate_up(rows, w_gate, w_up, *tiles, kept=True)
    return (_gmm(hidden, w_down, *tiles),
            (rows, w_gate, w_up, w_down, hidden, gate, up, tile_expert,
             tiles_used))


def _swiglu_bwd(tile_rows, res, dy):
    rows, w_gate, w_up, w_down, hidden, gate, up, *tiles = res
    n_groups = w_gate.shape[0]
    d_gate, d_up = _over_tiles(
        "moe_gmm_dswiglu", [dy], [w_down], [(0, 0)], *tiles, tile_rows,
        transposed=True, seen=(gate, up), epilogue=_swiglu_back, n_out=2)
    d_rows, = _over_tiles(
        "moe_gmm", [d_gate, d_up], [w_gate, w_up], [(0, 0), (1, 0)],
        *tiles, tile_rows, transposed=True)
    dw = [_tgmm(x, g, *tiles, n_groups, tile_rows).astype(w.dtype)
          for x, g, w in ((rows, d_gate, w_gate), (rows, d_up, w_up),
                          (hidden, dy, w_down))]
    return d_rows, *dw, None, None


grouped_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _relu2(up):
    """hidden = relu(up)^2, and what the backward pass reads: up."""
    return jnp.square(jax.nn.relu(up)), up


def _relu2_back(d_hidden, up):
    return (2.0 * d_hidden * jax.nn.relu(up),)


def _up(rows, w_up, tile_expert, tiles_used, tm, kept: bool):
    """relu^2 of the rows' product in its kernel's epilogue -> [hidden],
    and with `kept` up beside it."""
    return _over_tiles(
        "moe_gmm_relu2", [rows], [w_up], [(0, 0)], tile_expert, tiles_used,
        tm, epilogue=_relu2, n_out=2 if kept else 1)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def grouped_relu2(rows, w_up, w_down, tile_expert, tiles_used,
                  tile_rows: int):
    """The held experts' FFN without a gate, on rows sorted by expert:
    `relu(rows W_up,e)^2 W_down,e` a tile, as `grouped_swiglu` takes
    rows, tiles and weights (w_up (G, d, f), w_down (G, f, d), as
    stored). Two products forward and four backward, relu^2 and its
    derivative in their kernels' epilogues: nothing outside the kernels
    passes over the buffer or the weights. -> (M, d) in the rows' dtype."""
    tiles = tile_expert, tiles_used, tile_rows
    hidden, = _up(rows, w_up, *tiles, kept=False)
    return grouped_matmul(hidden, w_down, *tiles)


def _relu2_fwd(rows, w_up, w_down, tile_expert, tiles_used, tile_rows):
    tiles = tile_expert, tiles_used, tile_rows
    hidden, up = _up(rows, w_up, *tiles, kept=True)
    return (_gmm(hidden, w_down, *tiles),
            (rows, w_up, w_down, hidden, up, tile_expert, tiles_used))


def _relu2_bwd(tile_rows, res, dy):
    rows, w_up, w_down, hidden, up, *tiles = res
    n_groups = w_up.shape[0]
    d_up, = _over_tiles(
        "moe_gmm_drelu2", [dy], [w_down], [(0, 0)], *tiles, tile_rows,
        transposed=True, seen=(up,), epilogue=_relu2_back)
    d_rows = _gmm(d_up, w_up, *tiles, tile_rows, transposed=True)
    dw = [_tgmm(x, g, *tiles, n_groups, tile_rows).astype(w.dtype)
          for x, g, w in ((rows, d_up, w_up), (hidden, dy, w_down))]
    return d_rows, *dw, None, None


grouped_relu2.defvjp(_relu2_fwd, _relu2_bwd)


def _unwritten(shape, dtype):
    """The sorted buffer before its tiles are written; what a row behind
    `tiles_used` holds is never read (a test fills it with NaN). Zeros: a
    buffer taken unwritten from a kernel that does nothing saved the
    pass over it and cost the step program 0.26 GB (PR 34)."""
    return jnp.zeros(shape, dtype)


def _tile_rows(plan) -> int:
    """Rows of a tile: `order` is the T * k choices and one tile more."""
    return plan["order"].shape[0] - plan["choice_row"].size


def _tile_choices(plan, i):
    """Tile i -> (its rows' place in the sorted order, their flat choices
    (tile,), which of the rows hold one)."""
    tm = _tile_rows(plan)
    start = plan["tile_start"][i]
    choice = jax.lax.dynamic_slice(plan["order"], (start,), (tm,))
    valid = jnp.arange(tm, dtype=jnp.int32) < plan["tile_valid"][i]
    return start, choice, valid


def _rows_of(x, plan, weights=None):
    """(T, d) tokens -> (M, d) rows, `tiles_used` tiles of them: a row
    holds its choice's token, times the choice's weight where (T, k)
    weights are given, padding rows of a tile zero."""
    k, tm = plan["choice_row"].shape[1], _tile_rows(plan)

    def tile(i, rows):
        _, choice, valid = _tile_choices(plan, i)
        part = x[choice // k]
        if weights is not None:
            part = (part.astype(jnp.float32)
                    * weights.reshape(-1)[choice][:, None]).astype(x.dtype)
        part = jnp.where(valid[:, None], part, 0)
        return jax.lax.dynamic_update_slice(rows, part, (i * tm, 0))

    return jax.lax.fori_loop(
        0, plan["tiles_used"][0], tile,
        _unwritten((plan["tile_expert"].shape[0] * tm, x.shape[1]), x.dtype))


# The chip gathers rows 4-5 times faster from a table the compiler can
# keep in its fast memory (128 MiB on a v5e; 2.4 against 0.55-0.64 ms for
# 65,536 rows of 2,304 bf16: PR 34). The groups are packed at the
# buffer's front, so while they end inside this many bytes the return
# gathers from that front alone.
FRONT_BYTES = 96 << 20


def _tokens_of(y, plan, weights=None):
    """(M, d) rows -> (T, d): a token's sum over its held choices' rows,
    times their weights where (T, k) weights are given, in float32. A
    choice a (T, d) slab, so the sum adds whole slabs; a choice that is
    not held reads the row of its own token's number and counts as zero
    (all of them reading one row was slower still)."""
    row = plan["choice_row"].T                              # (k, T)
    m, d = y.shape
    held = row < m
    tm = _tile_rows(plan)
    front = min(m, FRONT_BYTES // (d * y.dtype.itemsize) // tm * tm)
    at = jnp.where(held, row, jnp.arange(row.shape[1]) % front)
    rows = jax.lax.cond(plan["tiles_used"][0] * tm <= front,
                        lambda: y[:front][at], lambda: y[at])
    total = None
    for j in range(row.shape[0]):       # one pass: no (k, T, d) in float32
        part = rows[j].astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, j, None]
        part = jnp.where(held[j][:, None], part, 0.0)
        total = part if total is None else total + part
    return total


# the two moves are each other's transpose: written out, because a loop
# whose trip count the routing gives has no derivative of its own, and
# so that the backward pass gathers rows too instead of scattering them
@jax.custom_vjp
def rows_from_tokens(x, plan):
    """(T, d) tokens -> the (M, d) buffer sorted by held expert."""
    return _rows_of(x, plan)


def _rft_fwd(x, plan):
    return _rows_of(x, plan), plan


def _rft_bwd(plan, g):
    return _tokens_of(g, plan).astype(g.dtype), None


rows_from_tokens.defvjp(_rft_fwd, _rft_bwd)


@jax.custom_vjp
def tokens_from_rows(y, weights, plan):
    """(M, d) rows and the (T, k) weights of every choice -> (T, d)
    float32: each token's weighted sum over its held choices' rows."""
    return _tokens_of(y, plan, weights)


def _tfr_fwd(y, weights, plan):
    return _tokens_of(y, plan, weights), (y, weights, plan)


def _tfr_bwd(res, g):
    """d rows: the tokens' cotangents moved like the tokens, times the
    weights. d weights: a row's product with its token's cotangent,
    written at the row's place in the sorted order a tile at a time, and
    brought back to the choices' order by a sort on that order."""
    y, weights, plan = res
    t, k = weights.shape
    tm = _tile_rows(plan)
    gc = g.astype(y.dtype)

    def tile(i, at_place):
        start, choice, valid = _tile_choices(plan, i)
        own = jnp.sum(
            jax.lax.dynamic_slice(y, (i * tm, 0), (tm, y.shape[1])
                                  ).astype(jnp.float32)
            * gc[choice // k].astype(jnp.float32), axis=1)
        # a padding row's place is the next group's first: left as it is
        kept = jax.lax.dynamic_slice(at_place, (start,), (tm,))
        return jax.lax.dynamic_update_slice(
            at_place, jnp.where(valid, own, kept), (start,))

    at_place = jax.lax.fori_loop(
        0, plan["tiles_used"][0], tile,
        jnp.zeros(plan["order"].shape, jnp.float32))
    _, dw = jax.lax.sort((plan["order"][:t * k], at_place[:t * k]),
                         num_keys=1)
    return _rows_of(gc, plan, weights), dw.reshape(t, k), None


tokens_from_rows.defvjp(_tfr_fwd, _tfr_bwd)


def held_moe_ffn(params, x, cfg: HeldExperts, compute_dtype=jnp.bfloat16):
    """This rank's part of the layer for (T, d) tokens: `sum over the
    held e in top-k(x) of w_e * W_down,e(silu(x W_gate,e) * (x W_up,e))`,
    or with `cfg.activation == "relu2"` of `w_e * W_down,e relu(x
    W_up,e)^2` (no w_gate).
    params: router (d, n_routed), w_gate / w_up (n_held, d, f), w_down
    (n_held, f, d), and optionally router_bias (n_routed,), which moves
    the selection and takes no gradient. Products take `compute_dtype`
    operands and accumulate in float32. -> (y (T, d) float32, {"counts":
    tokens per held expert, "dropped": 0}, and with a bias "counts_all":
    tokens per routed expert, held or not, which the bias rule needs)."""
    k, tm = cfg.top_k, cfg.tile_rows
    xc = x.astype(compute_dtype)
    with jax.named_scope("seq.moe.route"):
        logits = jnp.dot(xc, params["router"].astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        bias = params.get("router_bias")
        ids, weights = route_top_k(logits, k, cfg.norm_topk, cfg.score,
                                   bias, cfg.scale)
        plan = dispatch_plan(ids, cfg)
        rows = rows_from_tokens(xc, plan)
    with jax.named_scope("seq.moe.gmm"):
        tiles = plan["tile_expert"], plan["tiles_used"], tm
        if cfg.activation == "relu2":
            out_rows = grouped_relu2(rows, params["w_up"], params["w_down"],
                                     *tiles)
        else:
            out_rows = grouped_swiglu(
                rows, params["w_gate"], params["w_up"], params["w_down"],
                *tiles)
    with jax.named_scope("seq.moe.combine"):
        y = tokens_from_rows(out_rows, weights, plan)
    aux = {"counts": plan["counts"], "dropped": plan["dropped"]}
    if bias is not None:
        aux["counts_all"] = jnp.sum(
            ids.reshape(-1, 1) == jnp.arange(cfg.n_routed)[None, :],
            axis=0, dtype=jnp.int32)
    return y, aux
