"""Mixture-of-experts FFN with expert parallelism (ep) over the mesh.

Net-new beyond the reference's capability set (like the sequence family it
plugs into — SURVEY.md §5 notes the reference has no sequence models at
all), this is the framework's expert-parallel building block: the MoE FFN
drops in for the dense FFN of the sequential recommender's transformer
blocks.

TPU-first design:
 * routing and dispatch are ONE-HOT MATMULS, not gathers: tokens are
   combined into per-expert capacity slots with a (tokens, experts*cap)
   dispatch matrix — einsums the MXU tiles well, and shapes stay static
   (capacity-dropped tokens pass through on the residual path, the
   standard Switch-Transformer treatment);
 * expert parallelism shards the EXPERT axis over mesh devices with
   `shard_map`: tokens are exchanged to their experts' devices via
   `jax.lax.all_to_all` over ICI (the collective the reference's Spark
   shuffle would have played), expert FFNs run local dense matmuls, and a
   second all_to_all returns expert outputs to the tokens' devices;
 * the router's load-balance auxiliary loss (mean fraction x mean prob per
   expert) keeps experts busy so capacity drops stay rare.

Single-device (ep=1) and expert-parallel paths compute the same function;
tests pin them together and pin top-1 routing against a per-token loop.
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
