"""The least work of a step of the latent-attention stack (the cell
`glm-4.7-flash-ep8.train-8k-mtp`), from shapes and the window's real
group sizes alone.

As benchmark/harness/roofline_sequence.py counts: operations are
multiply-adds counted as 2, of the mathematics once, a forward and a
backward pass (a product of the weights forward, and its two backward
products: 3 x 2 x rows x k x n). What the program computes again (layers
recomputed in the backward pass, scores recomputed by the attention
kernels' backward, the masked half of the diagonal blocks, tiles padded
to whole, Adam) is not counted, so a share can only be lowered by it.

The configuration's keys are read as its file has them: `n_routed_experts`
is the experts held here, `first_k_dense_replace` the dense layers,
`num_nextn_predict_layers` the prediction modules (each one more expert
layer, one more attention and one more head product over the vocabulary
rows held).
"""

from __future__ import annotations

from benchmark.harness.roofline_sequence import attention_least, grouped_least


def layer_counts(cfg: dict) -> dict:
    """How many layers of each kind a step runs, the modules' with them."""
    mtp = cfg.get("num_nextn_predict_layers", 0)
    dense = cfg.get("first_k_dense_replace", 0)
    return {"attention": cfg["num_hidden_layers"] + mtp, "dense": dense,
            "expert": cfg["num_hidden_layers"] - dense + mtp, "heads": 1 + mtp,
            "mtp": mtp}


def latent_attention_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """Every attention layer of a step is full and causal; q.k is
    qk_nope + qk_rope wide and p.v v_head_dim, which are equal here: one
    width serves `attention_least`."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    if qk != cfg["v_head_dim"]:
        raise ValueError("q.k and p.v widths differ: count them apart")
    heads = cfg["num_attention_heads"]
    return attention_least(batch, seq_len, heads, heads, qk,
                           [None] * layer_counts(cfg)["attention"])


def latent_grouped_least(cfg: dict, rows_a_layer: float) -> dict:
    """The held experts' grouped products over `rows_a_layer` (token,
    held expert) rows in each expert layer of a step."""
    return grouped_least(rows_a_layer, cfg["n_routed_experts"],
                         cfg["hidden_size"], cfg["moe_intermediate_size"],
                         layer_counts(cfg)["expert"])


def weight_flops_a_token(cfg: dict) -> dict:
    """Multiply-adds (counted as 2) a token takes forward through the
    weights it really uses, by part, the held experts' left out (their
    rows are counted from the router's real choices)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n = layer_counts(cfg)
    latent = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
              + rkv * h * (dn + dv) + h * dv * d)
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg.get(
        "n_shared_experts", 0)
    return {
        "latent": 2 * latent * n["attention"],
        "dense": 2 * 3 * d * cfg["intermediate_size"] * n["dense"],
        "shared": 2 * shared * n["expert"],
        "router": 2 * d * cfg["num_experts_routed"] * n["expert"],
        "join": 2 * 2 * d * d * n["mtp"],
        "head": 2 * d * cfg["vocab_size"] * n["heads"],
    }


def step_least(cfg: dict, batch: int, seq_len: int,
               rows_a_layer: float) -> dict:
    """One whole step: the products of the weights actually used plus
    attention, forward and backward once. Bytes are not counted (the
    step is held against the peak FLOP/s alone: `seq_step_mfu`)."""
    tokens = batch * seq_len
    by_part = {k: 3 * v * tokens
               for k, v in weight_flops_a_token(cfg).items()}
    by_part["experts"] = latent_grouped_least(cfg, rows_a_layer)["flops"]
    by_part["attention"] = latent_attention_least(cfg, batch, seq_len)["flops"]
    return {"flops": sum(by_part.values()), "by_part": by_part}
