"""The least work of a step of the stack of gated grouped-query layers
(the cell `laguna-xs2-ep16.train-8k-gated`), from shapes and the window's
real group sizes alone.

As benchmark/harness/roofline_sequence.py counts: operations are
multiply-adds counted as 2, of the mathematics once, a forward and a
backward pass (a product of the weights forward, and its two backward
products: 3 x 2 x rows x k x n). What the program computes again (layers
recomputed in the backward pass, scores recomputed by the attention
kernels' backward, the masked half of the diagonal blocks, tiles padded
to whole, Adam) is not counted, so a share can only be lowered by it.

The attention kernels are counted a layer kind at a time: a kind has its
own number of query heads (`num_attention_heads_per_layer`) and its own
band (a sliding layer's window, a full layer's causal half). The
configuration's keys are read as its file has them: `num_experts` is the
experts held here, `mlp_layer_types`' leading run of "dense" the dense
layers, `layer_types`' first `num_hidden_layers` entries the layers.
"""

from __future__ import annotations

from benchmark.harness.roofline_sequence import attention_least, grouped_least

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(cfg: dict) -> list[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def q_heads(cfg: dict, kind: str) -> int:
    return cfg["num_attention_heads_per_layer"][
        cfg["layer_types"].index(kind)]


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    types = list(cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])
    dense = next((n for n, t in enumerate(types) if t != "dense"),
                 len(types))
    return {FULL: kinds.count(FULL), SLIDING: kinds.count(SLIDING),
            "dense": dense, "expert": len(kinds) - dense}


def attention_by_kind(cfg: dict, batch: int, seq_len: int) -> dict:
    """kind -> one step's attention over the layers of that kind, at that
    kind's query heads and band."""
    n = layer_counts(cfg)
    return {kind: attention_least(
        batch, seq_len, q_heads(cfg, kind), cfg["num_key_value_heads"],
        cfg["head_dim"],
        [cfg["sliding_window"] if kind == SLIDING else None] * n[kind])
        for kind in (FULL, SLIDING) if n[kind]}


def gated_attention_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """Both kinds' kernels: the sum of `attention_by_kind`."""
    parts = attention_by_kind(cfg, batch, seq_len).values()
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


def gated_grouped_least(cfg: dict, rows_a_layer: float) -> dict:
    """The held experts' grouped products over `rows_a_layer` (token,
    held expert) rows in each expert layer of a step."""
    return grouped_least(rows_a_layer, cfg["num_experts"],
                         cfg["hidden_size"], cfg["moe_intermediate_size"],
                         layer_counts(cfg)["expert"])


def balanced_rows(cfg: dict, batch: int, seq_len: int) -> float:
    """(token, held expert) rows a layer at balance: every routed expert
    the same share of the tokens' choices."""
    return (batch * seq_len * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["num_experts_routed"])


def weight_flops_a_token(cfg: dict) -> dict:
    """Multiply-adds (counted as 2) a token takes forward through the
    weights it really uses, by part, the held experts' left out (their
    rows are counted from the router's real choices)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * dh
    n = layer_counts(cfg)
    # W_q and W_o of a kind's heads, W_k and W_v, and the gate a head
    projections = sum(
        (2 * d * q_heads(cfg, kind) * dh + 2 * d * hkv) * n[kind]
        for kind in (FULL, SLIDING) if n[kind])
    gate = sum(d * q_heads(cfg, kind) * n[kind]
               for kind in (FULL, SLIDING) if n[kind])
    return {
        "projections": 2 * projections,
        "gate": 2 * gate,
        "dense": 2 * 3 * d * cfg["intermediate_size"] * n["dense"],
        "shared": 2 * 3 * d * cfg["shared_expert_intermediate_size"]
        * n["expert"],
        "router": 2 * d * cfg["num_experts_routed"] * n["expert"],
        "head": 2 * d * cfg["vocab_size"],
    }


def step_least(cfg: dict, batch: int, seq_len: int,
               rows_a_layer: float | None = None) -> dict:
    """One whole step: the products of the weights actually used, the
    held experts' at `rows_a_layer` (at balance if not given), and
    attention, forward and backward once. Bytes are not counted (the step
    is held against the peak FLOP/s alone: `seq_step_mfu`)."""
    tokens = batch * seq_len
    if rows_a_layer is None:
        rows_a_layer = balanced_rows(cfg, batch, seq_len)
    by_part = {k: 3 * v * tokens
               for k, v in weight_flops_a_token(cfg).items()}
    by_part["experts"] = gated_grouped_least(cfg, rows_a_layer)["flops"]
    by_part["attention"] = gated_attention_least(
        cfg, batch, seq_len)["flops"]
    return {"flops": sum(by_part.values()), "by_part": by_part}
