"""The least work of a step of the looped stack (the cell
`ouro-2.6b-l4.train-8k-loop`), from shapes alone.

As benchmark/harness/roofline_sequence.py counts: operations are
multiply-adds counted as 2, of the mathematics once, a forward and a
backward pass (a product of the weights forward, and its two backward
products: 3 x 2 x rows x k x n). What the program computes again (layers
recomputed in the backward pass, scores recomputed by the attention
kernels' backward, the masked half of the diagonal blocks, Adam) is not
counted, so a share can only be lowered by it. The embedding's gather,
the norms and the exit gate's d products a token and pass are not
counted either.

A step runs T = `total_ut_steps` passes over the L = `num_hidden_layers`
layers held, so T x L layer applications (each the attention projections,
full causal attention and the SwiGLU) and T products with the head.
"""

from __future__ import annotations

from benchmark.harness.roofline_sequence import attention_least


def layer_applications(cfg: dict) -> int:
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def loop_attention_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """Every layer application is full and causal."""
    return attention_least(
        batch, seq_len, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        [None] * layer_applications(cfg))


def weight_flops_a_token(cfg: dict) -> dict:
    """Multiply-adds (counted as 2) a token takes forward through the
    weights, by part, over all T passes."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n = layer_applications(cfg)
    return {
        "projections": 2 * (2 * d * hq + 2 * d * hkv) * n,
        "dense": 2 * 3 * d * cfg["intermediate_size"] * n,
        "head": 2 * d * cfg["vocab_size"] * cfg["total_ut_steps"],
    }


def step_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """One whole step: the products of the weights plus attention,
    forward and backward once. Bytes are not counted (the step is held
    against the peak FLOP/s alone: `seq_step_mfu`)."""
    tokens = batch * seq_len
    by_part = {k: 3 * v * tokens
               for k, v in weight_flops_a_token(cfg).items()}
    by_part["attention"] = loop_attention_least(cfg, batch, seq_len)["flops"]
    return {"flops": sum(by_part.values()), "by_part": by_part}
