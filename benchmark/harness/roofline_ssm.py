"""The least work of a step of the stack of single-mixer blocks (the cell
`nemotron-3-nano-ep16.train-8k-ssm`), from shapes and the window's real
group sizes alone.

As benchmark/harness/roofline_sequence.py counts: operations are
multiply-adds counted as 2, of the mathematics once, a forward and a
backward pass (a product of the weights forward, and its two backward
products: 3 x 2 x rows x k x n). What the program computes again (blocks
recomputed in the backward pass, scores recomputed by the attention
kernel's backward, the masked half of the diagonal blocks, tiles padded
to whole, Adam) is not counted, so a share can only be lowered by it.

The scan is counted by what bounds *any* implementation of the
recurrence, not by one chunk size's algebra: the state update and the
read-out, 4 x N x P operations a token and head forward (h <- decay h +
dt x B^T; y = h C) and twice that backward, and x, dt, B, C, y and their
gradients moved once each way. The chunked form does more operations
than that (the (Q, Q) products inside a chunk) so that they are matrix
products; the share says how near the op comes to the bound all the same.

The configuration's keys are read as its file has them:
`n_routed_experts` is the experts held here, `hybrid_override_pattern`'s
first `num_hidden_layers` letters the blocks.
"""

from __future__ import annotations

from benchmark.harness.roofline_sequence import attention_least


def block_counts(cfg: dict) -> dict:
    kinds = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {"M": kinds.count("M"), "E": kinds.count("E"),
            "*": kinds.count("*")}


def scan_least(cfg: dict, batch: int, seq_len: int,
               bytes_per: int = 2) -> dict:
    """One step's scans over the M blocks. Forward reads x, dt, B, C and
    writes y; backward reads them and dy and writes dx, d dt, dB, dC.
    dt and its gradient are float32."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    tokens = batch * seq_len * block_counts(cfg)["M"]
    wide, groups, steps = h * p * bytes_per, 2 * g * n * bytes_per, h * 4
    forward = 2 * wide + groups + steps
    backward = 3 * wide + 2 * groups + 2 * steps
    return {"flops": (4 + 8) * n * p * h * tokens,
            "bytes": (forward + backward) * tokens}


def ssm_attention_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """Every attention block is full and causal."""
    return attention_least(
        batch, seq_len, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        [None] * block_counts(cfg)["*"])


def relu2_grouped_least(cfg: dict, rows_a_block: float,
                        bytes_per: int = 2) -> dict:
    """The held experts' grouped products over `rows_a_block` (token,
    held expert) rows in each E block of a step: up and down forward (2
    products of hidden x width a row), and for each its two backward
    products. Bytes: every held expert's two matrices read forward and
    backward and their float32 gradients written; a row's input, its
    product with W_up, the hidden one and the output moved once each
    way."""
    d, f, blocks = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                    block_counts(cfg)["E"])
    flops = 2 * 3 * 2 * d * f * rows_a_block * blocks
    weights = cfg["n_routed_experts"] * 2 * d * f
    row_bytes = (2 * d + 2 * f) * bytes_per * rows_a_block
    return {"flops": flops,
            "bytes": blocks * (2 * weights * bytes_per + weights * 4
                               + 2 * row_bytes)}


def balanced_rows(cfg: dict, batch: int, seq_len: int) -> float:
    """(token, held expert) rows a block at balance: every routed expert
    the same share of the tokens' choices."""
    return (batch * seq_len * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["num_experts_routed"])


def weight_flops_a_token(cfg: dict) -> dict:
    """Multiply-adds (counted as 2) a token takes forward through the
    weights it really uses, by part, the held experts' left out."""
    d = cfg["hidden_size"]
    h, di = cfg["mamba_num_heads"], (cfg["mamba_num_heads"]
                                     * cfg["mamba_head_dim"])
    conv = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n = block_counts(cfg)
    return {
        "ssm": 2 * (d * (di + conv + h) + di * d
                    + cfg["conv_kernel"] * conv) * n["M"],
        "shared": 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
        * cfg.get("n_shared_experts", 0) * n["E"],
        "router": 2 * d * cfg["num_experts_routed"] * n["E"],
        "projections": 2 * (2 * d * hq + 2 * d * hkv) * n["*"],
        "head": 2 * d * cfg["vocab_size"],
    }


def step_least(cfg: dict, batch: int, seq_len: int) -> dict:
    """One whole step at balance: the products of the weights actually
    used, the held experts' at `balanced_rows`, attention and the scans'
    recurrence, forward and backward once. Bytes are not counted (the
    step is held against the peak FLOP/s alone: `seq_step_mfu`)."""
    tokens = batch * seq_len
    by_part = {k: 3 * v * tokens
               for k, v in weight_flops_a_token(cfg).items()}
    by_part["experts"] = relu2_grouped_least(
        cfg, balanced_rows(cfg, batch, seq_len))["flops"]
    by_part["attention"] = ssm_attention_least(cfg, batch, seq_len)["flops"]
    by_part["scan"] = scan_least(cfg, batch, seq_len)["flops"]
    return {"flops": sum(by_part.values()), "by_part": by_part}
