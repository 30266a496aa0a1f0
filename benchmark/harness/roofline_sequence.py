"""The least work of the sequence cell's kernels, from shapes alone.

Operations are multiply-adds counted as 2, of the mathematics once:
a forward pass and a backward pass. What the program computes again
(layers recomputed in the backward pass, scores recomputed by the
attention kernels' backward, blocks of the band that are partly masked,
tiles padded to whole) is not counted, so a share of the roofline can
only be lowered by it.
"""

from __future__ import annotations


def kept_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal layer keeps: j <= i, and i - j < window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_least(batch: int, seq_len: int, q_heads: int, kv_heads: int,
                    head_dim: int, windows: list[int | None],
                    bytes_per: int = 2) -> dict:
    """One step's attention over the layers whose windows are listed
    (None: full). Forward: scores and probabilities-times-values, 2
    products; backward: 4 products (dP, dV, dQ, dK). Bytes: q, k, v read
    and o written forward; q, k, v, o, do read and dq, dk, dv written
    backward."""
    flops = bytes_ = 0
    for window in windows:
        pairs = kept_pairs(seq_len, window) * batch * q_heads
        flops += (2 + 4) * 2 * head_dim * pairs
        q = batch * q_heads * seq_len * head_dim * bytes_per
        kv = batch * kv_heads * seq_len * head_dim * bytes_per
        bytes_ += (2 * q + 2 * kv) + (4 * q + 4 * kv)
    return {"flops": flops, "bytes": bytes_}


def grouped_least(rows: float, n_held: int, hidden: int, width: int,
                  layers: int, bytes_per: int = 2) -> dict:
    """One step's grouped products over `rows` (token, held expert) rows
    a layer: gate, up and down forward (3 products of hidden x width a
    row), and for each its two backward products. Bytes: every held
    expert's three matrices read forward and backward and their float32
    gradients written; a row's input, hidden pair and output moved once
    each way."""
    flops = 3 * 3 * 2 * hidden * width * rows * layers
    weights = n_held * 3 * hidden * width
    row_bytes = (2 * hidden + 3 * width) * bytes_per * rows
    bytes_ = layers * (2 * weights * bytes_per + weights * 4 + 2 * row_bytes)
    return {"flops": flops, "bytes": bytes_}


def least_seconds(work: dict, peaks: dict) -> float:
    return max(work["flops"] / peaks["flops_per_s_bf16"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
