"""Training-throughput artifact for the neural model families on the
current backend: two-tower retrieval (examples/s) and the sequential
transformer recommender (tokens/s), plus the Pallas flash-attention
kernel in isolation vs the naive reference attention.

The headline bench (bench.py) covers ALS; this artifact extends the
hardware evidence to the net-new families SURVEY §5 added (long-context
/ sequence parallelism) so their TPU-native claims are numbers, not
prose. Methodology matches bench.py: a scalar readback ends each timed
region, and steady-state spans are measured by difference to cancel
dispatch round trip and compile.

Usage: python eval/neural_throughput.py [--out PATH]
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.data.bimap import EntityIdIndex  # noqa: E402


def _index(n, prefix):
    return EntityIdIndex([f"{prefix}{j}" for j in range(n)])


def two_tower_throughput() -> dict:
    from pio_tpu.data.eventstore import Interactions
    from pio_tpu.models.twotower import TwoTowerParams, train_two_tower

    rng = np.random.default_rng(0)
    n_users, n_items, nnz = 100_000, 20_000, 2_000_000
    inter = Interactions(
        user_idx=(rng.zipf(1.3, nnz) % n_users).astype(np.int32),
        item_idx=(rng.zipf(1.3, nnz) % n_items).astype(np.int32),
        values=np.ones(nnz, np.float32),
        users=_index(n_users, "u"), items=_index(n_items, "i"),
    )
    p_hi = TwoTowerParams(embed_dim=128, hidden_dim=256, out_dim=64,
                          batch_size=4096, steps=220, seed=0)
    p_lo = dataclasses.replace(p_hi, steps=20)

    def run(p):
        t0 = time.monotonic()
        params, emb, _ = train_two_tower(inter, p)
        float(jnp.sum(emb))
        return time.monotonic() - t0

    run(p_lo)  # compile
    t_hi = min(run(p_hi) for _ in range(2))
    t_lo = min(run(p_lo) for _ in range(2))
    steps = p_hi.steps - p_lo.steps
    sec = max(t_hi - t_lo, 1e-9)
    return {
        "batch_size": p_hi.batch_size, "embed_dim": p_hi.embed_dim,
        "steady_steps_per_sec": round(steps / sec, 1),
        "examples_per_sec": round(steps * p_hi.batch_size / sec, 1),
    }


def sequence_throughput() -> dict:
    from pio_tpu.models.sequence import (
        SequenceData,
        SequenceParams,
        train_sequence_model,
    )

    rng = np.random.default_rng(0)
    n_seqs, max_len, n_items = 8_192, 128, 20_000
    seqs = (rng.zipf(1.3, (n_seqs, max_len)) % (n_items - 1) + 1).astype(
        np.int32)
    data = SequenceData(seqs=seqs, users=_index(n_seqs, "u"),
                        items=_index(n_items, "i"))
    p_hi = SequenceParams(max_len=max_len, embed_dim=128, num_heads=4,
                          num_layers=2, ffn_dim=256, batch_size=256,
                          steps=120, seed=0)
    p_lo = dataclasses.replace(p_hi, steps=20)

    def run(p):
        t0 = time.monotonic()
        params, encoder, loss = train_sequence_model(data, p)
        float(loss)
        return time.monotonic() - t0

    run(p_lo)
    t_hi = min(run(p_hi) for _ in range(2))
    t_lo = min(run(p_lo) for _ in range(2))
    steps = p_hi.steps - p_lo.steps
    sec = max(t_hi - t_lo, 1e-9)
    tokens = steps * p_hi.batch_size * (max_len - 1)
    return {
        "batch_size": p_hi.batch_size, "seq_len": max_len,
        "layers": p_hi.num_layers, "embed_dim": p_hi.embed_dim,
        "steady_steps_per_sec": round(steps / sec, 2),
        "tokens_per_sec": round(tokens / sec, 1),
    }


def long_context_training() -> dict:
    """End-to-end long-context TRAINING on one chip: the sequence
    trainer at max_len 2048 resolves attention='auto' to the chunked
    (differentiable online-softmax) path — naive attention's stored
    logits would be B*H*S^2*4 B * layers in the backward here."""
    from pio_tpu.models.sequence import (
        SequenceData,
        SequenceParams,
        train_sequence_model,
    )

    rng = np.random.default_rng(0)
    n_seqs, max_len, n_items = 512, 2048, 20_000
    seqs = (rng.zipf(1.3, (n_seqs, max_len)) % (n_items - 1) + 1).astype(
        np.int32)
    data = SequenceData(seqs=seqs, users=_index(n_seqs, "u"),
                        items=_index(n_items, "i"))
    p_hi = SequenceParams(max_len=max_len, embed_dim=128, num_heads=4,
                          num_layers=2, ffn_dim=256, batch_size=16,
                          steps=40, seed=0)
    p_lo = dataclasses.replace(p_hi, steps=8)

    def run(p):
        t0 = time.monotonic()
        params, encoder, loss = train_sequence_model(data, p)
        float(loss)
        return time.monotonic() - t0

    run(p_lo)
    t_hi = min(run(p_hi) for _ in range(2))
    t_lo = min(run(p_lo) for _ in range(2))
    steps = p_hi.steps - p_lo.steps
    sec = max(t_hi - t_lo, 1e-9)
    tokens = steps * p_hi.batch_size * (max_len - 1)
    return {
        "batch_size": p_hi.batch_size, "seq_len": max_len,
        "attention": "chunked (auto)",
        "steady_steps_per_sec": round(steps / sec, 2),
        "tokens_per_sec": round(tokens / sec, 1),
    }


def flash_attention_throughput() -> dict:
    """Isolated kernel: Pallas flash attention vs the naive reference at
    long context — the memory win that makes long sequences fit."""
    from functools import partial

    from pio_tpu.ops.attention import attention_reference, flash_attention

    out = {}
    key = jax.random.PRNGKey(0)
    for seq in (2048, 8192, 32768):
        b, h, d = 4, 8, 64
        q, k, v = (jax.random.normal(kk, (b, seq, h, d), jnp.bfloat16)
                   for kk in jax.random.split(key, 3))

        def timed(fn, reps=8):
            @partial(jax.jit, static_argnums=())
            def chained(q, k, v):
                def body(_, acc):
                    o = fn(acc, k, v)
                    return acc * (1 - 1e-30) + o.astype(acc.dtype) * 1e-30
                return jnp.sum(jax.lax.fori_loop(0, reps, body, q)
                               .astype(jnp.float32))

            @partial(jax.jit, static_argnums=())
            def single(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32))

            float(chained(q, k, v)); float(single(q, k, v))
            br = bs = float("inf")
            for _ in range(3):
                t0 = time.monotonic(); float(chained(q, k, v))
                br = min(br, time.monotonic() - t0)
                t0 = time.monotonic(); float(single(q, k, v))
                bs = min(bs, time.monotonic() - t0)
            return max(br - bs, 1e-9) / (reps - 1)

        flash = partial(flash_attention, causal=True)
        ref = partial(attention_reference, causal=True)
        t_flash = timed(flash)
        row = {"flash_sec": round(t_flash, 5),
               "flash_tokens_per_sec": round(b * seq / t_flash, 1)}
        try:
            t_ref = timed(ref)
            row["reference_sec"] = round(t_ref, 5)
            row["speedup_vs_reference"] = round(t_ref / t_flash, 2)
        except Exception as e:  # noqa: BLE001 - ref OOMs at long context
            row["reference_sec"] = f"failed: {str(e)[:80]}"
        out[f"seq{seq}"] = row
    return out


def main() -> None:
    dev = jax.devices()[0]
    from pio_tpu.utils.tpu_health import telemetry

    out = {"transport": telemetry(),
           "device_kind": dev.device_kind, "platform": dev.platform,
           "note": ("single-invocation numbers: trainer rows swing "
                    "with host load between invocations; compare rows "
                    "WITHIN one artifact, and treat the isolated "
                    "flash-kernel rows (chained on-device, "
                    "dispatch-cancelled) as the stable numbers")}
    out["two_tower"] = two_tower_throughput()
    print(json.dumps({"two_tower": out["two_tower"]}), flush=True)
    out["sequence"] = sequence_throughput()
    print(json.dumps({"sequence": out["sequence"]}), flush=True)
    out["long_context_training"] = long_context_training()
    print(json.dumps({"long_context_training": out["long_context_training"]}),
          flush=True)
    out["flash_attention"] = flash_attention_throughput()
    print(json.dumps({"flash_attention": out["flash_attention"]}), flush=True)
    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
