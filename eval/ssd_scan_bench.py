"""`ops/ssd.py ssd_scan` alone on the chip, at the state-space cell's
shapes: forward, and forward + backward, on seeded inputs.

    chiprun -- python eval/ssd_scan_bench.py [--batch 2] [--out FILE]
    chiprun -- python eval/ssd_scan_bench.py --against-recurrence 1024

Prints one JSON line a pass: milliseconds a call, the best and the median
of `--repeats` calls after one that compiles; `--out` writes them all.
`--against-recurrence S` times nothing: it holds the op's output and six
gradients to the recurrence a position at a time (`ssd_reference`,
float32) on S positions. PR 45 timed XLA's lowering of the same chunked
algebra beside the kernels with this script (PERF.md section 6). Nothing
here is a cell's number: an op alone times otherwise than inside the
step (the cell's `seq_ssm_scan_device_s` is the one that counts)."""

import argparse
import json
import os
import sys
import time
from statistics import median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pio_tpu.ops import ssd

# positions, heads, head width, groups, state: the published widths
S, H, P, G, N, CHUNK = 8192, 64, 64, 8, 128, 128
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def inputs(batch: int, seed: int, s: int = S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (batch, s, H)) - 4.0)   # ~0.02, as drawn
    return (jax.random.normal(ks[0], (batch, s, H, P)).astype(jnp.bfloat16),
            dt, -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (batch, s, G, N)).astype(jnp.bfloat16),
            jax.random.normal(ks[4], (batch, s, G, N)).astype(jnp.bfloat16),
            jnp.ones((H,)),
            jax.random.normal(ks[5], (batch, s, H, P)))


def with_gradients(fn):
    def run(cot, *a):
        y, vjp = jax.vjp(fn, *a)
        return (y,) + vjp(cot)
    return jax.jit(run)


def against_recurrence(batch: int, positions: int, seed: int) -> None:
    *ins, cot = inputs(batch, seed, positions)
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(with_gradients(ssd.ssd_reference)(cot, *ins))
    got = jax.device_get(with_gradients(
        lambda *a: ssd.ssd_scan(*a, CHUNK))(cot, *ins))
    print(json.dumps({
        "batch": batch, "positions": positions,
        "against_recurrence_rel": {
            n: float(np.linalg.norm(np.float64(a) - np.float64(b))
                     / np.linalg.norm(np.float64(b)))
            for n, a, b in zip(NAMES, got, want)}}), flush=True)


def timed(fn, args, repeats: int) -> list[float]:
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--against-recurrence", type=int, metavar="S")
    args = ap.parse_args()
    if args.against_recurrence:
        against_recurrence(args.batch, args.against_recurrence, args.seed)
        return 0
    *ins, cot = inputs(args.batch, args.seed)
    rows = []
    for name, fn, fn_args in (
            ("forward", jax.jit(lambda *a: ssd.ssd_scan(*a, CHUNK)), ins),
            ("forward+backward",
             with_gradients(lambda *a: ssd.ssd_scan(*a, CHUNK)),
             [cot, *ins])):
        ms = timed(fn, fn_args, args.repeats)
        rows.append({"pass": name, "batch": args.batch, "best_ms": min(ms),
                     "median_ms": median(ms),
                     "device": jax.devices()[0].device_kind})
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
