"""`ops/ssd.py ssd_scan` alone on the chip, at the state-space cell's
shapes: forward, and forward + backward, on seeded inputs.

    chiprun -- python eval/ssd_scan_bench.py [--other DIR] [--out FILE]
    chiprun -- python eval/ssd_scan_bench.py --against-recurrence 1024

`--other DIR` (it may repeat) times another checkout's
`pio_tpu/ops/ssd.py` beside this one (the parent's, from `git archive`, in
a git-ignored directory), in the same process on the same inputs, at
each of `--batches` (1, as the block stack calls the op, and 2). Prints
one JSON line a (checkout, batch, pass): milliseconds a call, the best and
the median of `--repeats` calls after one that compiles, and how far y and
the six gradients lie from the first checkout's; `--out` writes them all.
`--against-recurrence S` times nothing: it holds every checkout's output
and six gradients to the recurrence a position at a time (`ssd_reference`,
float32) on S positions. PR 45 timed XLA's lowering of the same chunked
algebra beside the kernels with this script, and PR 47 the kernels that
take a head at a time beside the ones that take a group's eight together
(PERF.md section 6 has both). Nothing here is a cell's number: an op
alone times otherwise than inside the step (the cell's
`seq_ssm_scan_device_s` is the one that counts)."""

import argparse
import importlib.util
import json
import os
import sys
import time
from statistics import median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# positions, heads, head width, groups, state: the published widths
S, H, P, G, N, CHUNK = 8192, 64, 64, 8, 128, 128
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def load_ssd(root: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "pio_tpu", "ops", "ssd.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def inputs(batch: int, seed: int, s: int = S, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (batch, s, H)) - 4.0)   # ~0.02, as drawn
    return (jax.random.normal(ks[0], (batch, s, H, P)).astype(dtype),
            dt, -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (batch, s, G, N)).astype(dtype),
            jax.random.normal(ks[4], (batch, s, G, N)).astype(dtype),
            jnp.ones((H,)),
            jax.random.normal(ks[5], (batch, s, H, P)))


def with_gradients(fn):
    def run(cot, *a):
        y, vjp = jax.vjp(fn, *a)
        return (y,) + vjp(cot)
    return jax.jit(run)


def rel(a, b) -> float:
    a, b = np.float64(jax.device_get(a)), np.float64(jax.device_get(b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def against_recurrence(sides, batch: int, positions: int, seed: int,
                       dtype) -> None:
    *ins, cot = inputs(batch, seed, positions, dtype)
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(
            with_gradients(sides[0][1].ssd_reference)(cot, *ins))
    # float32 operands as the cell's scan probe hands them over: under
    # "highest", or the products round them to bfloat16 all the same
    precision = "highest" if dtype == jnp.float32 else "default"
    for side, module in sides:
        with jax.default_matmul_precision(precision):
            got = with_gradients(
                lambda *a, m=module: m.ssd_scan(*a, CHUNK))(cot, *ins)
        print(json.dumps({
            "checkout": side, "batch": batch, "positions": positions,
            "operands": jnp.dtype(dtype).name, "against_recurrence_rel": {
                n: rel(a, b) for n, a, b in zip(NAMES, got, want)}}),
            flush=True)


def timed(fn, args, repeats: int):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t0))
    return ms, out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[])
    ap.add_argument("--batches", default="1,2")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--against-recurrence", type=int, metavar="S")
    ap.add_argument("--operands", default="bfloat16",
                    help="x, B and C of --against-recurrence: bfloat16 as "
                    "the block stack hands them over, or float32 as the "
                    "cell's scan probe does")
    args = ap.parse_args()

    from pio_tpu.ops import ssd as here

    sides = [("this", here)] + [
        (root, load_ssd(root, f"_other_ssd_{n}"))
        for n, root in enumerate(args.other)]
    batches = [int(b) for b in args.batches.split(",")]
    if args.against_recurrence:
        for batch in batches:
            against_recurrence(sides, batch, args.against_recurrence,
                               args.seed, jnp.dtype(args.operands))
        return 0
    rows = []
    for batch in batches:
        *ins, cot = inputs(batch, args.seed)
        first = None
        for side, module in sides:
            def scan(*a, m=module):
                return m.ssd_scan(*a, CHUNK)

            for name, fn, fn_args in (
                    ("forward", jax.jit(scan), ins),
                    ("forward+backward", with_gradients(scan), [cot, *ins])):
                ms, out = timed(fn, fn_args, args.repeats)
                row = {"checkout": side, "pass": name, "batch": batch,
                       "best_ms": min(ms), "median_ms": median(ms),
                       "device": jax.devices()[0].device_kind}
                if name == "forward+backward":
                    if first is None:
                        first = out
                    else:
                        row["rel_to_first"] = {
                            n: rel(a, b)
                            for n, a, b in zip(NAMES, out, first)}
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
