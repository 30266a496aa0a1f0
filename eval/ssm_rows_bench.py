"""`ops/ssm_rows.py`'s two passes alone on the chip, at the state-space
cell's shapes: the convolution with its SiLU and the gated group norm over
`in_proj`'s output (1, 8192, 10304), forward, and forward + backward,
beside the plain functions of `models/seq_blocks.py` on slices as the
block called them before the passes (PR 50).

    chiprun -- python eval/ssm_rows_bench.py [--other DIR] [--out FILE]
    chiprun -- python eval/ssm_rows_bench.py --tiles 512,512,16,128 --tiles ...

`--other DIR` (it may repeat) times another checkout's
`pio_tpu/ops/ssm_rows.py` beside this one (a parent's, from `git archive`,
in a git-ignored directory), in the same process on the same inputs; a
checkout without the file (PR 50's own parent) has the plain side alone,
which is timed once, from this checkout. `--tiles ROWS,LANES,SUB,CHAIN`
(it may repeat) times this checkout's passes with those in place of the
module's `_ROWS`, `_LANES`, `_SUB`, `_CHAIN`. Prints one JSON line a (checkout, op, pass):
milliseconds a call, the best and the median of `--repeats` calls after
one that compiles, and how far the results and gradients lie from the
plain side's. Nothing here is a cell's number: an op alone times
otherwise than inside the step (the cell's `seq_ssm_conv_device_s` and
`seq_ssm_proj_device_s` are the ones that count)."""

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

# positions; x, B and C; heads; groups; taps: the published widths
S, DI, GN, H, G, TAPS = 8192, 4096, 1024, 64, 8, 4
WIDTHS = (DI, GN, GN)
EPS, BF16 = 1e-5, jnp.bfloat16


def load_rows(root: str, name: str):
    path = os.path.join(root, "pio_tpu", "ops", "ssm_rows.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def inputs(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    conv = (jax.random.normal(ks[0], (1, S, 2 * DI + 2 * GN + H)),
            jax.random.uniform(ks[1], (TAPS, sum(WIDTHS)), minval=-.5,
                               maxval=.5),
            jax.random.uniform(ks[2], (sum(WIDTHS),), minval=-.5, maxval=.5))
    conv_cot = tuple(jax.random.normal(k, (1, S, w)).astype(BF16)
                     for k, w in zip(ks[3:6], WIDTHS))
    norm = (jax.random.normal(ks[6], (1, S, DI)), conv[0],
            1.0 + 0.1 * jax.random.normal(ks[7], (DI,)))
    return {"conv": (conv, conv_cot),
            "norm": (norm, jax.random.normal(ks[8], (1, S, DI)).astype(BF16))}


def plain_ops():
    """The two passes as `_mamba_block` made them of the plain functions:
    slices of u, float32 inside, the results cast."""
    from pio_tpu.models import seq_blocks

    def conv(u, w, b):
        out = jax.nn.silu(seq_blocks.causal_conv(u[..., DI:-H], w, b))
        return tuple(v.astype(BF16)
                     for v in jnp.split(out, (DI, DI + GN), axis=-1))

    def norm(y, u, gain):
        return seq_blocks.gated_group_norm(
            y, u[..., :DI], gain, G, EPS).astype(BF16)

    return {"conv": conv, "norm": norm}


def passes_of(module):
    return {"conv": lambda u, w, b: module.conv_silu(u, w, b, DI, WIDTHS,
                                                     BF16),
            "norm": lambda y, u, gain: module.gated_norm(y, u, gain, G, EPS,
                                                         BF16)}


def with_gradients(fn):
    def run(cot, *a):
        out, vjp = jax.vjp(fn, *a)
        return (out,) + vjp(cot)
    return jax.jit(run)


def rel(a, b) -> float:
    a, b = (np.concatenate([np.float64(v).ravel()
                            for v in jax.tree_util.tree_leaves(
                                jax.device_get(t))]) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


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
    ap.add_argument("--tiles", action="append", default=[],
                    metavar="ROWS,LANES,SUB,CHAIN")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()

    from pio_tpu.ops import ssm_rows as here

    sides = [("plain", plain_ops(), None), ("this", passes_of(here), None)]
    for tiles in args.tiles:
        sides.append((f"this {tiles}", passes_of(here),
                      [int(v) for v in tiles.split(",")]))
    for n, root in enumerate(args.other):
        module = load_rows(root, f"_other_rows_{n}")
        if module is not None:
            sides.append((root, passes_of(module), None))
    data, rows, plain = inputs(args.seed), [], {}
    default = (here._ROWS, here._LANES, here._SUB, here._CHAIN)
    for side, ops, tiles in sides:
        here._ROWS, here._LANES, here._SUB, here._CHAIN = tiles or default
        jax.clear_caches()
        for op, (ins, cot) in data.items():
            for name, fn, fn_args in (
                    ("forward", jax.jit(ops[op]), ins),
                    ("forward+backward", with_gradients(ops[op]),
                     (cot, *ins))):
                ms, out = timed(fn, fn_args, args.repeats)
                row = {"checkout": side, "op": op, "pass": name,
                       "best_ms": min(ms), "median_ms": median(ms),
                       "device": jax.devices()[0].device_kind}
                if side == "plain":
                    plain[op, name] = out
                else:
                    row["rel_to_plain"] = rel(out, plain[op, name])
                rows.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
