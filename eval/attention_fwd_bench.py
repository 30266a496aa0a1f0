"""`ops/attention.py banded_flash_attention` alone on the chip, at the
sequence cells' attention shapes: the forward kernel (`_banded_fwd`: o and
the rows' log-sum-exp), and forward + backward, on seeded inputs.

    chiprun -- python eval/attention_fwd_bench.py [--other DIR] [--out FILE]
    chiprun -- python eval/attention_fwd_bench.py --blocks 512x512,1024x512

`--other DIR` (it may repeat) times another checkout's
`pio_tpu/ops/attention.py` beside this one (the parent's, from `git
archive`, in a git-ignored directory), in the same process on the same
inputs. `--blocks` reads the forward kernel at other block shapes than the
one `forward_blocks` chooses (this checkout only; the backward kernel's
stay). Prints one JSON line a (checkout, shape, blocks, pass): milliseconds
a call, the best and the median of `--repeats` calls after one that
compiles, and how far o and lse lie from the first checkout's; `--out`
writes them all. Nothing here is a cell's number: a kernel alone times
otherwise than inside the step (the cells' `seq_attn_*_device_s` and a
traced run's `flash_attention_fwd` rows are the ones that count)."""

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

BLOCK = 512            # models/seq_blocks.py ATTN_BLOCK
# batch, query heads, key-value heads, positions, head width, window
SHAPES = {
    "mellum2_full": (2, 32, 4, 8192, 128, None),
    "mellum2_window": (2, 32, 4, 8192, 128, 1024),
    "glm_latent": (2, 20, 20, 8192, 256, None),
    "ouro": (2, 16, 16, 8192, 128, None),
    "nemotron": (2, 32, 2, 8192, 128, None),
    # groups of 6 and of 8 query heads, a window of one block (PR 49)
    "laguna_full": (2, 48, 8, 8192, 128, None),
    "laguna_window": (2, 64, 8, 8192, 128, 512),
}


def load_attention(root: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "pio_tpu", "ops", "attention.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def inputs(shape, seed: int):
    b, hq, hkv, s, d, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (b, h, s, d)).astype(jnp.bfloat16)
            for k, h in zip(ks, (hq, hkv, hkv, hq))]


def passes(attention, window):
    """The two jitted programs of one checkout's kernels."""
    def forward(q, k, v):
        o, res = attention._banded_fwd(q, k, v, window, None, BLOCK, BLOCK,
                                       None)
        return o, res[-1]

    def both(q, k, v, ct):
        o, vjp = jax.vjp(lambda q, k, v: attention.banded_flash_attention(
            q, k, v, window, None, BLOCK, BLOCK), q, k, v)
        return (o,) + vjp(ct)

    return {"forward": jax.jit(forward), "forward+backward": jax.jit(both)}


def timed(fn, args, repeats: int):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t0))
    return ms, out


def rel(a, b) -> float:
    a, b = np.float64(jax.device_get(a)), np.float64(jax.device_get(b))
    return float(np.linalg.norm(a.ravel() - b.ravel())
                 / np.linalg.norm(b.ravel()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="",
                    help="forward block shapes, QxK,...: this checkout's "
                    "forward kernel at each, not the one it chooses")
    ap.add_argument("--passes", default="forward,forward+backward")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()

    from pio_tpu.ops import attention as here

    sides = [("this", here, None)]
    for blk in filter(None, args.blocks.split(",")):
        sides.append((f"this@{blk}", here, tuple(map(int, blk.split("x")))))
    for n, root in enumerate(args.other):
        sides.append((root, load_attention(root, f"_other_attention_{n}"),
                      None))
    chosen = here.forward_blocks
    rows = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        q, k, v, ct = inputs(shape, args.seed)
        first = None
        for side, module, blocks in sides:
            if blocks is not None:
                here.forward_blocks = lambda *a, blocks=blocks: blocks
            for which, fn in passes(module, shape[5]).items():
                if which not in args.passes.split(","):
                    continue
                if blocks is not None and which != "forward":
                    continue
                fn_args = (q, k, v) if which == "forward" else (q, k, v, ct)
                try:
                    ms, out = timed(fn, fn_args, args.repeats)
                except Exception as e:  # noqa: BLE001 - a refused shape
                    print(json.dumps({"checkout": side, "shape": name,
                                      "pass": which,
                                      "refused": str(e)[:300]}), flush=True)
                    continue
                row = {"checkout": side, "shape": name, "pass": which,
                       "best_ms": min(ms), "median_ms": median(ms),
                       "device": jax.devices()[0].device_kind}
                if which == "forward":
                    row["lse_shape"] = list(out[1].shape)
                    if first is None:
                        first = out
                    else:
                        row["o_rel"] = rel(out[0], first[0])
                        row["lse_rel"] = rel(out[1], first[1])
                rows.append(row)
                print(json.dumps(row), flush=True)
            if blocks is not None:
                here.forward_blocks = chosen
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
