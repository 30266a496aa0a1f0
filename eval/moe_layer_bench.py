"""`held_moe_ffn` alone on the chip, forward + backward, at the
sequence cells' shapes (`laguna`: 16 of 256 experts of width 512, 256
rows a held expert at balance, half a tile of 512; PR 49) and three
seeded routings: the cell's (a held
share near 0.15: what one rank's router sends over a job), balanced
(1.0) and every choice held (the sorted buffer full, the regime in
which moves that follow `tiles_used` can only lose).

    chiprun -- python eval/moe_layer_bench.py [--other DIR] [--out FILE]

`--other DIR` (it may repeat) times another checkout's
`pio_tpu/ops/moe.py` beside this one (the parent's, from `git archive`,
in a git-ignored directory), in the same process on the same inputs. Prints one JSON line a
(shape, routing): milliseconds a forward + backward, held share, tiles
used of the buffer's; `--out` writes them all. `--tiles 512,256,128`
times this checkout's layer at other rows a tile too (`HeldExperts.
tile_rows`, which the block stack sets to `MOE_TILE`): `this_tile<n>_ms`
and that tile's fill. Nothing here is a cell's number: a layer alone
times otherwise than inside the step."""

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = {
    # tokens, hidden, expert width, routed, held, top-k, router
    "mellum2": dict(t=8192, d=2304, f=896, routed=64, held=16, k=8,
                    score="softmax", scale=1.0),
    "glm": dict(t=8192, d=2048, f=1536, routed=64, held=8, k=4,
                score="sigmoid", scale=1.8),
    "laguna": dict(t=8192, d=2048, f=512, routed=256, held=16, k=8,
                   score="sigmoid", scale=2.5),
}
# what is added to the held experts' router logits
ROUTINGS = {"cell": None, "balanced": 0.0, "full": 30.0}
CELL_SHARE = 0.15


def load_moe(root: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "pio_tpu", "ops", "moe.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def inputs(shape: dict, seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    t, d, f, held = shape["t"], shape["d"], shape["f"], shape["held"]
    params = {
        "router": jax.random.normal(ks[0], (d, shape["routed"])) * 0.02,
        "w_gate": jax.random.normal(ks[1], (held, d, f)) * 0.02,
        "w_up": jax.random.normal(ks[2], (held, d, f)) * 0.02,
        "w_down": jax.random.normal(ks[3], (held, f, d)) * 0.02}
    if shape["score"] == "sigmoid":
        params["router_bias"] = jnp.zeros(shape["routed"])
    x = jax.random.normal(ks[4], (t, d))
    return params, x.at[:, 0].set(1.0), jax.random.normal(ks[5], (t, d))


def routed(params, shift, held: int):
    """The held experts' logits moved by `shift` (feature 0 is 1)."""
    router = params["router"].at[0, :held].add(shift)
    return {**params, "router": router}


def held_share(moe, params, x, cfg) -> float:
    logits = jnp.dot(x.astype(jnp.bfloat16),
                     params["router"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    ids, _ = moe.route_top_k(logits, cfg.top_k, cfg.norm_topk, cfg.score,
                             params.get("router_bias"), cfg.scale)
    got = float(jnp.sum(ids < cfg.n_held))
    return got / (ids.size * cfg.n_held / cfg.n_routed)


def cell_shift(moe, params, x, cfg) -> float:
    """The shift that gives the cell's held share, by bisection."""
    lo, hi = -3.0, 0.0
    for _ in range(12):
        mid = (lo + hi) / 2
        if held_share(moe, routed(params, mid, cfg.n_held), x, cfg) \
                < CELL_SHARE:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def timed(fn, args, reps: int) -> list[float]:
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(5):
        start = time.perf_counter()
        for _ in range(reps):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - start) / reps * 1e3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--other", action="append", default=[])
    ap.add_argument("--out")
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shapes", default="mellum2,glm")
    ap.add_argument("--routings", default="cell,balanced,full")
    ap.add_argument("--tiles", default="512",
                    help="rows a tile, this checkout's layer at each")
    args = ap.parse_args(argv)
    tiles_asked = [int(t) for t in args.tiles.split(",")]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sides = {"this": load_moe(here, "moe_this")}
    for root in args.other:
        side = os.path.basename(os.path.normpath(root))
        sides[side] = load_moe(root, "moe_" + side)
    device = jax.devices()[0]
    lines = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        params, x, cot = inputs(shape, args.seed)
        for routing in args.routings.split(","):
            line = {"shape": name, "routing": routing,
                    "device": device.device_kind,
                    "platform": device.platform}
            for side, moe in sides.items():
                for tile in (tiles_asked if side == "this" else [512]):
                    cfg = moe.HeldExperts(
                        shape["routed"], shape["k"], (0, shape["held"]),
                        True, tile, shape["score"], shape["scale"])
                    shift = ROUTINGS[routing]
                    if shift is None:
                        shift = cell_shift(moe, params, x, cfg)
                    p = routed(params, shift, cfg.n_held)

                    def loss(p, x, cot, moe=moe, cfg=cfg):
                        y, aux = moe.held_moe_ffn(p, x, cfg)
                        return jnp.sum(y * cot), aux["counts"]

                    step = jax.jit(jax.value_and_grad(loss, (0, 1),
                                                      has_aux=True))
                    ms = timed(step, (p, x, cot), args.reps)
                    (_, counts), _ = step(p, x, cot)
                    counts = np.asarray(counts)
                    used = int(np.maximum(-(-counts // tile), 1).sum())
                    key = side if tile == 512 else f"{side}_tile{tile}"
                    line.update({key + "_ms": min(ms),
                                 key + "_ms_all": ms,
                                 key + "_tile_fill": float(
                                     counts.sum() / (used * tile))})
                    if tile == 512:
                        line.update({
                            "held_share": held_share(moe, p, x, cfg),
                            "tiles_used": used,
                            "tiles": cfg.row_capacity(shape["t"]) // 512})
            for side in list(sides)[1:]:
                line["this_over_" + side] = (line["this_ms"]
                                             / line[side + "_ms"])
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
