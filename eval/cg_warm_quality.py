"""Quality evidence for the warm-sweep CG schedule (ALSParams.cg_warm_iters).

The schedule cuts the sweep's dominant at-peak traffic term (CG matvecs)
by running full-strength CG only while cold. This
script commits the quality side of that trade as an artifact:

  explicit:  heldout RMSE on structured synthetic ratings (mean + user/
             item biases + low-rank taste + noise) for cg_warm in
             {-1 (off), 8 (default), 4}, vs the global-mean baseline;
  implicit:  the full implicit-ALS objective (all-pairs term via the
             Gram identity) for the same grid.

Usage: python eval/cg_warm_quality.py [--out PATH]
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.ops.als import ALSParams, als_train, rmse  # noqa: E402

NU, NI, NNZ, R = 50_000, 8_000, 4_000_000, 16
ALPHA, REG = 10.0, 0.05


def main() -> None:
    rng = np.random.default_rng(7)
    bu = rng.normal(0, 0.4, NU)
    bi = rng.normal(0, 0.4, NI)
    U = rng.normal(0, 1 / np.sqrt(R), (NU, R))
    V = rng.normal(0, 1 / np.sqrt(R), (NI, R))
    uu = (rng.zipf(1.3, NNZ) % NU).astype(np.int32)
    ii = (rng.zipf(1.3, NNZ) % NI).astype(np.int32)
    r = (3.5 + bu[uu] + bi[ii] + np.einsum("nk,nk->n", U[uu], V[ii])
         + rng.normal(0, 0.3, NNZ))
    r = np.clip(r, 1, 5).astype(np.float32)
    split = int(NNZ * 0.9)
    tr, te = slice(0, split), slice(split, NNZ)

    dev = jax.devices()[0]
    out: dict = {"device_kind": dev.device_kind, "platform": dev.platform,
                 "shape": {"n_users": NU, "n_items": NI, "nnz": NNZ},
                 "explicit": [], "implicit": []}

    for warm in (-1, 8, 6, 4):
        p = ALSParams(rank=64, iterations=10, reg=REG, implicit=False,
                      chunk=65536, chunk_slots=8192, cg_warm_iters=warm)
        m = als_train(uu[tr], ii[tr], r[tr], NU, NI, p)
        row = {"cg_warm_iters": warm,
               "train_rmse": round(rmse(m, uu[tr], ii[tr], r[tr]), 5),
               "heldout_rmse": round(rmse(m, uu[te], ii[te], r[te]), 5)}
        out["explicit"].append(row)
        print(json.dumps(row), flush=True)
    mean = float(np.mean(r[tr]))
    out["mean_baseline_heldout"] = round(
        float(np.sqrt(np.mean((r[te] - mean) ** 2))), 5)
    print(json.dumps({"mean_baseline_heldout": out["mean_baseline_heldout"]}),
          flush=True)

    cnt = rng.integers(1, 20, NNZ).astype(np.float32)

    def objective(m):
        X, Y = m.user_factors, m.item_factors
        s_all = jnp.trace((X.T @ X) @ (Y.T @ Y))
        pred = jnp.einsum("nk,nk->n", X[uu], Y[ii])
        c = 1 + ALPHA * cnt
        return float(s_all + jnp.sum(c * (1 - pred) ** 2)
                     - jnp.sum(pred ** 2)
                     + REG * (jnp.sum(X ** 2) + jnp.sum(Y ** 2)))

    base = None
    for warm in (-1, 8, 6, 4):
        p = ALSParams(rank=64, iterations=10, reg=REG, alpha=ALPHA,
                      implicit=True, chunk=65536, chunk_slots=8192,
                      cg_warm_iters=warm)
        m = als_train(uu, ii, cnt, NU, NI, p)
        obj = objective(m)
        base = obj if warm == -1 else base
        row = {"cg_warm_iters": warm, "objective": round(obj, 1),
               "rel_vs_full_cg": round((obj - base) / abs(base), 5)}
        out["implicit"].append(row)
        print(json.dumps(row), flush=True)

    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
