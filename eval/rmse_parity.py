"""Model-quality evidence: ALS vs trivial baselines + CG/Cholesky parity.

Supports the project north star ("≥10x vs Spark-CPU **at equal RMSE**")
with two claims every measured speed rests on:

 1. ABSOLUTE quality: the shipped ALS clearly beats the global-mean
    predictor (and the stronger per-user/per-item bias baseline) on
    heldout data, with the regularizer picked by a real validation
    sweep — not asserted at a default.
 2. RELATIVE parity: the fast auto solver (short warm-started CG,
    ops/als.py) matches the exact per-entity Cholesky solve that MLlib's
    ALS performs (reference examples/scala-parallel-recommendation/
    custom-query/src/main/scala/ALSAlgorithm.scala:56-67) — within 1%
    heldout RMSE, usually better.

Synthetic ratings with REALISTIC learnable structure (round-2 verdict:
the old planted-rank generator was noise-dominated, so nothing could
beat the mean — that artifact demonstrated parity but not quality):

    r_ui = clip(round(mu + b_u + b_i + <p_u, q_i> + eps), 1, 5)

mean 3.4, user/item bias std 0.45, low-rank (rank 24) dot std ~0.75,
noise std 0.35 — bias structure is a rank-2 component, so the whole
signal is learnable by rank>=26 factors. Popularity is zipf on both
sides (the gather/scatter pattern the kernel actually faces).

Writes eval/RMSE_PARITY.json and eval/RMSE_PARITY.md.

Usage: python eval/rmse_parity.py [--scale full|medium|small] [--cpu]
  full   = ML-20M shape (138493 x 26744, 20M ratings)  -- TPU
  medium = 1/10 shape (2M ratings)                     -- TPU or patient CPU
  small  = 200k ratings                                -- CPU smoke
--cpu forces the CPU backend (JAX_PLATFORMS=cpu does the same).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCALES = {
    "full": (138_493, 26_744, 20_000_000),
    "medium": (13_850, 2_675, 2_000_000),
    "small": (4_000, 1_200, 200_000),
}
RANK = 64
SIGNAL_RANK = 24
SWEEPS = 10
TUNE_SWEEPS = 6
REGS = (0.02, 0.05, 0.1, 0.2, 0.4)
HOLDOUT = 0.05


def synth_ratings(n_users: int, n_items: int, nnz: int, seed=0):
    """mu + user bias + item bias + low-rank + noise -> 1..5 stars."""
    rng = np.random.default_rng(seed)
    mu = 3.4
    b_u = rng.normal(scale=0.45, size=n_users).astype(np.float32)
    b_i = rng.normal(scale=0.45, size=n_items).astype(np.float32)
    P = rng.normal(size=(n_users, SIGNAL_RANK)).astype(np.float32)
    Q = rng.normal(size=(n_items, SIGNAL_RANK)).astype(np.float32)
    scale = 0.75 / np.sqrt(SIGNAL_RANK)  # dot std ~0.75
    users = (rng.zipf(1.2, nnz) % n_users).astype(np.int64)
    items = (rng.zipf(1.2, nnz) % n_items).astype(np.int64)
    score = (
        mu + b_u[users] + b_i[items]
        + np.einsum("nk,nk->n", P[users] * scale, Q[items])
        + rng.normal(scale=0.35, size=nnz).astype(np.float32)
    )
    vals = np.clip(np.rint(score), 1.0, 5.0).astype(np.float32)
    return users, items, vals


def bias_baseline_rmse(tr_u, tr_i, tr_v, te_u, te_i, te_v,
                       n_users, n_items, reg=10.0) -> float:
    """Damped per-user/per-item bias model (one alternating pass) — the
    strong trivial baseline: mu + b_i + b_u."""
    mu = tr_v.mean()
    resid = tr_v - mu
    item_sum = np.bincount(tr_i, weights=resid, minlength=n_items)
    item_cnt = np.bincount(tr_i, minlength=n_items)
    b_i = item_sum / (item_cnt + reg)
    resid2 = resid - b_i[tr_i]
    user_sum = np.bincount(tr_u, weights=resid2, minlength=n_users)
    user_cnt = np.bincount(tr_u, minlength=n_users)
    b_u = user_sum / (user_cnt + reg)
    pred = mu + b_i[te_i] + b_u[te_u]
    return float(np.sqrt(np.mean((te_v - pred) ** 2)))


def train_eval(users, items, vals, te_users, te_items, te_vals,
               n_users, n_items, reg, cg_iters, chunk, sweeps,
               trajectory=False):
    """-> heldout RMSE after every sweep if trajectory, else after the
    last one alone."""
    from pio_tpu.ops.als import ALSParams, als_train, rmse

    # cg_warm_iters=-1 in BOTH modes: trajectory mode re-enters
    # als_train with iterations=1, which would otherwise never leave the
    # full-strength phase of the warm-CG schedule (the schedule keys on
    # the per-call sweep index) while one-shot mode would — the parity
    # comparison must run one solver
    calls, per_call = (sweeps, 1) if trajectory else (1, sweeps)
    p = ALSParams(rank=RANK, iterations=per_call, reg=reg, chunk=chunk,
                  cg_iters=cg_iters, cg_warm_iters=-1)
    out = []
    model = None
    for _ in range(calls):
        model = als_train(users, items, vals, n_users, n_items, p,
                          init=model)
        out.append(round(float(
            rmse(model, te_users, te_items, te_vals)), 5))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=SCALES, default="full")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    n_users, n_items, nnz = SCALES[args.scale]
    chunk = 8192

    print(f"scale={args.scale}: {n_users} x {n_items}, {nnz} ratings, "
          f"rank {RANK}", flush=True)
    users, items, vals = synth_ratings(n_users, n_items, nnz)
    rng = np.random.default_rng(1)
    idx = rng.permutation(nnz)
    # train / validation (reg tuning) / heldout test
    cut_te = int(nnz * (1 - HOLDOUT))
    cut_va = int(cut_te * (1 - HOLDOUT))
    tr, va, te = idx[:cut_va], idx[cut_va:cut_te], idx[cut_te:]
    tr_u, tr_i, tr_v = users[tr], items[tr], vals[tr]
    va_u, va_i, va_v = users[va], items[va], vals[va]
    te_u, te_i, te_v = users[te], items[te], vals[te]

    import jax

    from pio_tpu.ops.als import ALSParams

    device = jax.devices()[0]
    _p = ALSParams(rank=RANK, cg_iters=-1)
    cg_user = _p.resolved_cg_iters(n_users)
    cg_item = _p.resolved_cg_iters(n_items)
    solver_label = (
        f"user side {'CG-' + str(cg_user) if cg_user else 'exact Cholesky'}, "
        f"item side {'CG-' + str(cg_item) if cg_item else 'exact Cholesky'}"
    )

    # -- reg sweep on the validation slice (auto solver) --------------------
    print(f"reg sweep ({solver_label}, {TUNE_SWEEPS} sweeps):", flush=True)
    sweep_rows = []
    for reg in REGS:
        (v_rmse,) = train_eval(
            tr_u, tr_i, tr_v, va_u, va_i, va_v, n_users, n_items,
            reg, -1, chunk, TUNE_SWEEPS)
        sweep_rows.append({"reg": reg, "val_rmse": v_rmse})
        print(f"  reg={reg}: val RMSE {v_rmse:.5f}", flush=True)
    best = min(sweep_rows, key=lambda r: r["val_rmse"])
    reg = best["reg"]
    print(f"best reg = {reg}", flush=True)

    # -- trajectories at the tuned reg --------------------------------------
    print("auto-solver trajectory:", flush=True)
    cg_traj = train_eval(
        tr_u, tr_i, tr_v, te_u, te_i, te_v, n_users, n_items,
        reg, -1, chunk, SWEEPS, trajectory=True)
    for s, r in enumerate(cg_traj):
        print(f"  sweep {s + 1:2d}: heldout RMSE {r:.5f}", flush=True)
    print("direct-Cholesky trajectory:", flush=True)
    ch_traj = train_eval(
        tr_u, tr_i, tr_v, te_u, te_i, te_v, n_users, n_items,
        reg, 0, chunk, SWEEPS, trajectory=True)
    for s, r in enumerate(ch_traj):
        print(f"  sweep {s + 1:2d}: heldout RMSE {r:.5f}", flush=True)

    # -- validation-driven best-sweep selection (round-4) -------------------
    # als_train_validated picks the best sweep on the VALIDATION slice
    # inside the compiled scan; the selected model is then scored once on
    # the untouched TEST slice. No peeking: selection and reporting use
    # different data. This is the shipped configuration when
    # validation_fraction > 0 (models/recommendation.py).
    print("best-sweep selection (validation-driven):", flush=True)
    from pio_tpu.ops.als import als_train_validated, rmse as als_rmse

    p_sel = ALSParams(rank=RANK, iterations=SWEEPS, reg=reg, chunk=chunk,
                      cg_iters=-1)
    model_sel, valinfo = als_train_validated(
        tr_u, tr_i, tr_v, n_users, n_items, p_sel, va_u, va_i, va_v)
    sel_test = round(float(als_rmse(model_sel, te_u, te_i, te_v)), 5)
    print(f"  val curve: {valinfo.curve}", flush=True)
    print(f"  best sweep {valinfo.best_sweep}/{SWEEPS} "
          f"(val {valinfo.best_rmse:.5f}); heldout-test RMSE of the "
          f"SELECTED model: {sel_test:.5f}", flush=True)

    mean_base = float(np.sqrt(np.mean((te_v - tr_v.mean()) ** 2)))
    bias_base = bias_baseline_rmse(
        tr_u, tr_i, tr_v, te_u, te_i, te_v, n_users, n_items)
    # headline = the best-sweep-selected model's TEST score (what the
    # framework ships with validation_fraction>0); the last-sweep figure
    # stays alongside as the no-selection reference behavior
    als_final = sel_test
    final_gap = (cg_traj[-1] - ch_traj[-1]) / ch_traj[-1]
    quality = als_final < 0.95 * mean_base and als_final < bias_base
    result = {
        "scale": args.scale,
        "shape": {"n_users": n_users, "n_items": n_items, "nnz": nnz},
        "rank": RANK,
        "signal_rank": SIGNAL_RANK,
        "sweeps": SWEEPS,
        "reg_sweep": sweep_rows,
        "best_reg": reg,
        "cg_iters_auto": {"user": cg_user, "item": cg_item},
        "solver_label": solver_label,
        "holdout_frac": HOLDOUT,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "heldout_rmse_cg": cg_traj,
        "heldout_rmse_cholesky": ch_traj,
        "best_sweep_selection": {
            "val_curve": list(valinfo.curve),
            "best_sweep": valinfo.best_sweep,
            "best_val_rmse": valinfo.best_rmse,
            "final_val_rmse": valinfo.final_rmse,
            "selected_test_rmse": sel_test,
            "last_sweep_test_rmse": cg_traj[-1],
            "note": "selection on the validation slice inside the "
                    "compiled scan (ops/als.py ALSValidation); test slice "
                    "untouched until the single final score",
        },
        "config_ties": {
            "note": ("this artifact's tuned config (rank, solver, "
                     "warm-CG schedule) is the benchmark's: the cell "
                     "ml20m-r64.train-coo (benchmark/configs/"
                     "als-ml20m-r64.json) trains rank 64 with the auto "
                     "solver and the warm schedule at this ML-20M shape; "
                     "eval/RANKING_EVAL.md's rank-16 grid winner is the "
                     "small quickstart dataset's tuning, not this shape's")
            if args.scale == "full" else
            ("scaled-down run (--scale %s): shape and solver mirror the "
             "benchmark's structure but NOT its size — config-tie claims "
             "apply only to the full-scale artifact" % args.scale),
            "bench_rank": 64, "this_rank": RANK,
            "is_bench_shape": args.scale == "full",
        },
        "final_rel_gap": round(final_gap, 6),
        "mean_baseline_rmse": round(mean_base, 5),
        "bias_baseline_rmse": round(bias_base, 5),
        "als_vs_mean_improvement": round(1 - als_final / mean_base, 4),
        "als_vs_bias_improvement": round(1 - als_final / bias_base, 4),
        "parity": final_gap < 0.01,   # one-sided: auto must not be worse
        "beats_baselines": quality,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    # non-full scales get their own files: a CPU fallback run must not
    # clobber committed full-shape evidence
    suffix = "" if args.scale == "full" else f"_{args.scale}"
    with open(os.path.join(here, f"RMSE_PARITY{suffix}.json"), "w") as f:
        json.dump(result, f, indent=2)

    lines = [
        "# ALS model quality: baselines, reg sweep, CG-vs-Cholesky parity",
        "",
        f"Synthetic bias+rank-{SIGNAL_RANK} ratings at scale "
        f"`{args.scale}` = {n_users:,} users x {n_items:,} items, "
        f"{nnz:,} ratings; {int(HOLDOUT * 100)}% heldout; rank {RANK}; "
        f"auto solver: {solver_label}.",
        f"Platform: {device.platform} ({device.device_kind}).",
        "",
        "## Regularizer sweep (validation slice, auto solver)",
        "",
        "| reg | validation RMSE |",
        "|---|---|",
    ]
    for r in sweep_rows:
        mark = " **<- best**" if r["reg"] == reg else ""
        lines.append(f"| {r['reg']} | {r['val_rmse']:.5f}{mark} |")
    lines += [
        "",
        f"## Heldout trajectories at reg={reg}",
        "",
        "| sweep | auto-solver heldout RMSE | all-Cholesky heldout RMSE |",
        "|---|---|---|",
    ]
    for s in range(SWEEPS):
        lines.append(f"| {s + 1} | {cg_traj[s]:.5f} | {ch_traj[s]:.5f} |")
    lines += [
        "",
        "## Verdicts",
        "",
        f"- Global-mean baseline RMSE: **{mean_base:.5f}**",
        f"- Damped user/item-bias baseline RMSE: **{bias_base:.5f}**",
        f"- Best-sweep selection: sweep **{valinfo.best_sweep}/{SWEEPS}** "
        f"by validation RMSE {valinfo.best_rmse:.5f} (validation curve "
        f"tail {valinfo.final_rmse:.5f}); last-sweep test RMSE would be "
        f"{cg_traj[-1]:.5f}",
        f"- ALS heldout RMSE (best-sweep-selected model): "
        f"**{als_final:.5f}** "
        f"({(1 - als_final / mean_base) * 100:.1f}% below mean baseline, "
        f"{(1 - als_final / bias_base) * 100:.1f}% below bias baseline) — "
        f"{'QUALITY OK' if quality else 'QUALITY FAIL'}",
        f"- Auto-vs-Cholesky final signed gap: {final_gap * 100:+.3f}% "
        f"(negative = auto better) — "
        f"{'PARITY' if result['parity'] else 'NO PARITY'} at the 1% bar",
    ]
    with open(os.path.join(here, f"RMSE_PARITY{suffix}.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps({"final_rel_gap": result["final_rel_gap"],
                      "parity": result["parity"],
                      "beats_baselines": quality,
                      "als_rmse": als_final,
                      "mean_baseline": result["mean_baseline_rmse"],
                      "bias_baseline": result["bias_baseline_rmse"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
