"""What decides `correct` in a train_sequence_mtp cell: the timed path's
own numbers against the plain reference
(benchmark/reference/latent_moe_lm.py), at the published widths and the
timed shapes.

The child (benchmark/drivers/train_sequence_mtp_child.py) hands over what
the program produced; nothing of the program is imported here:

  1. the step-0 main and prediction-module losses the window's last job
     logged (its seeded initial weights on its first batch), and the same
     two from the jobs' own step program run once more on the same
     weights and batch: they must agree (the same program twice), and
     each must agree with the reference's;
  2. that step's gradients (Adam's first moment after one step from
     zero, over 1 - b1) of named slices (`named_slices`) against
     `jax.grad` of the reference, each by ||program - reference|| /
     ||reference||, in four families with a limit each: the five routers;
     the three matrices of the busiest held expert and of the shared
     expert of the second expert layer; the four latent projections
     (W_qa, W_qb, W_kva, W_kvb) of the dense layer and of the last stack
     layer; and W_eh with the embedding's rows 1-256 ("dense": they sum
     over every token);
  3. the router's bias, held to the reference and not to a number the
     program reports of itself: that step's token counts over every
     routed expert, as the step program returned them, against the
     reference's routing of the same weights and batch
     (`router_counts_rel`: sum |program - reference| / sum reference; top-k
     choices that flip under bfloat16 operands move a few tokens, counts
     of the held experts alone or of one history move half or more); the
     bias the step left against the reference's rule (`bias_after`)
     applied here to those counts; and the persisted biases a whole
     number of moves from zero, at most one a step (`router_bias_abs`:
     the largest of the three distances);
  4. the model the last job persisted, as `load_models` returned it: the
     configuration's shapes, float32, finite; its mean loss (main +
     weight * module) over HELD_BATCHES held seeded batches by the
     program (the step program again) and by the reference, equal within
     a limit and below the step-0 loss by a margin;
  5. the router's probe: the program's routing (`route_top_k` as the
     layers call it) and the reference's on seeded logits under each
     router's persisted bias, compared on the dense (tokens, experts)
     weights. At step 0 every bias is zero, and on one rank a job's
     router sends its held experts next to nothing by its end, so a
     router that weighs by score + bias moves the persisted model's loss
     by a part in a million or not at all; the probe sees the weights
     themselves.

Each limit is in the configuration file (`check.limits`) with the
readings it was set between (PERF.md section 2). `faults` makes the
reference a faulty one, and PROGRAM_FAULTS the program's side: the check
must then fail, which benchmark/tests/test_check_latent.py holds it to.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from benchmark.harness.check_sequence import relative_error
from benchmark.reference import latent_moe_lm as ref

EMBED_ROWS = 256
HELD_BATCHES = 2

# the faulty references the limits are set against and tested with
FAULTS = {
    "bfloat16 accumulation": {"accumulate": "bfloat16"},
    "softmax for sigmoid": {"score": "softmax"},
    "scaling 1.0 for 1.8": {"routed_scaling": 1.0},
    "top-3 for top-4": {"top_k": 3},
    "weights from score + bias": {"weights_biased": True},
    "kv_a norm left out": {"kv_norm": False},
    "RoPE on q_nope and k_nope too": {"rope_nope": True},
    "k_pe per head": {"k_pe": "per_head"},
    "scale 1/sqrt(192)": {"scale_dim": 192},
    "shared expert left out": {"shared": False},
    "module predicts t+1": {"mtp_target": 1},
    "module weight 0.1": {"mtp_weight": 0.1},
}


def _held_only(program: dict, cfg: dict) -> dict:
    lo, hi = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    counts = np.zeros_like(program["counts0"])
    counts[..., lo:hi] = program["counts0"][..., lo:hi]
    return dict(program, counts0=counts)


# what a train step could get wrong about its counts and its bias, made
# of the sound program's numbers: name -> function(program, cfg)
PROGRAM_FAULTS = {
    "counts of the held experts only": _held_only,
    "counts of one history": lambda program, cfg: dict(
        program, counts0=program["counts0"][:, :1]),
    "a bias move missed": lambda program, cfg: dict(
        program, bias1=np.zeros_like(program["bias1"])),
    "a bias the optimizer moved": lambda program, cfg: dict(
        program, bias_model=program["bias_model"] + 1e-4),
}
LATENT = ("wq_a", "wq_b", "wkv_a", "wkv_b")
EXPERT = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")


def expected_shapes(cfg: dict) -> dict:
    d, f, h = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_attention_heads"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    held, routed = cfg["n_routed_experts"], cfg["num_experts_routed"]
    i, fs = cfg["intermediate_size"], cfg["n_shared_experts"] * f
    attention = {"norm1": (d,), "wq_a": (d, rq), "q_norm": (rq,),
                 "wq_b": (rq, h * (dn + dr)), "wkv_a": (d, rkv + dr),
                 "kv_norm": (rkv,), "wkv_b": (rkv, h * (dn + dv)),
                 "wo": (h * dv, d), "norm2": (d,)}
    dense = dict(attention, mlp_gate=(d, i), mlp_up=(d, i), mlp_down=(i, d))
    sparse = dict(attention, router=(d, routed), router_bias=(routed,),
                  w_gate=(held, d, f), w_up=(held, d, f), w_down=(held, f, d),
                  shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d))
    n_dense = cfg["first_k_dense_replace"]
    return {"embed": (cfg["vocab_size"], d), "head": (cfg["vocab_size"], d),
            "final_norm": (d,),
            "layers": [dict(dense if n < n_dense else sparse)
                       for n in range(cfg["num_hidden_layers"])],
            "mtp": {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                    "final_norm": (d,), "layer": dict(sparse)}}


def shape_faults(cfg: dict, params) -> list[str]:
    """What is wrong with a loaded model's parameter tree; [] if nothing."""
    import jax

    def is_shape(x):
        return isinstance(x, tuple)

    try:
        pairs = jax.tree_util.tree_map(
            lambda shape, x: (shape, np.asarray(x)), expected_shapes(cfg),
            params, is_leaf=is_shape)
    except (ValueError, KeyError, TypeError) as e:
        return [f"tree differs: {e}"]
    wrong = []
    for path, (shape, x) in jax.tree_util.tree_leaves_with_path(
            pairs, is_leaf=is_shape):
        name = jax.tree_util.keystr(path)
        if x.shape != shape or x.dtype != np.float32:
            wrong.append(f"{name} is {x.dtype}{x.shape}, not float32{shape}")
        elif not np.isfinite(x).all():
            wrong.append(f"{name} is not finite")
    return wrong


def router_layers(cfg: dict, tree) -> list:
    """The layers that hold a router, in the order of the trainer's
    counters: the stack's expert layers, then the module's."""
    return list(tree["layers"][cfg["first_k_dense_replace"]:]) + [
        tree["mtp"]["layer"]]


def named_slices(cfg: dict, expert: int) -> dict:
    """name -> function(gradient tree) -> array (see the header)."""
    n_dense, n_layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    second = n_dense + 1                # the second expert layer
    out = {f"layer{n}.router": (lambda g, n=n: g["layers"][n]["router"])
           for n in range(n_dense, n_layers)}
    out["mtp.router"] = lambda g: g["mtp"]["layer"]["router"]
    for name in EXPERT:
        out[f"layer{second}.{name}[e]"] = (
            lambda g, name=name: g["layers"][second][name][expert])
    for name in SHARED:
        out[f"layer{second}.{name}"] = (
            lambda g, name=name: g["layers"][second][name])
    for n in (0, n_layers - 1):
        for name in LATENT:
            out[f"layer{n}.{name}"] = (
                lambda g, n=n, name=name: g["layers"][n][name])
    out["mtp.eh_proj"] = lambda g: g["mtp"]["eh_proj"]
    out["embed[1:257]"] = lambda g: g["embed"][1:1 + EMBED_ROWS]
    return out


def family(name: str) -> str:
    leaf = name.split(".")[-1]
    if leaf == "router":
        return "router"
    if leaf in LATENT:
        return "latent"
    return "expert" if leaf.split("[")[0] in EXPERT + SHARED else "dense"


def busiest_expert(cfg: dict, grads) -> int:
    """The held expert of the second expert layer whose down projection
    has the largest gradient: an expert the router sends nothing has zero
    gradients on both sides, which compare nothing."""
    w = np.asarray(grads["layers"][cfg["first_k_dense_replace"] + 1][
        "w_down"], np.float32)
    return int(np.argmax(np.linalg.norm(w.reshape(len(w), -1), axis=1)))


def gradient_slices(cfg: dict, grads, expert: int | None = None) -> dict:
    """The named slices of a gradient tree, on the host, and under
    "expert" which expert's they are (the tree's busiest, if not given)."""
    if expert is None:
        expert = busiest_expert(cfg, grads)
    out = {name: np.asarray(pick(grads), np.float32)
           for name, pick in named_slices(cfg, expert).items()}
    out["expert"] = expert
    return out


def router_biases(cfg: dict, params) -> np.ndarray:
    """(routers, routed) float64, in `router_layers`' order."""
    return np.stack([np.asarray(lp["router_bias"], np.float64)
                     for lp in router_layers(cfg, params)])


def bias_fault(cfg: dict, counts0, bias1, bias_model, steps: int) -> float:
    """The largest of: |bias1 - the rule on counts0| (the bias one step
    from zero left, against the reference's `bias_after` of that step's
    (routers, routed) counts); the persisted biases' distance from a
    whole number of moves; their excess over `steps` moves."""
    rate = cfg.get("router_bias_update_rate", ref.BIAS_RATE)
    want = np.stack([ref.bias_after(np.zeros(len(c)), c, rate)
                     for c in counts0])
    if want.shape != np.shape(bias1) or want.shape != np.shape(bias_model):
        return float("inf")
    moves = np.asarray(bias_model, np.float64) / rate
    return float(max(np.abs(bias1 - want).max(),
                     rate * np.abs(moves - np.round(moves)).max(),
                     rate * max(np.abs(moves).max() - steps, 0.0)))


def router_probe(cfg: dict, seed: int, params, tokens: int = 4096) -> dict:
    """Seeded float32 router logits (tokens, routed) and every router's
    persisted bias (routers, routed)."""
    rng = np.random.default_rng([seed, 0xB1A5])
    return {"logits": rng.standard_normal(
                (tokens, cfg["num_experts_routed"]), np.float32) * 1.5,
            "bias": np.stack([np.asarray(lp["router_bias"], np.float32)
                              for lp in router_layers(cfg, params)])}


def reference_router_probe(cfg: dict, probe: dict, faults=None) -> np.ndarray:
    """(routers, tokens, routed) routing weights of the reference: the
    logits pass an identity router, so its scores are the probe's."""
    import jax

    eye = np.eye(cfg["num_experts_routed"], dtype=np.float32)
    route = jax.jit(lambda logits, bias: ref.routing(
        logits, eye, bias, cfg, faults or {})[0])
    return np.stack([np.asarray(route(probe["logits"], bias))
                     for bias in probe["bias"]])


def reference_numbers(cfg: dict, params0, tokens0, model_params,
                      held_tokens, expert: int, probe: dict,
                      faults=None) -> dict:
    """The reference's side: step-0 losses and named gradient slices on
    the initial weights (expert `expert`'s: the one the program's side
    took), the persisted model's loss on each of the held batches
    (held_tokens: (batches, B, S + 2)), and the router probe's weights.
    `params0` and `model_params` are functions that make the trees: at
    the published widths the device holds one of them at a time beside
    what the reference's own program takes."""
    import jax

    # one compiled program serves every batch (the held batches'
    # gradients are not looked at): every large executable a run adds has
    # to share the machine's compile cache with the others
    grad_of = jax.jit(jax.value_and_grad(
        partial(ref.loss, cfg=cfg, faults=faults or {}), has_aux=True))
    params = params0()
    (loss0, (main0, mtp0)), grads = grad_of(params, tokens0)
    slices = gradient_slices(cfg, grads, expert)
    del grads
    counts0 = np.asarray(jax.jit(partial(
        ref.routed_counts, cfg=cfg, faults=faults or {}))(params, tokens0))
    params = model_params()
    held = [float(grad_of(params, batch)[0][0]) for batch in held_tokens]
    return {"loss0": float(loss0), "loss_main0": float(main0),
            "loss_mtp0": float(mtp0), "slices": slices, "held_losses": held,
            "counts0": counts0,
            "router_probe": reference_router_probe(cfg, probe, faults)}


def check(cfg: dict, limits: dict, program: dict, reference: dict) -> dict:
    """program: logged_main, logged_mtp, loss_main0, loss_mtp0, slices,
    counts0 (routers, histories, routed), bias1, bias_model (routers,
    routed), steps, held_losses, router_probe, shape_faults. reference:
    loss0, loss_main0, loss_mtp0, slices, counts0 (routers, routed),
    held_losses, router_probe. -> {"correct", "compared": lines,
    "numbers"}."""
    numbers: dict = {}
    compared: list[str] = []
    ok = True

    def hold(name: str, value: float, text: str) -> None:
        nonlocal ok
        lim = limits[name]
        passed = (value <= lim["max"] if "max" in lim
                  else value >= lim["min"])
        ok = ok and bool(passed)
        numbers[name] = value
        bound = f"<= {lim['max']}" if "max" in lim else f">= {lim['min']}"
        compared.append(f"{text}: {value:.6g} {bound}: "
                        f"{'ok' if passed else 'FAILED'}")

    def rel(a: float, b: float) -> float:
        return abs(a - b) / abs(b)

    faults = program["shape_faults"]
    ok = ok and not faults
    compared.append("persisted model: the configuration's shapes, float32, "
                    "finite: " + ("ok" if not faults
                                  else "FAILED " + "; ".join(faults[:4])))
    hold("loss_logged_rel",
         max(rel(program["logged_main"], program["loss_main0"]),
             rel(program["logged_mtp"], program["loss_mtp0"])),
         f"step-0 losses the job logged ({program['logged_main']:.8g}, "
         f"{program['logged_mtp']:.8g}) against the step program's "
         f"({program['loss_main0']:.8g}, {program['loss_mtp0']:.8g}), "
         "larger relative difference")
    for part, what in (("main", "main"), ("mtp", "prediction module's")):
        hold(f"loss_{part}_rel",
             rel(program[f"loss_{part}0"], reference[f"loss_{part}0"]),
             f"step-0 {what} loss program {program[f'loss_{part}0']:.8g} "
             f"against reference {reference[f'loss_{part}0']:.8g}, relative")
    errors = {name: relative_error(program["slices"][name], want)
              for name, want in reference["slices"].items()
              if name != "expert"}
    numbers["grad_rel_by_slice"] = errors
    numbers["expert"] = program["slices"]["expert"]
    for what in ("router", "expert", "latent", "dense"):
        group = {n: e for n, e in errors.items() if family(n) == what}
        worst = max(group, key=group.get)
        hold(f"grad_{what}_rel", group[worst],
             f"step-0 gradients of {len(group)} {what} slices against the "
             f"reference's, largest relative error (at {worst})")
    hold("router_probe_rel",
         relative_error(program["router_probe"], reference["router_probe"]),
         f"routing weights of {len(reference['router_probe'])} routers "
         "under their persisted biases on seeded logits against the "
         "reference's, relative error")
    counts = np.asarray(program["counts0"], np.int64).sum(axis=1)
    wanted = np.asarray(reference["counts0"], np.int64)
    hold("router_counts_rel",
         float(np.abs(counts - wanted).sum() / wanted.sum())
         if counts.shape == wanted.shape else float("inf"),
         f"step-0 token counts of {len(wanted)} routers over every routed "
         "expert against the reference's routing, sum of differences over "
         "tokens routed")
    hold("router_bias_abs",
         bias_fault(cfg, counts, program["bias1"], program["bias_model"],
                    program["steps"]),
         "the bias one step left against the rule on its counts, and the "
         "persisted biases against whole moves of at most one a step, "
         "largest absolute distance")
    mine, theirs = (float(np.mean(side["held_losses"]))
                    for side in (program, reference))
    numbers["held_rel_by_batch"] = [
        (a - b) / b for a, b in zip(program["held_losses"],
                                    reference["held_losses"])]
    hold("held_loss_rel", rel(mine, theirs),
         f"persisted model on {len(reference['held_losses'])} held "
         f"batches: program {mine:.8g} against reference {theirs:.8g}, "
         "relative")
    hold("held_below_step0", reference["loss0"] - theirs,
         f"held-batch loss {theirs:.6g} below the step-0 loss "
         f"{reference['loss0']:.6g} by")
    return {"correct": ok, "compared": compared, "numbers": numbers}
