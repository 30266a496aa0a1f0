"""What decides `correct` in a train_sequence cell: the timed path's own
numbers against the plain reference (benchmark/reference/sequence_lm.py),
at the published widths and the timed shapes.

The child (benchmark/drivers/train_sequence_child.py) hands over what the
program produced; nothing of the program is imported here:

  1. the step-0 loss the window's last job logged (its seeded initial
     weights on its first batch), and the same loss and its gradients
     from the jobs' own step program run once more on the same weights
     and batch (the gradient is Adam's first moment after one step from
     zero, over 1 - b1): the two losses must agree (the same program
     twice), and the loss must agree with the reference's;
  2. the step-0 gradients of named slices (`named_slices`) against
     `jax.grad` of the reference, each by ||program - reference|| /
     ||reference||;
  3. the model the last job persisted, as `load_models` returned it: the
     configuration's shapes, float32, finite; its mean loss over
     HELD_BATCHES held seeded batches by the program (the step program
     again) and by the reference, equal within a limit and below the
     step-0 loss by a margin (the job learned something). What rounding
     the program's operands does to a batch's loss has either sign and
     averages out over the batches; what rounding every result does (the
     control) has one sign and stays;
  4. the band's edge: the program's window kernel and the reference's
     attention on seeded queries, keys and values at the timed shapes,
     under a cotangent that is zero except on a few query rows more than
     a window apart. The gradients of the keys and values at each such
     row's last key inside the band and first key outside it: the second
     is exactly zero on both sides, and a band one key too wide or too
     narrow changes half of the compared numbers outright, where the
     model's own gradients move by one key in a window.

Each limit is in the configuration file (`check.limits`) with the
readings it was set between. `faults` makes the reference a faulty one
(a lower precision, another top-k, ...): the check must then fail, which
benchmark/tests/test_check_sequence.py holds it to.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from benchmark.reference import sequence_lm as ref

EMBED_ROWS = 256
EXPERT_LAYER = 1
HELD_BATCHES = 2


# the faulty references the limits are set against and tested with
FAULTS = {
    "bfloat16 accumulation": {"accumulate": "bfloat16"},
    "top-7 for top-8": {"top_k": 7},
    "router weights not normalised": {"norm_topk": False},
    "window of 1025": {"window": 1025},
    "key-value head i // 4": {"kv_head": "i//4"},
    "default RoPE on the full layer": {"rope_full": "default"},
}


def expected_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_routed", held)
    layer = {"norm1": (d,), "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
             "wo": (hq, d), "norm2": (d,), "router": (d, routed),
             "w_gate": (held, d, f), "w_up": (held, d, f),
             "w_down": (held, f, d)}
    return {"embed": (cfg["vocab_size"], d), "head": (cfg["vocab_size"], d),
            "final_norm": (d,),
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])]}


def named_slices(cfg: dict, expert: int) -> dict:
    """name -> function(gradient tree) -> array: every layer's router,
    held expert `expert`'s three matrices in the second layer, W_q and W_k
    of the first sliding and of the first full layer, and the first
    EMBED_ROWS item rows of the embedding (the most frequent items under
    the Zipf law)."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    out = {f"layer{n}.router": (lambda g, n=n: g["layers"][n]["router"])
           for n in range(len(kinds))}
    for name in ("w_gate", "w_up", "w_down"):
        out[f"layer{EXPERT_LAYER}.{name}[e]"] = (
            lambda g, name=name: g["layers"][EXPERT_LAYER][name][expert])
    for kind in ("sliding_attention", "full_attention"):
        if kind in kinds:
            n = kinds.index(kind)
            for name in ("wq", "wk"):
                out[f"layer{n}.{name}.{kind.split('_')[0]}"] = (
                    lambda g, n=n, name=name: g["layers"][n][name])
    out["embed[1:257]"] = lambda g: g["embed"][1:1 + EMBED_ROWS]
    return out


def busiest_expert(grads) -> int:
    """The held expert of the second layer whose down projection has the
    largest gradient: with a router that sends an expert nothing, its
    three matrices' gradients are 0 on both sides and compare nothing."""
    norms = np.linalg.norm(np.asarray(
        grads["layers"][EXPERT_LAYER]["w_down"], np.float32).reshape(
            len(grads["layers"][EXPERT_LAYER]["w_down"]), -1), axis=1)
    return int(np.argmax(norms))


def gradient_slices(cfg: dict, grads, expert: int | None = None) -> dict:
    """The named slices of a gradient tree, on the host, and under
    "expert" which expert's they are (the tree's busiest, if not given)."""
    if expert is None:
        expert = busiest_expert(grads)
    out = {name: np.asarray(pick(grads), np.float32)
           for name, pick in named_slices(cfg, expert).items()}
    out["expert"] = expert
    return out


def relative_error(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def shape_faults(cfg: dict, params) -> list[str]:
    """What is wrong with a loaded model's parameter tree; [] if nothing."""
    import jax

    want = expected_shapes(cfg)
    try:
        pairs = jax.tree_util.tree_map(
            lambda shape, x: (shape, x), want, params,
            is_leaf=lambda x: isinstance(x, tuple))
    except ValueError as e:
        return [f"tree differs: {e}"]
    wrong = []
    for path, (shape, x) in jax.tree_util.tree_leaves_with_path(
            pairs, is_leaf=lambda x: isinstance(x, tuple)
            and len(x) == 2 and isinstance(x[0], tuple)):
        x = np.asarray(x)
        name = jax.tree_util.keystr(path)
        if x.shape != shape or x.dtype != np.float32:
            wrong.append(f"{name} is {x.dtype}{x.shape}, not float32{shape}")
        elif not np.isfinite(x).all():
            wrong.append(f"{name} is not finite")
    return wrong


def edge_rows(seq_len: int, window: int, block: int) -> list[int]:
    """Query rows more than a window apart, so that no row of the list
    attends to another's band-edge keys. Every other one has its last
    key inside the band on the first row of a key block (and its first
    key outside on the last row of the block before, which the kernel
    must not visit); the last is the history's last row."""
    step = -(-(window + 1) // block) * block
    rows = list(range(window - 1 + block, seq_len, step))
    shift = min(block // 2 + 1, step - (window + 1))
    rows = [r + shift * (n % 2) for n, r in enumerate(rows)]
    if not rows or seq_len - 1 - rows[-1] <= window:
        rows = rows[:-1]
    return rows + [seq_len - 1]


def band_edge_probe(cfg: dict, seq_len: int, block: int, seed: int) -> dict:
    """Seeded float32 arrays that hold bfloat16 values: q (1, Hq, S, D),
    k, v (1, Hkv, S, D), and the cotangent `ct` of the output, zero but
    on `rows`."""
    import jax.numpy as jnp

    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rng = np.random.default_rng([seed, 0xED6E])

    def draw(*shape):
        return np.asarray(jnp.asarray(
            rng.standard_normal(shape, np.float32), jnp.bfloat16),
            np.float32)

    rows = edge_rows(seq_len, cfg["sliding_window"], block)
    ct = np.zeros((1, hq, seq_len, d), np.float32)
    ct[:, :, rows] = draw(1, hq, len(rows), d)
    return {"q": draw(1, hq, seq_len, d), "k": draw(1, hkv, seq_len, d),
            "v": draw(1, hkv, seq_len, d), "ct": ct, "rows": rows,
            "window": cfg["sliding_window"]}


def band_edge_slice(dk, dv, probe: dict) -> np.ndarray:
    """The rows of dk and dv at each probed query's last key inside the
    band (r - window + 1) and first key outside it (r - window)."""
    w = probe["window"]
    keys = [r - w + 1 for r in probe["rows"]] + [
        r - w for r in probe["rows"] if r - w >= 0]
    return np.stack([np.asarray(dk, np.float32)[0][:, keys],
                     np.asarray(dv, np.float32)[0][:, keys]])


def reference_band_edge(probe: dict, faults=None) -> np.ndarray:
    import jax

    attend = jax.jit(lambda q, k, v, ct: jax.vjp(
        partial(ref.attention, window=probe["window"],
                faults=faults or {}), q, k, v)[1](ct))
    with jax.default_matmul_precision("highest"):
        _, dk, dv = attend(probe["q"], probe["k"], probe["v"], probe["ct"])
    return band_edge_slice(dk, dv, probe)


def reference_numbers(cfg: dict, params0, tokens0, model_params,
                      held_tokens, expert: int, probe: dict,
                      faults=None) -> dict:
    """The reference's side: step-0 loss and named gradient slices on the
    initial weights (expert `expert`'s: the one the program's side
    took), the persisted model's loss on each of the held batches
    (held_tokens: (batches, B, S + 1)), and the band-edge slice."""
    import jax

    # one compiled program serves every batch (the held batches'
    # gradients are not looked at): every large executable a run adds has
    # to share the machine's compile cache with the others
    grad_of = jax.jit(jax.value_and_grad(
        partial(ref.loss, cfg=cfg, faults=faults or {})))
    loss0, grads = grad_of(params0, tokens0)
    slices = gradient_slices(cfg, grads, expert)
    del grads
    held = [float(grad_of(model_params, batch)[0]) for batch in held_tokens]
    return {"loss0": float(loss0), "slices": slices, "held_losses": held,
            "band_edge": reference_band_edge(probe, faults)}


def check(cfg: dict, limits: dict, program: dict, reference: dict) -> dict:
    """program: loss_logged, loss0, slices, held_losses, band_edge,
    shape_faults. reference: loss0, slices, held_losses, band_edge. ->
    {"correct", "compared": lines, "numbers"}."""
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

    faults = program["shape_faults"]
    ok = ok and not faults
    compared.append("persisted model: the configuration's shapes, float32, "
                    "finite: " + ("ok" if not faults
                                  else "FAILED " + "; ".join(faults[:4])))
    hold("loss_logged_rel",
         abs(program["loss_logged"] - program["loss0"])
         / abs(program["loss0"]),
         f"step-0 loss the job logged {program['loss_logged']:.8g} against "
         f"the step program's {program['loss0']:.8g}, relative")
    hold("loss_step0_rel",
         abs(program["loss0"] - reference["loss0"]) / abs(reference["loss0"]),
         f"step-0 loss program {program['loss0']:.8g} against reference "
         f"{reference['loss0']:.8g}, relative")
    errors = {name: relative_error(program["slices"][name], want)
              for name, want in reference["slices"].items()
              if name != "expert"}
    numbers["grad_rel_by_slice"] = errors
    numbers["expert"] = program["slices"]["expert"]
    # three families, a limit each: a top-k choice that flips under
    # bfloat16 rounding moves a router's gradient most, an expert's
    # matrices see only the tokens routed there, and the attention and
    # embedding slices ("dense") sum over every token
    def family(name: str) -> str:
        if name.endswith(".router"):
            return "router"
        return "expert" if "[e]" in name else "dense"

    for what in ("router", "expert", "dense"):
        group = {n: e for n, e in errors.items() if family(n) == what}
        worst = max(group, key=group.get)
        hold(f"grad_{what}_rel", group[worst],
             f"step-0 gradients of {len(group)} {what} slices against the "
             f"reference's, largest relative error (at {worst})")
    hold("band_edge_rel",
         relative_error(program["band_edge"], reference["band_edge"]),
         "window kernel's key and value gradients at "
         f"{program['band_edge'].shape[2]} band-edge keys against the "
         "reference's, relative error")
    mine, theirs = (float(np.mean(side["held_losses"]))
                    for side in (program, reference))
    numbers["held_rel_by_batch"] = [
        (a - b) / b for a, b in zip(program["held_losses"],
                                    reference["held_losses"])]
    hold("held_loss_rel", abs(mine - theirs) / abs(theirs),
         f"persisted model on {len(reference['held_losses'])} held "
         f"batches: program {mine:.8g} against reference {theirs:.8g}, "
         "relative")
    hold("held_below_step0", reference["loss0"] - theirs,
         f"held-batch loss {theirs:.6g} below the step-0 loss "
         f"{reference['loss0']:.6g} by")
    return {"correct": ok, "compared": compared, "numbers": numbers}

