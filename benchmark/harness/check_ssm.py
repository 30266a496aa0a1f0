"""What decides `correct` in a train_sequence_ssm cell: the timed path's
own numbers against the plain reference
(benchmark/reference/ssm_moe_lm.py), at the published widths and the
timed shapes.

The child (benchmark/drivers/train_sequence_ssm_child.py) hands over what
the program produced; nothing of the program is imported here:

  1. the step-0 loss the window's last job logged (its seeded initial
     weights on its first batch), and the same loss from the jobs' own
     step program run once more on the same weights and batch: they must
     agree (the same program twice), and agree with the reference's;
  2. that step's gradients (Adam's first moment after one step from
     zero, over 1 - b1) of named slices (`named_slices`) against
     `jax.grad` of the reference, each by ||program - reference|| /
     ||reference||, in six families with a limit each, by block kind:
     "ssm" (in_proj, the convolution's weights and bias, out_proj of the
     first and the last M block), "decay" (A_log, dt_bias, D of the
     same two), "router" (the four routers), "expert" (the two matrices
     of the busiest held expert and of the shared expert of the second E
     block), "attention" (W_q, W_k, W_v, W_o) and "dense" (rows 1-256 of
     the head and of the embedding: they sum over every token);
  3. the router's bias, held to the reference and not to a number the
     program reports of itself, as benchmark/harness/check_latent.py
     holds it: that step's token counts over every routed expert against
     the reference's routing (`router_counts_rel`), the bias the step
     left against the rule on those counts and the persisted biases a
     whole number of moves from zero (`router_bias_abs`);
  4. the model the last job persisted, as `load_models` returned it: the
     configuration's shapes, float32, finite; its mean loss over
     HELD_BATCHES held seeded batches by the program (the step program
     again) and by the reference, equal within a limit and below the
     step-0 loss by a margin;
  5. two probes of what the model's own numbers hardly show. The
     router's: the program's routing (`route_top_k` as the blocks call
     it) and the reference's on seeded logits under each router's
     persisted bias (at step 0 every bias is zero). The scan's: the
     program's scan op (`ssd_scan`, as the M blocks call it, at the cell's
     chunk) on one seeded history at the published widths, with the
     first M block's persisted A_log, dt_bias and D, against the
     reference's recurrence a position at a time. The op is given
     float32 operands there (under "highest" matmul precision), so what
     is left is its own float32 arithmetic: the running sums, the
     decays, the carried states. In the step every product rounds its
     operands to bfloat16, which moves a gradient by more than a carry
     kept in bfloat16 does; here a sound op reads ~1e-6.

Each limit is in the configuration file (`check.limits`) with the
readings it was set between (PERF.md section 2). `faults` makes the
reference a faulty one, and PROGRAM_FAULTS the program's side: the check
must then fail, which benchmark/tests/test_check_ssm.py holds it to. A
fault is a number the reference's compiled program takes as an argument
(ssm_moe_lm.SOUND), so one program serves every fault.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.check_latent import (  # noqa: F401
    PROGRAM_FAULTS, bias_fault,
)
from benchmark.harness.check_sequence import relative_error
from benchmark.reference import ssm_moe_lm as ref

EMBED_ROWS = 256
HELD_BATCHES = 2

# the faulty references the limits are set against and tested with
FAULTS = {
    "chunk carry dropped": {"state_reset": 1.0},
    "carry in bfloat16": {"carry_bf16": 1.0},
    "running sums of dt A in bfloat16": {"decay_bf16": 1.0},
    "convolution one tap late": {"conv_shift": 1.0},
    "convolution not causal": {"conv_shift": -1.0},
    "dt without softplus": {"softplus": 0.0},
    "dt without dt_bias": {"dt_bias": 0.0},
    "D x left out": {"skip": 0.0},
    "gated norm over one group": {"one_group": 1.0},
    "norm before gate": {"norm_before_gate": 1.0},
    "relu for relu^2": {"relu2": 0.0},
    "scaling 1.0 for 2.5": {"routed_scaling": 1.0},
    "shared expert left out": {"shared": 0.0},
    "top-6 on the unbiased score": {"select_biased": 0.0},
    "bfloat16 accumulation": {"accumulate_bf16": 1.0},
}
# the faults an explore run reads the held batches' loss of too: the
# precision below the one the configuration states (the control), and
# the fault `held_loss_rel` answers for
HELD_FAULTS = ("bfloat16 accumulation", "shared expert left out")
SSM = ("in_proj", "conv_w", "conv_b", "out_proj")
DECAY = ("A_log", "dt_bias", "D")
EXPERT = ("w_up", "w_down", "shared_up", "shared_down")
ATTENTION = ("wq", "wk", "wv", "wo")
FAMILIES = ("ssm", "decay", "router", "expert", "attention", "dense")


def blocks_of(cfg: dict, kind: str) -> list[int]:
    return [n for n, k in enumerate(ref.kinds(cfg)) if k == kind]


def expected_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, di = cfg["mamba_num_heads"], (cfg["mamba_num_heads"]
                                     * cfg["mamba_head_dim"])
    conv = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    held, routed = cfg["n_routed_experts"], cfg["num_experts_routed"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    by_kind = {
        "M": {"norm": (d,), "in_proj": (d, di + conv + h),
              "conv_w": (cfg["conv_kernel"], conv), "conv_b": (conv,),
              "dt_bias": (h,), "A_log": (h,), "D": (h,), "ssm_norm": (di,),
              "out_proj": (di, d)},
        "E": {"norm": (d,), "router": (d, routed), "router_bias": (routed,),
              "w_up": (held, d, f), "w_down": (held, f, d),
              "shared_up": (d, fs), "shared_down": (fs, d)},
        "*": {"norm": (d,), "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
              "wo": (hq, d)},
    }
    return {"embed": (cfg["vocab_size"], d), "head": (cfg["vocab_size"], d),
            "final_norm": (d,),
            "layers": [dict(by_kind[k]) for k in ref.kinds(cfg)]}


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


def named_slices(cfg: dict, expert: int) -> dict:
    """name -> function(gradient tree) -> array (see the header)."""
    scans, routers = blocks_of(cfg, "M"), blocks_of(cfg, "E")
    second = routers[min(1, len(routers) - 1)]

    def leaf(n, name, index=None):
        if index is None:
            return lambda g: g["layers"][n][name]
        return lambda g: g["layers"][n][name][index]

    out = {}
    for n in dict.fromkeys((scans[0], scans[-1])):
        for name in SSM + DECAY:
            out[f"block{n}.{name}"] = leaf(n, name)
    for n in routers:
        out[f"block{n}.router"] = leaf(n, "router")
    for name in EXPERT:
        held = name.startswith("w_")
        out[f"block{second}.{name}" + ("[e]" if held else "")] = leaf(
            second, name, expert if held else None)
    for n in blocks_of(cfg, "*")[:1]:
        for name in ATTENTION:
            out[f"block{n}.{name}"] = leaf(n, name)
    out["head[1:257]"] = lambda g: g["head"][1:1 + EMBED_ROWS]
    out["embed[1:257]"] = lambda g: g["embed"][1:1 + EMBED_ROWS]
    return out


def family(name: str) -> str:
    leaf = name.split(".")[-1].split("[")[0]
    for what, names in (("ssm", SSM), ("decay", DECAY), ("expert", EXPERT),
                        ("attention", ATTENTION), ("router", ("router",))):
        if leaf in names:
            return what
    return "dense"


def busiest_expert(cfg: dict, grads) -> int:
    """The held expert of the second E block whose down projection has
    the largest gradient: an expert the router sends nothing has zero
    gradients on both sides, which compare nothing."""
    routers = blocks_of(cfg, "E")
    w = np.asarray(grads["layers"][routers[min(1, len(routers) - 1)]][
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
    """(routers, routed) float64, the E blocks in order."""
    return np.stack([np.asarray(params["layers"][n]["router_bias"],
                                np.float64) for n in blocks_of(cfg, "E")])


# ---------------------------------------------------------------------------
# the probes
# ---------------------------------------------------------------------------

def router_probe(cfg: dict, seed: int, params, tokens: int = 4096) -> dict:
    """Seeded float32 router logits (tokens, routed) and every router's
    persisted bias (routers, routed)."""
    rng = np.random.default_rng([seed, 0xB1A5])
    return {"logits": rng.standard_normal(
                (tokens, cfg["num_experts_routed"]), np.float32) * 1.5,
            "bias": router_biases(cfg, params).astype(np.float32)}


def scan_probe(cfg: dict, seed: int, params, positions: int) -> dict:
    """One seeded history's inputs of the scan at the configuration's
    widths, float32: x (S, H, P), dt (S, H) after softplus, with the
    first M block's persisted dt_bias under it, B and C (S, G, N); and
    that block's a = -exp(A_log) and D."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    lp = params["layers"][blocks_of(cfg, "M")[0]]
    rng = np.random.default_rng([seed, 0x55D])

    def normal(*shape):
        return rng.standard_normal(shape, np.float32)

    raw = normal(positions, h) + np.asarray(lp["dt_bias"], np.float32)
    return {"x": normal(positions, h, p),
            "dt": np.logaddexp(raw, 0.0).astype(np.float32),
            "a": -np.exp(np.asarray(lp["A_log"], np.float32)),
            "b": normal(positions, g, n), "c": normal(positions, g, n),
            "d": np.asarray(lp["D"], np.float32)}


# ---------------------------------------------------------------------------
# the reference's side, and the verdict
# ---------------------------------------------------------------------------

class Reference:
    """The reference's programs, compiled once a run: a fault is an
    argument, so the sound reference and every faulty one share them."""

    def __init__(self, cfg: dict):
        import jax

        self.cfg = cfg
        self.grad_of = jax.jit(jax.value_and_grad(
            lambda params, tokens, flags: ref.loss(
                params, tokens, cfg, flags)))
        self.counts_of = jax.jit(lambda params, tokens, flags:
                                 ref.routed_counts(params, tokens, cfg, flags))
        # the probes: seeded logits through an identity router, so that
        # its scores are the probe's; the recurrence a position at a time
        eye = np.eye(cfg["num_experts_routed"], dtype=np.float32)
        self.route = jax.jit(lambda logits, bias, flags: ref.routing(
            logits, eye, bias, cfg, flags)[0])
        self.scan = jax.jit(lambda pr, flags: ref.scan(
            pr["x"], pr["dt"], pr["a"], pr["b"], pr["c"], pr["d"], flags,
            cfg["chunk_size"]))

    def numbers(self, params0, tokens0, model_params, held_tokens,
                expert: int, probes: dict, faults=None) -> dict:
        """Step-0 loss and named gradient slices on the initial weights
        (expert `expert`'s: the one the program's side took) and that
        step's routed counts, the persisted model's loss on each held
        batch (held_tokens: (batches, B, S + 1)), and the two probes.
        `params0` and `model_params` are functions that make the trees:
        the device holds one of them at a time beside what the
        reference's own program takes."""
        cfg = self.cfg
        flags = {k: np.float32(v)
                 for k, v in ref.with_faults(cfg, faults).items()}
        params = params0()
        loss0, grads = self.grad_of(params, tokens0, flags)
        slices = gradient_slices(cfg, grads, expert)
        del grads
        counts0 = np.asarray(self.counts_of(params, tokens0, flags))
        params = model_params()
        held = [float(self.grad_of(params, batch, flags)[0])
                for batch in held_tokens]
        router = probes["router"]
        return {"loss0": float(loss0), "slices": slices,
                "held_losses": held, "counts0": counts0,
                # (routers, tokens, routed) routing weights
                "router_probe": np.stack([
                    np.asarray(self.route(router["logits"], bias, flags))
                    for bias in router["bias"]]),
                # (S, H, P)
                "scan_probe": np.asarray(self.scan(probes["scan"], flags))}


def check(cfg: dict, limits: dict, program: dict, reference: dict) -> dict:
    """program: logged_loss, loss0, slices, counts0 (routers, histories,
    routed), bias1, bias_model (routers, routed), steps, held_losses,
    router_probe, scan_probe, shape_faults. reference: loss0, slices,
    counts0 (routers, routed), held_losses, router_probe, scan_probe.
    -> {"correct", "compared": lines, "numbers"}."""
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
    hold("loss_logged_rel", rel(program["logged_loss"], program["loss0"]),
         f"step-0 loss the job logged {program['logged_loss']:.8g} against "
         f"the step program's {program['loss0']:.8g}, relative")
    hold("loss_rel", rel(program["loss0"], reference["loss0"]),
         f"step-0 loss program {program['loss0']:.8g} against reference "
         f"{reference['loss0']:.8g}, relative")
    errors = {name: relative_error(program["slices"][name], want)
              for name, want in reference["slices"].items()
              if name != "expert"}
    numbers["grad_rel_by_slice"] = errors
    numbers["expert"] = program["slices"]["expert"]
    for what in FAMILIES:
        group = {n: e for n, e in errors.items() if family(n) == what}
        worst = max(group, key=lambda n: (np.isnan(group[n]), group[n]))
        hold(f"grad_{what}_rel", group[worst],
             f"step-0 gradients of {len(group)} {what} slices against the "
             f"reference's, largest relative error (at {worst})")
    hold("scan_probe_rel",
         relative_error(program["scan_probe"], reference["scan_probe"]),
         "the scan op in float32 on a seeded history against the "
         "recurrence a position at a time, relative error")
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
