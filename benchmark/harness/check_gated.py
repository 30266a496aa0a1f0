"""What decides `correct` in a train_sequence_gated cell: the timed path's
own numbers against the plain reference
(benchmark/reference/gated_gqa_moe_lm.py), at the published widths and
the timed shapes.

The child (benchmark/drivers/train_sequence_gated_child.py) hands over
what the program produced; nothing of the program is imported here:

  1. the step-0 loss the window's last job logged (its seeded initial
     weights on its first batch), and the same loss from the jobs' own
     step program run once more on the same weights and batch: they must
     agree (the same program twice), and agree with the reference's;
  2. that step's gradients (Adam's first moment after one step from
     zero, over 1 - b1) of named slices (`named_slices`) against
     `jax.grad` of the reference, each by ||program - reference|| /
     ||reference||, in five families with a limit each: "attention"
     (W_q, W_k, W_o of the first sliding layer and of the first and the
     last full one: the head counts, the key-value head a query head
     reads, both rotations), "gate" (the gate W_g and the two head-norm
     gains of the same layers), "router" (every router), "expert" (the
     three matrices of the busiest held expert and of the shared expert
     of the second expert layer) and "dense" (the leading dense layer's
     down projection, and rows 1-256 of the head and of the embedding:
     they sum over every token);
  3. the band's edge, as benchmark/harness/check_sequence.py holds it:
     the program's window kernel and the reference's attention on seeded
     queries, keys and values at the sliding layer's heads (64 over 8)
     and the timed length, under a cotangent that is zero except on a
     few query rows more than a window apart; the gradients of the keys
     and values at each such row's last key inside the band and first
     key outside it;
  4. the router's bias, held to the reference and not to a number the
     program reports of itself, as benchmark/harness/check_latent.py
     holds it: that step's token counts over every routed expert against
     the reference's routing (`router_counts_rel`), the bias the step
     left against the rule on those counts and the persisted biases a
     whole number of moves from zero (`router_bias_abs`), and the
     program's routing on seeded logits under each router's persisted
     bias against the reference's (`router_probe_rel`);
  5. the model the last job persisted, as `load_models` returned it: the
     configuration's shapes, float32, finite; its mean loss over
     HELD_BATCHES held seeded batches by the program (the step program
     again) and by the reference, equal within a limit and below the
     step-0 loss by a margin;
  6. the timed step at the precision the configuration states, as
     benchmark/harness/check_loop.py holds it: the reference once more
     with every product's operands rounded to bfloat16 and its sum kept
     in float32 (forward only), and against it the step program's loss
     on the persisted model, a held batch at a time (`held_stated_rel`,
     the largest relative difference). Against the float32 reference the
     held loss carries what rounding the weights and the operands does,
     which every bfloat16 program shares; against this one that part
     cancels, and what is left tells a program that keeps its products'
     sums in float32 from one that rounds them to bfloat16 (the control).

Each limit is in the configuration file (`check.limits`) with the
readings it was set between (PERF.md section 2). `faults` makes the
reference a faulty one, and PROGRAM_FAULTS the program's side: the check
must then fail, which benchmark/tests/test_check_gated.py holds it to. A
fault is a number the reference's compiled program takes as an argument
(gated_gqa_moe_lm.SOUND), so one program serves every fault.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import check_latent
from benchmark.harness.check_latent import bias_fault
from benchmark.harness.check_sequence import (
    band_edge_probe, band_edge_slice, relative_error,
)
from benchmark.reference import gated_gqa_moe_lm as ref

EMBED_ROWS = 256
HELD_BATCHES = 2
FULL, SLIDING = ref.KINDS

# the faulty references the limits are set against and tested with: the
# other reading of everything the configuration file lists as assumed,
# and what a layer kind's own head count, rotation and window could be
# mistaken for
FAULTS = {
    "key-value head h // 8 on a full layer": {"kv_group_full": 8},
    "the whole head rotated on a full layer": {"rotary_full": 1.0},
    "default RoPE for YaRN on a full layer": {"yarn_full": 0.0},
    "the two kinds' thetas swapped": {"theta_swap": 1.0},
    "window of 513": {"window": 513},
    "gate left out": {"gate": 0.0},
    "sigmoid for softplus": {"gate_softplus": 0.0},
    "gate read from x": {"gate_normed": 0.0},
    "head norms left out": {"head_norms": 0.0},
    "shared expert left out": {"shared": 0.0},
    "softmax for sigmoid": {"score_sigmoid": 0.0},
    "top-7 for top-8": {"top_k": 7},
    "scaling 1.0 for 2.5": {"routed_scaling": 1.0},
    "router weights not normalised": {"norm_topk": 0.0},
    "layer 0 given the experts' half": {"dense": 0.0},
    "bfloat16 accumulation": {"accumulate_bf16": 1.0},
}
# what a train step could get wrong about its counts and its bias:
# check_latent's, which reads the held experts' number under its own
# family's key
PROGRAM_FAULTS = {
    name: (lambda program, cfg, wrong=wrong: wrong(
        program, dict(cfg, n_routed_experts=cfg["num_experts"])))
    for name, wrong in check_latent.PROGRAM_FAULTS.items()}
# the precision the configuration states, and the control on top of it
STATED = {"operands_bf16": 1.0}
# the faults an explore run reads the held batches' losses of too, at
# both precisions: the control, and the mildest fault of each half of a
# layer (what `held_loss_rel` is set against)
HELD_FAULTS = ("bfloat16 accumulation",
               "default RoPE for YaRN on a full layer",
               "the two kinds' thetas swapped", "top-7 for top-8",
               "shared expert left out")
ATTENTION = ("wq", "wk", "wo")
GATE = ("w_gate_heads", "q_head_norm", "k_head_norm")
EXPERT = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
FAMILIES = ("attention", "gate", "router", "expert", "dense")


def router_layers(cfg: dict) -> list[int]:
    return list(range(ref.dense_layers(cfg), cfg["num_hidden_layers"]))


def probed_layers(cfg: dict) -> list[int]:
    """The first sliding layer, the first full layer and the last."""
    kinds = ref.kinds(cfg)
    full = [n for n, k in enumerate(kinds) if k == FULL]
    sliding = [n for n, k in enumerate(kinds) if k == SLIDING]
    return sorted(set(sliding[:1] + full[:1] + full[-1:]))


def expected_shapes(cfg: dict) -> dict:
    d, f, dh = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["head_dim"])
    hkv = cfg["num_key_value_heads"] * dh
    held, routed = cfg["num_experts"], cfg["num_experts_routed"]
    i, fs = cfg["intermediate_size"], cfg["shared_expert_intermediate_size"]
    dense = {"mlp_gate": (d, i), "mlp_up": (d, i), "mlp_down": (i, d)}
    sparse = {"router": (d, routed), "router_bias": (routed,),
              "w_gate": (held, d, f), "w_up": (held, d, f),
              "w_down": (held, f, d), "shared_gate": (d, fs),
              "shared_up": (d, fs), "shared_down": (fs, d)}
    layers = []
    for n, kind in enumerate(ref.kinds(cfg)):
        h = ref.q_heads(cfg, kind)
        layers.append({
            "norm1": (d,), "norm2": (d,), "wq": (d, h * dh), "wk": (d, hkv),
            "wv": (d, hkv), "wo": (h * dh, d), "w_gate_heads": (d, h),
            "q_head_norm": (dh,), "k_head_norm": (dh,),
            **(dense if n < ref.dense_layers(cfg) else sparse)})
    return {"embed": (cfg["vocab_size"], d), "head": (cfg["vocab_size"], d),
            "final_norm": (d,), "layers": layers}


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
    routers = router_layers(cfg)
    second = routers[min(1, len(routers) - 1)]

    def leaf(n, name, index=None):
        if index is None:
            return lambda g: g["layers"][n][name]
        return lambda g: g["layers"][n][name][index]

    out = {}
    for n in probed_layers(cfg):
        kind = ref.kinds(cfg)[n].split("_")[0]
        for name in ATTENTION + GATE:
            out[f"layer{n}.{name}.{kind}"] = leaf(n, name)
    for n in routers:
        out[f"layer{n}.router"] = leaf(n, "router")
    for name in EXPERT:
        out[f"layer{second}.{name}[e]"] = leaf(second, name, expert)
    for name in SHARED:
        out[f"layer{second}.{name}"] = leaf(second, name)
    for n in range(ref.dense_layers(cfg)):
        out[f"layer{n}.mlp_down"] = leaf(n, "mlp_down")
    out["head[1:257]"] = lambda g: g["head"][1:1 + EMBED_ROWS]
    out["embed[1:257]"] = lambda g: g["embed"][1:1 + EMBED_ROWS]
    return out


def family(name: str) -> str:
    leaf = name.split(".")[1].split("[")[0] if "." in name else name
    for what, names in (("attention", ATTENTION), ("gate", GATE),
                        ("expert", EXPERT + SHARED),
                        ("router", ("router",))):
        if leaf in names:
            return what
    return "dense"


def busiest_expert(cfg: dict, grads) -> int:
    """The held expert of the second expert layer whose down projection
    has the largest gradient: an expert the router sends nothing has zero
    gradients on both sides, which compare nothing."""
    routers = router_layers(cfg)
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
    """(routers, routed) float64, the layers that route in order."""
    return np.stack([np.asarray(params["layers"][n]["router_bias"],
                                np.float64) for n in router_layers(cfg)])


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


def edge_probe(cfg: dict, seq_len: int, block: int, seed: int) -> dict:
    """`check_sequence.band_edge_probe` at the sliding layer's heads."""
    return band_edge_probe(
        dict(cfg, num_attention_heads=ref.q_heads(cfg, SLIDING)), seq_len,
        block, seed)


# ---------------------------------------------------------------------------
# the reference's side, and the verdict
# ---------------------------------------------------------------------------

class Reference:
    """The reference's programs, compiled once a run: a fault is an
    argument, so the sound reference and every faulty one share them."""

    def __init__(self, cfg: dict):
        import jax

        self.cfg = cfg
        # every reading of the rotations, on the device once a length:
        # an argument of the programs, not a constant inside them
        self.tables: dict = {}
        self.grad_of = jax.jit(jax.value_and_grad(
            lambda params, tokens, flags, tables: ref.loss(
                params, tokens, cfg, flags, tables=tables)))
        # forward only: the stated precision's side
        self.value_of = jax.jit(
            lambda params, tokens, flags, tables: ref.loss(
                params, tokens, cfg, flags, tables=tables))
        self.counts_of = jax.jit(
            lambda params, tokens, flags, tables: ref.routed_counts(
                params, tokens, cfg, flags, tables))
        # seeded logits through an identity router, so that its scores
        # are the probe's
        eye = np.eye(cfg["num_experts_routed"], dtype=np.float32)
        self.route = jax.jit(lambda logits, bias, flags: ref.routing(
            logits, eye, bias, cfg, flags)[0])
        hq, hkv = ref.q_heads(cfg, SLIDING), cfg["num_key_value_heads"]
        kv_of = np.arange(hq) // (hq // hkv)

        def edge(q, k, v, ct, flags):
            return jax.vjp(lambda q, k, v: ref.attention(
                q, k, v, kv_of, flags["window"], flags), q, k, v)[1](ct)

        self.edge = jax.jit(edge)

    def rotations(self, tokens) -> dict:
        import jax

        positions = tokens.shape[1] - 1
        if positions not in self.tables:
            self.tables[positions] = jax.device_put(
                ref.rotation_tables(self.cfg, positions))
        return self.tables[positions]

    def flags(self, faults=None) -> dict:
        return {k: np.float32(v)
                for k, v in ref.with_faults(self.cfg, faults).items()}

    def band_edge(self, probe: dict, faults=None) -> np.ndarray:
        import jax

        with jax.default_matmul_precision("highest"):
            _, dk, dv = self.edge(probe["q"][0], probe["k"][0], probe["v"][0],
                                  probe["ct"][0], self.flags(faults))
        return band_edge_slice(np.asarray(dk)[None], np.asarray(dv)[None],
                               probe)

    def numbers(self, params0, tokens0, model_params, held_tokens,
                expert: int, probes: dict, faults=None) -> dict:
        """Step-0 loss and named gradient slices on the initial weights
        (expert `expert`'s: the one the program's side took) and that
        step's routed counts, the persisted model's loss on each held
        batch (held_tokens: (batches, B, S + 1)) in float32 and at the
        stated precision, and the two probes. `params0` and
        `model_params` are functions that make the trees: the device
        holds one of them at a time beside what the reference's own
        program takes."""
        cfg = self.cfg
        flags = self.flags(faults)
        stated = self.flags({**STATED, **(faults or {})})
        params, tables = params0(), self.rotations(tokens0)
        loss0, grads = self.grad_of(params, tokens0, flags, tables)
        slices = gradient_slices(cfg, grads, expert)
        del grads
        counts0 = np.asarray(self.counts_of(params, tokens0, flags, tables))
        params = model_params()
        held = [float(self.value_of(params, batch, flags, tables))
                for batch in held_tokens]
        held_stated = [float(self.value_of(params, batch, stated, tables))
                       for batch in held_tokens]
        router = probes["router"]
        return {"loss0": float(loss0), "slices": slices,
                "held_losses": held, "held_stated": held_stated,
                "counts0": counts0,
                # (routers, tokens, routed) routing weights
                "router_probe": np.stack([
                    np.asarray(self.route(router["logits"], bias, flags))
                    for bias in router["bias"]]),
                "band_edge": self.band_edge(probes["edge"], faults)}


def check(cfg: dict, limits: dict, program: dict, reference: dict) -> dict:
    """program: logged_loss, loss0, slices, counts0 (routers, histories,
    routed), bias1, bias_model (routers, routed), steps, held_losses,
    router_probe, band_edge, shape_faults. reference: loss0, slices,
    counts0 (routers, routed), held_losses, held_stated, router_probe,
    band_edge. -> {"correct", "compared": lines, "numbers"}."""
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
    hold("band_edge_rel",
         relative_error(program["band_edge"], reference["band_edge"]),
         "window kernel's key and value gradients at "
         f"{program['band_edge'].shape[2]} band-edge keys against the "
         "reference's, relative error")
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
    numbers["held_stated_by_batch"] = [
        (a - b) / b for a, b in zip(program["held_losses"],
                                    reference["held_stated"])]
    hold("held_stated_rel",
         max(abs(x) for x in numbers["held_stated_by_batch"]),
         f"persisted model's loss on each of "
         f"{len(reference['held_stated'])} held batches: the step program "
         f"{program['held_losses']} against the reference at the stated "
         f"precision {reference['held_stated']}, largest relative "
         "difference")
    hold("held_below_step0", reference["loss0"] - theirs,
         f"held-batch loss {theirs:.6g} below the step-0 loss "
         f"{reference['loss0']:.6g} by")
    return {"correct": ok, "compared": compared, "numbers": numbers}
