"""What decides `correct` in a train_sequence_loop cell: the timed path's
own numbers against the plain reference
(benchmark/reference/looped_lm.py), at the published widths and the
timed shapes.

The child (benchmark/drivers/train_sequence_loop_child.py) hands over
what the program produced; nothing of the program is imported here:

  1. the step-0 loss and the T exit losses the window's last job logged
     (its seeded initial weights on its first batch), and the same from
     the jobs' own step program run once more on the same weights and
     batch: they must agree (the same program twice);
  2. that step's loss, its T exit losses (`exit_loss_rel`: the largest
     relative difference; another number of exits is infinitely far) and
     its T exit masses (`exit_mass_abs`: the mean p_t over the batch's
     tokens, largest absolute difference) against the reference's;
  3. that step's gradients (Adam's first moment after one step from
     zero, over 1 - b1) of named slices (`named_slices`) against
     `jax.grad` of the reference, each by ||program - reference|| /
     ||reference||, in three families with a limit each: W_q, W_o and
     the SwiGLU's W_down of the first and the last layer ("layer": the
     sum over the T passes of one set of weights); the embedding's and
     the head's rows 1-256 ("dense": they sum over every token, the
     head's over every exit too); the exit gate's weight and bias
     ("gate"). The gate's two are sums over tokens and exits of terms
     whose signs differ, and in about one seed of ten they all but
     cancel (the bias's relative error read 0.002 to 0.11 on nine seeds
     of one program): their error is taken over the size the sum would
     have if its terms were unrelated, sqrt(sum |term|^2), which the
     reference gives beside its gradient (`gate_terms`);
  4. the model the last job persisted, as `load_models` returned it: the
     configuration's shapes, float32, finite; its mean loss over
     HELD_BATCHES held seeded batches by the program (the step program
     again) and by the reference, equal within a limit and below the
     step-0 loss by a margin;
  5. the timed step at the precision the configuration states: the
     reference once more with every product's operands rounded to
     bfloat16 and its sum kept in float32 (`STATED`; forward only), and
     against it the loss and the T exit losses of the step program on
     the persisted model over the held batches (`held_stated_rel`, the
     largest relative difference). Against the float32 reference the
     held loss carries what rounding the weights and the operands does
     to a model's loss, which every bfloat16 program shares and a limit
     there must allow (to 5.8e-5 on the chip); against this one that
     part cancels (1.2e-6 to 1.8e-6), and what is left tells a program
     that keeps its products' sums in float32 from one that rounds them
     to bfloat16 (the control: 1.2e-4 to 4.2e-4). This is the limit the
     control answers for, on numbers of the timed step and of the model
     the timed job persisted;
  6. the exit gate's probe, beside it: the program's gate and
     distribution (the two functions its loss calls, handed over by the
     child as one) and the reference's on seeded normed states under the
     persisted gate, compared on the (T, tokens) masses
     (`exit_probe_abs`): the program computes the gate in float32, and a
     product that rounds to bfloat16 moves a mass by a part in a
     thousand;
  7. every job's record: T x L layer applications and as many attention
     forward kernels in the step's program, T exit masses that sum to 1.

Each limit is in the configuration file (`check.limits`) with the
readings it was set between (PERF.md section 2). `faults` makes the
reference a faulty one: the check must then fail, which
benchmark/tests/test_check_loop.py holds it to.
"""

from __future__ import annotations

import json
from functools import partial

import numpy as np

from benchmark.harness.check_sequence import relative_error
from benchmark.reference import looped_lm as ref

ROWS = 256
HELD_BATCHES = 2
# the precision the configuration states (`precision.train`), as the
# reference takes it: bfloat16 operands, float32 sums
STATED = {"operands": "bfloat16"}

# the faulty references the limits are set against and tested with
FAULTS = {
    "bfloat16 accumulation": {"accumulate": "bfloat16"},
    "three passes for four": {"loop_steps": 3},
    "the final norm outside the loop": {"final_norm": "outside"},
    "no post-norms": {"post_norms": False},
    "the last exit gated": {"last_exit": "gated"},
    "the entropy term's sign": {"entropy_sign": -1},
    "the layers' gradient from the last pass": {"layer_grads": "last pass"},
}
LAYER = ("wq", "wo", "mlp_down")


def expected_shapes(cfg: dict) -> dict:
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d),
             "norm1": (d,), "norm1_post": (d,), "norm2": (d,),
             "norm2_post": (d,), "mlp_gate": (d, i), "mlp_up": (d, i),
             "mlp_down": (i, d)}
    return {"embed": (cfg["vocab_size"], d), "head": (cfg["vocab_size"], d),
            "final_norm": (d,), "exit_gate": (d, 1), "exit_bias": (1,),
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])]}


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


def named_slices(cfg: dict) -> dict:
    """name -> function(gradient tree) -> array (see the header)."""
    out = {}
    for n in sorted({0, cfg["num_hidden_layers"] - 1}):
        for name in LAYER:
            out[f"layer{n}.{name}"] = (
                lambda g, n=n, name=name: g["layers"][n][name])
    out["embed[1:257]"] = lambda g: g["embed"][1:1 + ROWS]
    out["head[1:257]"] = lambda g: g["head"][1:1 + ROWS]
    out["exit_gate"] = lambda g: g["exit_gate"]
    out["exit_bias"] = lambda g: g["exit_bias"]
    return out


def family(name: str) -> str:
    if name.startswith("layer"):
        return "layer"
    return "gate" if name.startswith("exit_") else "dense"


def gradient_slices(cfg: dict, grads) -> dict:
    """The named slices of a gradient tree, on the host."""
    return {name: np.asarray(pick(grads), np.float32)
            for name, pick in named_slices(cfg).items()}


def logged_numbers(counters: dict) -> dict:
    """What the check reads of a job's `seq.wait` labels."""
    return {"loss_logged": float(counters["loss_first"]),
            "exit_losses_logged": json.loads(counters["loss_exit_first"])}


def job_faults(cfg: dict, jobs: list[dict]) -> list[str]:
    """What is wrong with the jobs' own records of their step program
    and of their last step's exits; [] if nothing."""
    want = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    wrong = []
    for n, c in enumerate(jobs):
        got = (int(c.get("layer_applications", -1)),
               int(c.get("attn_fwd_kernels", -1)))
        if got != (want, want):
            wrong.append(f"job {n}: layer applications and forward "
                         f"kernels {got}, not {want} each")
        mass = json.loads(c.get("exit_mass_last", "[]"))
        if len(mass) != cfg["total_ut_steps"] or abs(sum(mass) - 1) > 1e-4:
            wrong.append(f"job {n}: exit masses {mass}")
    return wrong


def exit_probe(cfg: dict, seed: int, tokens: int = 4096) -> np.ndarray:
    """Seeded float32 states (T, tokens, d) of unit mean square, as the
    final norm hands them to the gate."""
    rng = np.random.default_rng([seed, 0xE817])
    return rng.standard_normal(
        (cfg["total_ut_steps"], tokens, cfg["hidden_size"]), np.float32)


def reference_exit_probe(params, probe, faults=None) -> np.ndarray:
    """(T, tokens) exit masses of the reference under `params`' gate."""
    import jax

    faults = faults or {}

    def masses(gate, bias, y):
        lam = jax.nn.sigmoid(ref._matmul(y, gate, faults)[..., 0] + bias[0])
        return ref.exit_probabilities(lam, faults)

    return np.asarray(jax.jit(masses)(
        params["exit_gate"], params["exit_bias"], probe))


def reference_numbers(cfg: dict, params0, tokens0, model_params,
                      held_tokens, probe, faults=None) -> dict:
    """The reference's side: the step-0 loss, exit losses, exit masses
    and named gradient slices on the initial weights, and the persisted
    model's loss on each of the held batches (held_tokens: (batches, B,
    S + 1)) and its gate's masses on the probe. `params0` and
    `model_params` are functions that make the trees: the device holds
    one of them at a time beside what the reference's own program
    takes."""
    import jax

    # one compiled program serves every batch
    grad_of = jax.jit(jax.value_and_grad(
        partial(ref.loss, cfg=cfg, faults=faults or {}), has_aux=True))
    (loss0, (ce0, mass0, terms)), grads = grad_of(params0(), tokens0)
    slices = gradient_slices(cfg, grads)
    del grads
    params = model_params()
    held = [float(grad_of(params, batch)[0][0]) for batch in held_tokens]
    return {"exit_probe": reference_exit_probe(params, probe, faults),
            "loss0": float(loss0),
            "exit_losses0": [float(x) for x in ce0],
            "exit_mass0": [float(x) for x in mass0],
            "slices": slices, "held_losses": held,
            "gate_terms": {name: float(x) for name, x in terms.items()}}


def stated_numbers(cfg: dict, model_params, held_tokens,
                   faults=None) -> dict:
    """The reference at the stated precision, forward only: the
    persisted model's loss and exit losses on each held batch. `faults`
    on top of STATED: the control's rounds the sums too."""
    import jax

    value_of = jax.jit(partial(ref.loss, cfg=cfg,
                               faults={**STATED, **(faults or {})}))
    params = model_params()
    held = [value_of(params, batch) for batch in held_tokens]
    return {"held_losses": [float(value) for value, _ in held],
            "held_exit_losses": [[float(x) for x in aux[0]]
                                 for _, aux in held]}


def check(cfg: dict, limits: dict, program: dict, reference: dict,
          stated: dict) -> dict:
    """program: loss_logged, exit_losses_logged, loss0, exit_losses0,
    exit_mass0, slices, held_losses, held_exit_losses, exit_probe,
    shape_faults, job_faults. reference (`reference_numbers`): loss0,
    exit_losses0, exit_mass0, slices, gate_terms, held_losses,
    exit_probe. stated (`stated_numbers`): held_losses,
    held_exit_losses.
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

    def worst(mine, theirs, distance) -> float:
        if len(mine) != len(theirs):
            return float("inf")
        return max(distance(a, b) for a, b in zip(mine, theirs))

    for what, faults in (("persisted model: the configuration's shapes, "
                          "float32, finite", program["shape_faults"]),
                         ("every job's record: T x L layer applications and "
                          "forward kernels, exit masses that sum to 1",
                          program["job_faults"])):
        ok = ok and not faults
        compared.append(what + ": " + (
            "ok" if not faults else "FAILED " + "; ".join(faults[:4])))
    hold("loss_logged_rel",
         max(rel(program["loss_logged"], program["loss0"]),
             worst(program["exit_losses_logged"], program["exit_losses0"],
                   rel)),
         f"step-0 loss and exit losses the job logged "
         f"({program['loss_logged']:.8g}; {program['exit_losses_logged']}) "
         f"against the step program's ({program['loss0']:.8g}), largest "
         "relative difference")
    hold("loss_step0_rel", rel(program["loss0"], reference["loss0"]),
         f"step-0 loss program {program['loss0']:.8g} against reference "
         f"{reference['loss0']:.8g}, relative")
    hold("exit_loss_rel",
         worst(program["exit_losses0"], reference["exit_losses0"], rel),
         f"step-0 exit losses program {program['exit_losses0']} against "
         f"reference {reference['exit_losses0']}, largest relative "
         "difference")
    hold("exit_mass_abs",
         worst(program["exit_mass0"], reference["exit_mass0"],
               lambda a, b: abs(a - b)),
         f"step-0 exit masses program {program['exit_mass0']} against "
         f"reference {reference['exit_mass0']}, largest absolute difference")
    def error(name: str, mine, theirs) -> float:
        over = reference["gate_terms"].get(name)
        if over is None:
            return relative_error(mine, theirs)
        return float(np.linalg.norm(np.asarray(mine, np.float64) - theirs)
                     / max(over, 1e-300))

    errors = {name: error(name, program["slices"][name], want)
              for name, want in reference["slices"].items()}
    numbers["grad_rel_by_slice"] = errors
    # not held to a limit: the gate's two relative to the sum itself
    numbers["gate_rel_to_sum"] = {
        name: relative_error(program["slices"][name],
                             reference["slices"][name])
        for name in reference["gate_terms"]}
    for what in ("layer", "dense", "gate"):
        group = {n: e for n, e in errors.items() if family(n) == what}
        at = max(group, key=group.get)
        hold(f"grad_{what}_rel", group[at],
             f"step-0 gradients of {len(group)} {what} slices against the "
             "reference's, largest "
             + ("error over the size of the sum's terms" if what == "gate"
                else "relative error") + f" (at {at})")
    hold("held_stated_rel",
         max(worst(program["held_losses"], stated["held_losses"], rel),
             worst([x for b in program["held_exit_losses"] for x in b],
                   [x for b in stated["held_exit_losses"] for x in b],
                   rel)),
         f"persisted model's loss and exit losses on each of "
         f"{len(stated['held_losses'])} held batches: the step program "
         f"({program['held_losses']}; {program['held_exit_losses']}) "
         f"against the reference at the stated precision "
         f"({stated['held_losses']}; {stated['held_exit_losses']}), "
         "largest relative difference")
    got, want = program["exit_probe"], reference["exit_probe"]
    hold("exit_probe_abs",
         float(np.abs(got - want).max()) if got.shape == want.shape
         else float("inf"),
         f"exit masses of {want.shape[-1]} seeded states under the "
         "persisted gate against the reference's, largest absolute "
         "difference")
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
