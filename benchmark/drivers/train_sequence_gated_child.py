"""The child that holds the chip for a train_sequence_gated cell.

    python -m benchmark.drivers.train_sequence_gated_child <spec.json>

As benchmark/drivers/train_sequence_ssm_child.py, for a block
specification of gated grouped-query layers: histories of
history_events + 1 ids, and after the window the check of
benchmark/harness/check_gated.py on what the last job logged and
persisted. A checkout whose block stack has no head counts by layer
kind and no gate ends here at once, with exit code 1 and a line that
says so.

What is this family's own is `GATED`, a `Family`: the fields a checkout
needs and what to say without them, the rehearsal's overlay, the check's
module and the family's probe of the program. `main(spec_path, family)`
is the rest, the same for every block-stack family: a later family's
child is a `Family` and a call of this `main` (ROADMAP B2 folds the four
older children onto it; a PR may not edit them beside a new cell).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import logging
import os
import sys
import time

from benchmark.drivers.train_child import JobLog, device_report, memory_peak
from benchmark.drivers.train_sequence_child import WARM_STEPS


@dataclasses.dataclass(frozen=True)
class Family:
    """What a block-stack family's child has of its own."""
    fields: frozenset        # of BlockSpec: a checkout without them ends
    refusal: str             # ... with exit code 1 and this line
    overlay: str             # the rehearsal's, under benchmark/tests/rehearse
    check: str               # the module of benchmark.harness that decides
    # (cfg, traffic, seed, model params) -> (the probes the reference's
    # side takes, the program's readings of the family's own probe)
    probe: object


def window_kernel(cfg: dict, traffic: dict, seed: int, params):
    """The stack's window attention, as its sliding layers call it, on
    the band's edge: -> ({"edge": the probe}, {"band_edge": the rows of
    dk and dv there})."""
    import jax

    from pio_tpu.models import seq_blocks
    from pio_tpu.ops.attention import banded_flash_attention

    from benchmark.harness import check_gated as check

    probe = check.edge_probe(cfg, traffic["history_events"],
                             seq_blocks.ATTN_BLOCK, seed)
    q, k, v, ct = (jax.numpy.asarray(probe[name], seq_blocks.COMPUTE)
                   for name in ("q", "k", "v", "ct"))
    _, dk, dv = jax.jit(lambda q, k, v, ct: jax.vjp(
        lambda q, k, v: banded_flash_attention(
            q, k, v, probe["window"], None, seq_blocks.ATTN_BLOCK,
            seq_blocks.ATTN_BLOCK), q, k, v)[1](ct))(q, k, v, ct)
    return {"edge": probe}, {"band_edge": check.band_edge_slice(dk, dv, probe)}


GATED = Family(
    fields=frozenset({"heads_by_kind", "attn_gate"}),
    refusal="has no query-head counts or rotating width by layer kind "
            "(num_attention_heads_per_layer, rope_parameters[kind]."
            "partial_rotary_factor), no gate on attention's output "
            "(gating) and no head norms",
    overlay="gated-tiny.json", check="check_gated", probe=window_kernel)


def main(spec_path: str, family: Family = GATED) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    try:
        from pio_tpu.models import seq_blocks
        fields = {f.name for f in dataclasses.fields(seq_blocks.BlockSpec)}
    except ImportError:
        fields = set()
    if not family.fields <= fields:
        print("this checkout's block stack (pio_tpu/models/seq_blocks.py "
              f"BlockSpec) {family.refusal}: it cannot train the "
              "configuration", file=sys.stderr)
        return 1
    if spec["rehearse"] and spec["config"]["hidden_size"] > 256:
        print("a CPU rehearsal of this cell needs an overlay that shrinks "
              "the configuration (benchmark/tests/rehearse/"
              f"{family.overlay}): the published widths do not run here",
              file=sys.stderr)
        return 1
    import jax
    import numpy as np

    device = device_report(jax)
    want = "cpu" if spec["rehearse"] else "tpu"
    if device["platform"] != want or device["count"] < spec["chips"]:
        with open(spec["out"], "w") as f:
            json.dump({"device": device}, f)
        return 0          # the parent says why there is no result

    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.data.storage import get_storage
    from pio_tpu.ops.moe import route_top_k
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import load_models, run_train

    from benchmark import engines_sequence as es
    check = importlib.import_module("benchmark.harness." + family.check)
    from benchmark.harness import program as intake

    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    log = logging.getLogger("benchmark")
    n_items, length = config["vocab_size"] - 1, traffic["history_events"] + 1
    seqs = es.make_histories(traffic["histories"], length, n_items,
                             traffic["zipf_exponent"], seed)
    log.info("made %d histories of %d", *seqs.shape)
    storage = get_storage()
    engine = es.seeded_engine(seqs, n_items)
    alg = es.algorithm_params(config, traffic, seed)
    ep = EngineParams(datasource=("", None), algorithms=[("sasrec", alg)])
    ctx = create_workflow_context(storage, use_mesh=False)
    job_log, span_log = JobLog(), intake.SpanLog()
    logging.getLogger("pio_tpu.workflow").addHandler(job_log)
    logging.getLogger("pio_tpu.workflow").addHandler(span_log)

    def job(params: EngineParams = ep) -> dict:
        job_log.job, span_log.rows = {}, []
        t_a = time.monotonic()
        instance = run_train(engine, params, storage, engine_id="bench",
                             ctx=ctx)
        t_b = time.monotonic()
        return dict(job_log.job, spans=span_log.rows,
                    counters=span_log.labels("seq.wait"), instance=instance,
                    start=t_a, end=t_b, wall_s=t_b - t_a)

    warm = job(EngineParams(datasource=("", None), algorithms=[
        ("sasrec", dict(alg, steps=WARM_STEPS))]))
    log.info("warm job of %d steps %.2fs", WARM_STEPS, warm["wall_s"])
    tracing = spec["trace"]
    trace_dir = os.path.join(os.path.dirname(spec["out"]), "trace")
    if tracing:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # ours are TraceAnnotations
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    jobs = []
    t_open = time.monotonic()
    with jax.profiler.TraceAnnotation("bench:window"):
        while True:
            jobs.append(job())
            if time.monotonic() - t_open >= spec["seconds"]:
                break
    t_close = time.monotonic()
    if tracing:
        jax.profiler.stop_trace()
    peak, memory = memory_peak(jax)
    log.info("window closed: %d jobs", len(jobs))

    # -- after the window: the check, on what the last job logged and
    # persisted
    t_c = time.monotonic()
    cfg = es.block_spec_of(config)
    bspec = seq_blocks.BlockSpec.parse(cfg)
    order = seq_blocks.epoch_order(len(seqs), traffic["steps"],
                                   traffic["batch_histories"], seed)
    tokens0 = jax.numpy.asarray(seqs[order[0]])
    optimizer, step = seq_blocks.make_train_step(
        bspec, traffic["learning_rate"])

    def first_step(params, batch):
        """The jobs' own step program (compiled once, in set-up) on
        `batch` from a zero optimizer state: -> (the loss before the
        update, Adam's first moment after it: (1 - b1) times the
        gradient the step took, the step's token counts (routers,
        histories, routed), the biases it left)."""
        after, state, loss, aux = step(params, optimizer.init(params),
                                       batch)
        return (float(loss), state[0].mu, np.asarray(aux["counts_all"]),
                check.router_biases(cfg, after))

    def program_routing(experts, probe: dict):
        """The stack's routing, as its layers call it, on the probe's
        logits under each bias: -> (routers, tokens, routed) weights, 0
        where an expert is not chosen."""
        @jax.jit
        def dense(logits, bias):
            ids, w = route_top_k(logits, experts.top_k, experts.norm_topk,
                                 experts.score, bias, experts.scale)
            rows = jax.numpy.arange(logits.shape[0])[:, None]
            return jax.numpy.zeros_like(logits).at[rows, ids].set(w)

        return np.stack([np.asarray(dense(probe["logits"], bias))
                         for bias in probe["bias"]])

    loss0, moment, counts0, bias1 = first_step(
        seq_blocks.init_params(bspec, seed), tokens0)
    grads = jax.tree_util.tree_map(
        lambda mu: mu / (1.0 - seq_blocks.ADAM_B1), moment)
    del moment
    last = jobs[-1]["counters"]
    program = {"loss0": loss0, "logged_loss": float(last["loss_first"]),
               "slices": check.gradient_slices(cfg, grads),
               "counts0": counts0, "bias1": bias1,
               "steps": traffic["steps"]}
    del grads
    model = load_models(storage, engine, ep, jobs[-1]["instance"], ctx)[0]
    program["shape_faults"] = check.shape_faults(cfg, model.params)
    held = jax.numpy.asarray(es.make_histories(
        check.HELD_BATCHES * traffic["batch_histories"], length,
        n_items, traffic["zipf_exponent"], seed, stream=1).reshape(
            check.HELD_BATCHES, traffic["batch_histories"], length))
    compared, numbers, correct = [], {}, False
    if program["shape_faults"]:
        compared.append("persisted model: FAILED "
                        + "; ".join(program["shape_faults"][:4]))
    else:
        program["bias_model"] = check.router_biases(cfg, model.params)
        own, readings = family.probe(cfg, traffic, seed, model.params)
        probes = {"router": check.router_probe(cfg, seed, model.params),
                  **own}
        program["router_probe"] = program_routing(bspec.experts,
                                                  probes["router"])
        program.update(readings)
        # the step donates its parameters: a copy a batch
        program["held_losses"] = [
            first_step(jax.device_put(model.params), batch)[0]
            for batch in held]
        log.info("program's side of the check %.1fs",
                 time.monotonic() - t_c)
        reference = check.Reference(cfg)
        sides = (lambda: seq_blocks.init_params(bspec, seed), tokens0,
                 lambda: jax.device_put(model.params), held,
                 program["slices"]["expert"], probes)
        sound = reference.numbers(*sides)
        limits = config["check"]["limits"]
        verdict = check.check(cfg, limits, program, sound)
        compared, correct = verdict["compared"], verdict["correct"]
        numbers = dict(verdict["numbers"])
        log.info("the check %.1fs", time.monotonic() - t_c)
        if spec.get("explore"):
            # `explore` True: every fault; a list: the faults it names.
            # But for `check.HELD_FAULTS`, a faulty reference's held
            # losses are the sound one's: the others are read at step 0
            # or by a probe, and the held losses cost four passes more
            names = (check.FAULTS if spec["explore"] is True
                     else spec["explore"])
            numbers["explore"] = {
                name: check.check(cfg, limits, wrong(program, cfg), sound)
                for name, wrong in check.PROGRAM_FAULTS.items()}
            for name in names:
                t_f = time.monotonic()
                whole = name in check.HELD_FAULTS
                faulty = reference.numbers(
                    *sides[:3], held if whole else [], *sides[4:],
                    check.FAULTS[name])
                if not whole:
                    faulty.update(held_losses=sound["held_losses"],
                                  held_stated=sound["held_stated"])
                numbers["explore"][name] = check.check(
                    cfg, limits, program, faulty)
                log.info("explore %s (%.1fs): %s", name,
                         time.monotonic() - t_f,
                         numbers["explore"][name]["compared"])
    numbers["check_seconds"] = time.monotonic() - t_c
    compiles = sum(j.get("programs", 0) for j in jobs)
    compared.append(f"programs compiled inside the window {compiles} <= 0: "
                    f"{'ok' if compiles == 0 else 'FAILED'}")
    dropped = sum(int(j["counters"].get("dropped_tokens", -1)) for j in jobs)
    compared.append(f"routed tokens dropped inside the window {dropped} "
                    f"<= 0: {'ok' if dropped == 0 else 'FAILED'}")
    out = {
        "device": dict(device, memory_peak_bytes=peak),
        "memory_stats": memory,
        "warm_job": warm, "jobs": jobs,
        "window": {"open": t_open, "close": t_close},
        "correct": bool(correct and compiles == 0 and dropped == 0),
        "numbers": numbers, "compared": compared,
    }
    log.info("checked: %s", compared)
    log.info("check numbers: %s", json.dumps(
        {k: v for k, v in numbers.items() if k != "explore"}))
    if tracing:
        out["trace"] = intake.reduce_trace(trace_dir, len(jobs), log)
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
