"""The child that holds the chip for a train_sequence_loop cell.

    python -m benchmark.drivers.train_sequence_loop_child <spec.json>

Set-up: the device report, the seeded histories, one `run_train` of
WARM_STEPS steps (which compiles, or loads from the persistent cache,
every program a job runs: none of them holds the step count). Window:
`run_train` back to back until the time is up; the job in flight then is
finished and counted whole. After the window: the check, on what the
last job logged and persisted. With `trace`, the window runs under the
jax profiler and the trace is reduced here, where jax is. A job's
`counters` are the labels of its `seq.wait` span.

A checkout whose block stack has no loop ends here at once, with exit
code 1 and a line that says so. What is this family's own stands at the
top (EXTRA_IDS, OVERLAY, the check module and its reference, the step's
counters and the probe in `main`): ROADMAP B2 folds the two older
children (train_sequence_child.py, train_sequence_mtp_child.py) in.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import sys
import time

from benchmark.drivers.train_child import JobLog, device_report, memory_peak

WARM_STEPS = 2
EXTRA_IDS = 1               # ids of a history beyond its trained positions
OVERLAY = "loop-tiny.json"  # the rehearsal's, under benchmark/tests/rehearse


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    try:
        from pio_tpu.models import seq_blocks
        fields = {f.name for f in dataclasses.fields(seq_blocks.BlockSpec)}
    except ImportError:
        fields = set()
    if "loop_steps" not in fields:
        print("this checkout's block stack (pio_tpu/models/seq_blocks.py "
              "BlockSpec) has no looped stack (total_ut_steps: the layers "
              "several times with one set of weights, an exit gate): it "
              "cannot train the configuration", file=sys.stderr)
        return 1
    if spec["rehearse"] and spec["config"]["hidden_size"] > 256:
        print("a CPU rehearsal of this cell needs an overlay that shrinks "
              "the configuration (benchmark/tests/rehearse/"
              f"{OVERLAY}): the published widths do not run here",
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
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import load_models, run_train

    from benchmark import engines_sequence as es
    from benchmark.harness import check_loop as check
    from benchmark.harness import program as intake

    config, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    log = logging.getLogger("benchmark")
    n_items = config["vocab_size"] - 1
    length = traffic["history_events"] + EXTRA_IDS
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
        update, the step's counters, Adam's first moment after it:
        (1 - b1) times the gradient the step took)."""
        _, state, loss, aux = step(params, optimizer.init(params), batch)
        return float(loss), aux, state[0].mu

    loss0, aux0, moment = first_step(
        seq_blocks.init_params(bspec, seed), tokens0)
    grads = jax.tree_util.tree_map(
        lambda mu: mu / (1.0 - seq_blocks.ADAM_B1), moment)
    del moment
    program = {"loss0": loss0,
               "slices": check.gradient_slices(cfg, grads),
               "job_faults": check.job_faults(
                   cfg, [j["counters"] for j in jobs]),
               **check.logged_numbers(jobs[-1]["counters"]),
               "exit_losses0": [float(x) for x in
                                np.asarray(aux0["exit_losses"])],
               "exit_mass0": [float(x) for x in
                              np.asarray(aux0["exit_mass"])]}
    del grads
    model = load_models(storage, engine, ep, jobs[-1]["instance"], ctx)[0]
    program["shape_faults"] = check.shape_faults(cfg, model.params)
    held = jax.numpy.asarray(es.make_histories(
        check.HELD_BATCHES * traffic["batch_histories"], length, n_items,
        traffic["zipf_exponent"], seed, stream=1).reshape(
            check.HELD_BATCHES, traffic["batch_histories"], length))
    compared, numbers, correct = [], {}, False
    if program["shape_faults"]:
        compared.append("persisted model: FAILED "
                        + "; ".join(program["shape_faults"][:4]))
    else:
        # the gate's probe: the two functions the program's loss calls
        probe = check.exit_probe(cfg, seed)
        program["exit_probe"] = np.asarray(jax.jit(
            lambda params, y: seq_blocks.exit_probabilities(
                seq_blocks.exit_gate_logits(params, y)))(
                    model.params, probe))
        # the step donates its parameters: a copy a batch
        on_held = [first_step(jax.device_put(model.params), batch)[:2]
                   for batch in held]
        program["held_losses"] = [loss for loss, _ in on_held]
        program["held_exit_losses"] = [
            [float(x) for x in np.asarray(aux["exit_losses"])]
            for _, aux in on_held]
        log.info("program's side of the check %.1fs",
                 time.monotonic() - t_c)
        def persisted():
            return jax.device_put(model.params)

        sides = (lambda: seq_blocks.init_params(bspec, seed), tokens0,
                 persisted, held, probe)
        reference = check.reference_numbers(cfg, *sides)
        stated = check.stated_numbers(cfg, persisted, held)
        limits = config["check"]["limits"]
        verdict = check.check(cfg, limits, program, reference, stated)
        compared, correct = verdict["compared"], verdict["correct"]
        numbers = dict(verdict["numbers"])
        if spec.get("explore"):
            # `explore` True: every fault; a list: the faults it names
            names = (check.FAULTS if spec["explore"] is True
                     else spec["explore"])
            numbers["explore"] = {}
            for name in names:
                fault = check.FAULTS[name]
                numbers["explore"][name] = check.check(
                    cfg, limits, program,
                    check.reference_numbers(cfg, *sides, fault),
                    check.stated_numbers(cfg, persisted, held, fault))
                log.info("explore %s: %s", name,
                         numbers["explore"][name]["compared"])
    numbers["check_seconds"] = time.monotonic() - t_c
    compiles = sum(j.get("programs", 0) for j in jobs)
    compared.append(f"programs compiled inside the window {compiles} <= 0: "
                    f"{'ok' if compiles == 0 else 'FAILED'}")
    out = {
        "device": dict(device, memory_peak_bytes=peak),
        "memory_stats": memory,
        "warm_job": warm, "jobs": jobs,
        "window": {"open": t_open, "close": t_close},
        "correct": bool(correct and compiles == 0),
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
