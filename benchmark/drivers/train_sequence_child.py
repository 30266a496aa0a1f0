"""The child that holds the chip for a train_sequence cell.

    python -m benchmark.drivers.train_sequence_child <spec.json>

Set-up: the device report, the seeded histories, one `run_train` of
WARM_STEPS steps (which compiles, or loads from the persistent cache,
every program a job runs: none of them holds the step count).
Window: `run_train` back to back until the time is up; the job in flight
then is finished and counted whole. After the window: the check
(benchmark/harness/check_sequence.py) on what the last job logged and
persisted. With `trace`, the window runs under the jax profiler and the
trace is reduced here, where jax is: busy and idle as for the ALS cells
(benchmark/harness/trace.py), and device seconds by the program's
`seq.*` scopes (benchmark/harness/program.py). A job's `counters` are
the labels of its `seq.wait` span.
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


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    from pio_tpu.models.sequence import SequenceParams

    if "block_spec" not in {f.name for f in
                            dataclasses.fields(SequenceParams)}:
        print("this checkout's sequence engine takes no block "
              "specification (SequenceParams.block_spec): it cannot train "
              "the configuration", file=sys.stderr)
        return 1
    if spec["rehearse"] and spec["config"]["hidden_size"] > 256:
        print("a CPU rehearsal of this cell needs an overlay that shrinks "
              "the configuration (benchmark/tests/rehearse/"
              "sequence-tiny.json): the published widths do not run here",
              file=sys.stderr)
        return 1
    import jax

    device = device_report(jax)
    want = "cpu" if spec["rehearse"] else "tpu"
    if device["platform"] != want or device["count"] < spec["chips"]:
        with open(spec["out"], "w") as f:
            json.dump({"device": device}, f)
        return 0          # the parent says why there is no result

    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.data.storage import get_storage
    from pio_tpu.models import seq_blocks
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import load_models, run_train

    from benchmark import engines_sequence as es
    from benchmark.harness import check_sequence, program as intake

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
        gradient the step took)."""
        _, state, loss, _ = step(params, optimizer.init(params), batch)
        return float(loss), state[0].mu

    def window_kernel(probe: dict):
        """The stack's window attention, as its layers call it, on the
        probe: -> the band-edge rows of dk and dv."""
        from pio_tpu.ops.attention import banded_flash_attention

        q, k, v, ct = (jax.numpy.asarray(probe[name], seq_blocks.COMPUTE)
                       for name in ("q", "k", "v", "ct"))
        _, dk, dv = jax.jit(lambda q, k, v, ct: jax.vjp(
            lambda q, k, v: banded_flash_attention(
                q, k, v, probe["window"], None, seq_blocks.ATTN_BLOCK,
                seq_blocks.ATTN_BLOCK), q, k, v)[1](ct))(q, k, v, ct)
        return check_sequence.band_edge_slice(dk, dv, probe)

    loss0, moment = first_step(seq_blocks.init_params(bspec, seed), tokens0)
    grads = jax.tree_util.tree_map(
        lambda mu: mu / (1.0 - seq_blocks.ADAM_B1), moment)
    del moment
    program = {"loss0": loss0,
               "loss_logged": float(jobs[-1]["counters"]["loss_first"]),
               "slices": check_sequence.gradient_slices(cfg, grads)}
    del grads
    model = load_models(storage, engine, ep, jobs[-1]["instance"], ctx)[0]
    program["shape_faults"] = check_sequence.shape_faults(cfg, model.params)
    held = jax.numpy.asarray(es.make_histories(
        check_sequence.HELD_BATCHES * traffic["batch_histories"], length,
        n_items, traffic["zipf_exponent"], seed, stream=1).reshape(
            check_sequence.HELD_BATCHES, traffic["batch_histories"], length))
    probe = check_sequence.band_edge_probe(
        cfg, traffic["history_events"], seq_blocks.ATTN_BLOCK, seed)
    program["band_edge"] = window_kernel(probe)
    compared, numbers, correct = [], {}, False
    if program["shape_faults"]:
        compared.append("persisted model: FAILED "
                        + "; ".join(program["shape_faults"][:4]))
    else:
        # the step donates its parameters: a copy a batch
        program["held_losses"] = [
            first_step(jax.device_put(model.params), batch)[0]
            for batch in held]
        model_params = jax.device_put(model.params)
        params0 = seq_blocks.init_params(bspec, seed)
        log.info("program's side of the check %.1fs",
                 time.monotonic() - t_c)
        expert = program["slices"]["expert"]
        reference = check_sequence.reference_numbers(
            cfg, params0, tokens0, model_params, held, expert, probe)
        verdict = check_sequence.check(cfg, config["check"]["limits"],
                                       program, reference)
        compared, correct = verdict["compared"], verdict["correct"]
        numbers = dict(verdict["numbers"])
        if spec.get("explore"):
            numbers["explore"] = {}
            for name, faults in check_sequence.FAULTS.items():
                faulty = check_sequence.reference_numbers(
                    cfg, params0, tokens0, model_params, held, expert,
                    probe, faults)
                numbers["explore"][name] = check_sequence.check(
                    cfg, config["check"]["limits"], program, faulty)
                log.info("explore %s: %s", name,
                         numbers["explore"][name]["compared"])
        del model_params
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
