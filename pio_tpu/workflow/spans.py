"""Span scheduling for on-device training scans.

Both neural trainers (models/twotower.py, models/sequence.py) replace
their per-step host loops with `lax.scan` over SPANS of steps: one
compiled program per span instead of one dispatch + batch transfer per
step (the per-step loop is dispatch-bound).
The span boundaries have to respect two constraints:

 * bounded staging — a span's batch tensors are materialized host-side
   and transferred once, so spans are capped;
 * checkpoint cadence — orbax only accepts saves at steps that are
   multiples of save_every, and resume correctness requires hitting
   exactly the steps the original per-step loop hit (0, k, 2k, ...), so
   a span must END right after a save-eligible step.

This module owns that boundary math so the trainers share one tested
implementation.
"""

from __future__ import annotations

from typing import Iterator


def span_bounds(start: int, steps: int, save_every: int | None,
                cap: int = 512) -> Iterator[tuple[int, int, bool]]:
    """Yield (lo, hi, save_after) spans covering [start, steps).

    `save_after` is True when step hi-1 is save-eligible
    ((hi-1) % save_every == 0) — the caller then invokes
    checkpoint.maybe_save(hi-1, ...). With save_every=None no span ever
    asks for a save."""
    s = start
    while s < steps:
        e = min(steps, s + cap)
        if save_every is not None:
            m = s if s % save_every == 0 else (
                s // save_every + 1) * save_every
            if m < e:
                e = m + 1
        yield s, e, (
            save_every is not None and (e - 1) % save_every == 0
        )
        s = e


def step_chaos_active() -> bool:
    """True when a `train.step` chaos spec is live: trainers then degrade
    their spans to single steps (cap=1) so a `train.step.<n>` fault fires
    at EXACTLY step n — deterministic kill-at-step for the resume tests.
    Zero-cost when chaos is off (one module-global read)."""
    from pio_tpu.resilience import chaos

    return chaos.watches("train.step")


def after_span(
    hi: int,
    total_steps: int,
    params,
    opt_state,
    *,
    checkpoint,
    lifecycle,
    save_after: bool,
    step_chaos: bool,
) -> None:
    """Shared span-boundary bookkeeping for the iterative trainers
    (models/twotower.py, models/sequence.py) — one implementation so the
    chaos/save/preemption ordering cannot drift between them:

      1. `train.step.<hi-1>` chaos point (the kill-at-step hook);
      2. cadence save (only save-eligible steps reach maybe_save — it
         device_gets the full state, which a declined save would waste);
      3. preemption: force-save the current step when it is off-cadence,
         then raise TrainingPreempted (via lifecycle.check_preemption).
         Multi-host, the flag is OR-reduced across processes FIRST — a
         SIGTERM often lands on one host only, and a lone force-saver
         would strand its peers at the save barrier;
      4. heartbeat.
    """
    if step_chaos:
        from pio_tpu.resilience import chaos

        chaos.maybe_inject(f"train.step.{hi - 1}")
    if save_after:
        checkpoint.maybe_save(hi - 1, params, opt_state)
    if lifecycle is not None:
        from pio_tpu.parallel.distributed import any_process

        if any_process(lifecycle.preempted()):
            if checkpoint is not None and not save_after:
                checkpoint.save(hi - 1, params, opt_state)
            lifecycle.check_preemption(hi - 1, force=True)  # raises
        lifecycle.heartbeat(hi - 1, total_steps)
