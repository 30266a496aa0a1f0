#!/usr/bin/env python3
"""Record the two small chip traces that tests/test_profile_reduce.py
reduces (run on the chip, four chips for both):

    chiprun --chips 4 -- python3 tests/record_train_trace.py chiprun_out

A warm `run_train`, then two more under the jax profiler: once on one
chip (`tiny_one_chip.xplane.pb`) and, where the machine has more, once
through the sharded trainer over all of them
(`tiny_sharded.xplane.pb`). Rank 64, so the Pallas flush kernel is the
one the cells run; a few thousand ratings, so the files stay small.
Copy them to tests/data/.
"""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from pio_tpu.obs import profile  # noqa: E402
from pio_tpu.workflow.context import create_workflow_context  # noqa: E402
from pio_tpu.workflow.train import run_train  # noqa: E402
from tests._tiny_train import memory_storage, tiny_engine, tiny_params  # noqa: E402


def record(out: str, use_mesh: bool) -> None:
    storage = memory_storage()
    engine = tiny_engine(n_users=2000, n_items=1500, nnz=40000)
    ep = tiny_params(rank=64, sweeps=3, cg_iters=4, cg_warm_iters=2,
                     cg_warm_sweeps=1)
    ctx = create_workflow_context(storage, use_mesh=use_mesh)

    def job():
        run_train(engine, ep, storage, engine_id="tiny", ctx=ctx)

    job()                               # compiles
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are TraceAnnotations
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir, profiler_options=options)
    job()
    job()
    jax.profiler.stop_trace()
    shutil.copy(profile.find_xplane(logdir), out)
    shutil.rmtree(logdir)
    print(out, os.path.getsize(out), "bytes")
    if jax.devices()[0].platform == "tpu":
        print(profile.render(profile.reduce(profile.read_profile(out))))


def main(outdir: str) -> None:
    os.makedirs(outdir, exist_ok=True)
    print(jax.devices())
    record(os.path.join(outdir, "tiny_one_chip.xplane.pb"), use_mesh=False)
    if len(jax.devices()) > 1:
        record(os.path.join(outdir, "tiny_sharded.xplane.pb"), use_mesh=True)


if __name__ == "__main__":
    main(sys.argv[1])
