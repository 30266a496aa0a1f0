"""The byte and FLOP functions against shapes worked by hand."""

from benchmark.harness import roofline
from benchmark.readers import als_roofline


def test_half_sweep_by_hand():
    # 10 rows, 100 ratings, rank 4, 2 CG iterations
    # ratings 100*8 = 800; gather 100*4*2 = 800; A = 10*4*4*4 = 640,
    # written once and read 3 times = 2560; vectors 3*10*4*4 = 480
    assert roofline.half_sweep_bytes(10, 100, 4, 2) == 800 + 800 + 2560 + 480
    # exact solve: A written once, read once
    assert roofline.half_sweep_bytes(10, 100, 4, 0) == 800 + 800 + 1280 + 480
    # build 2*100*16 + 2*100*4 + Gram of 7 other rows 2*7*16 = 4224;
    # CG: 3 matvecs of 2*16 flops for 10 rows = 960
    assert roofline.half_sweep_flops(10, 7, 100, 4, 2) == 4224 + 960


def test_schedule_is_the_engines():
    assert roofline.cg_schedule(10, 16, 2, 6) == [16, 16] + [6] * 8
    assert roofline.cg_schedule(1, 16, 2, 6) == [16]
    assert roofline.cg_schedule(3, 16, 2, -1) == [16, 16, 16]


def test_job_is_the_sum_of_its_half_sweeps():
    job = roofline.job_least(10, 7, 100, 4, [2, 1], [0, 0])
    want = (roofline.half_sweep_bytes(10, 100, 4, 2)
            + roofline.half_sweep_bytes(10, 100, 4, 1)
            + 2 * roofline.half_sweep_bytes(7, 100, 4, 0))
    assert job["bytes"] == want


def test_roofline_share_at_ml20m():
    """At the ML-20M shape the least bytes of a job are about 0.32 TB:
    0.39 s of a v5e's 819 GB/s. A job whose device is busy 5.95 s reads
    6.6 %, and memory is the bound (operations need 17 ms)."""
    spec = {"cg_full_iters": 16, "cg_full_sweeps": 2, "cg_warm_iters": 6,
            "exact_solve_up_to_rows": 8192}
    evidence = {
        "trace": {"busy_s": 11.9}, "jobs": [{}, {}], "chips": 1,
        "device_kind": "TPU v5 lite", "rehearse": False,
        "config": {"data": {"n_users": 138493, "n_items": 26744,
                            "nnz": 20000263},
                   "algorithm": {"rank": 64, "num_iterations": 10}}}
    share = als_roofline.read(spec, evidence)
    assert 6.0 < share < 7.2
    assert als_roofline.read(spec, dict(evidence, rehearse=True)) is None
