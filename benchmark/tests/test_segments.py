from benchmark.runners import train


def test_whole_window_rate_counts_a_stall_and_the_segment_median_does_not():
    steady = [1.0] * 40
    tok_s, seg, stall, med = train.read_segments(steady, 40.0, 4096, 12)
    assert tok_s == seg == 4096 * 12 and stall == 0.0 and med == 1.0
    # 130 ms lost once, as in PR 23's refused runs
    slow = [1.0] * 39 + [1.13]
    tok_s2, seg2, stall2, _ = train.read_segments(slow, 40.13, 4096, 12)
    assert tok_s2 == 4096 * 12 * 40 / 40.13     # all the work over all the time
    assert seg2 == seg                          # the per-layer reading stays
    assert abs(stall2 - 0.13 / 40.13) < 1e-12   # and the loss is reported


def test_parent_side_of_the_runners_stays_off_jax():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.run, benchmark.measure, benchmark.sweep, "
            "benchmark.runners.train, benchmark.runners.serve; "
            "assert 'jax' not in sys.modules")
    from conftest import CHECKOUT
    subprocess.run([sys.executable, "-c", code % CHECKOUT], check=True)
