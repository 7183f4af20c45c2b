"""The operation and byte counts against hand counts."""
from chipbench import work

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_fold_chunk_hand_count():
    # m=2 tasks, n=3 rows, p=4 features: X'X is 3*16 multiply-adds per
    # task (192 FLOP for both), X'y 3*4 (48 FLOP); bytes: X (24 floats)
    # and y (6) read, Sigma (32) and c (8) read and written
    flops, bytes_ = work.fold_chunk(2, 3, 4)
    assert flops == 192 + 48
    assert bytes_ == 4 * (24 + 6) + 2 * 4 * (32 + 8)


def test_fold_at_the_tenants_shape():
    flops, bytes_ = work.fold_chunk(128, 512, 1024)
    assert flops == 137_438_953_472 + 134_217_728
    assert bytes_ == 268_697_600 + 1_074_790_400


def test_debias_step_is_five_stacks_and_2mp3():
    flops, bytes_ = work.debias_step(4, 4096)
    assert flops == 2 * 4 * 4096 ** 3          # 550 GFLOP
    assert bytes_ == 5 * 4 * 4 * 4096 * 4096


def test_refit_phases_scale_with_iterations():
    one = work.refit_phases(8, 16, refits=1, lasso_iters=10, debias_iters=20)
    two = work.refit_phases(8, 16, refits=2, lasso_iters=20, debias_iters=40)
    assert [(2 * f, 2 * b) for f, b in one] == two
    power, lasso, debias, formula = one
    assert power == (64 * 2 * 8 * 256, 64 * 4 * 8 * 256)
    assert lasso == (10 * 2 * 8 * 256, 10 * 4 * 8 * 256)
    assert debias == (20 * 2 * 8 * 16 ** 3, 20 * 5 * 4 * 8 * 256)
    assert formula == (2 * 2 * 8 * 256, 2 * 4 * 8 * 256)


def test_least_time_takes_the_binding_bound():
    # 197e12 flops and 819e9 bytes each take one second
    assert work.least_time([(197e12, 1.0)], PEAKS) == 1.0
    assert work.least_time([(1.0, 819e9)], PEAKS) == 1.0
    assert work.least_time([(197e12, 0.0), (0.0, 2 * 819e9)], PEAKS) == 3.0
