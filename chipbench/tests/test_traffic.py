"""The predict schedule and the backlog count, by hand."""
import numpy as np

from chipbench import harness
from chipbench.traffic import stream


def _due(seed, rate=32):
    run = stream.StreamRun({"m": 1, "p": 1, "chunk_n": 1},
                           {"predict_rate_per_s": rate}, seed, None)
    return run._arrivals()


def test_every_seed_offers_the_same_gaps_in_every_block():
    a, b = _due(1), _due(2 ** 31 + 5)
    assert not np.array_equal(a, b)
    gaps_a, gaps_b = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    for k in range(0, 32 * 20, 32):
        assert np.allclose(np.sort(gaps_a[k:k + 32]),
                           np.sort(gaps_b[k:k + 32]))
    assert a[-1] >= stream.GAP_HORIZON_S
    # a block of the rate's gaps spans about a second
    assert 0.9 < a[31] < 1.0 and np.isclose(a[31], b[31])


def test_waited_twice_counts_requests_past_the_next_refit():
    # publishes at 6.49 s and 12.99 s; requests due at 0.1 s and 5.6 s
    # answered after the first, 6.0 s after the second, 7.0 s due in
    # the second cycle and answered at its end
    w = {"timeline": [(0.0, 0.136, False), (0.136, 6.49, True),
                      (6.49, 6.63, False), (6.63, 12.99, True)],
         "due_ms": np.array([100.0, 5600.0, 6000.0, 7000.0]),
         "latency_ms": np.array([6400.0, 900.0, 6990.0, 5990.0])}
    assert harness.waited_twice(w) == 1
