"""Metric arithmetic: time to gap, tails, rates, op and byte counts, the
peaks table, and the generators' determinism."""
import math

import numpy as np
import pytest

from benchkit import device, flops, stats


def test_crossing_is_interpolated_in_log_gap():
    # gap 1e-2 at t=2 s, 1e-4 at t=6 s: 1e-3 is half way in log-gap
    t = stats.crossing_time([2.0, 6.0], [1e-2, 1e-4], 1e-3)
    assert t == pytest.approx(4.0)
    # a row exactly at the target crosses at its own stamp
    assert stats.crossing_time([1.0, 2.0], [0.5, 0.1], 0.1) == \
        pytest.approx(2.0)
    # the first row already below: its stamp
    assert stats.crossing_time([3.0, 5.0], [1e-5, 1e-6], 1e-3) == 3.0
    # never reached
    assert stats.crossing_time([1.0, 2.0], [1.0, 0.5], 0.1) is None
    # a non-monotone start: the first crossing counts
    t = stats.crossing_time([1.0, 2.0, 3.0, 4.0], [1.0, 1e-2, 0.5, 1e-3],
                            1e-1)
    assert t == pytest.approx(1.0 + 0.5)
    # a gap that is not positive: linear in the gap
    t = stats.crossing_time([0.0, 1.0], [0.2, -0.2], 0.1)
    assert t == pytest.approx(0.25)


def test_p95_counts_requests_unserved_at_the_close():
    due = np.arange(100, dtype=float) * 0.01          # 0 .. 0.99 s
    done = due + 0.002
    done[90:] = np.nan                                 # 10 never answered
    lat = stats.open_loop_latencies(due, done, end=1.0)
    assert lat[:90] == pytest.approx(0.002)
    assert lat[90:] == pytest.approx(1.0 - due[90:])
    assert stats.p95(lat) == pytest.approx(np.percentile(lat, 95))
    assert stats.p95(lat) > 0.002
    # an answer after the close counts as unanswered
    done2 = done.copy()
    done2[0] = 2.0
    assert stats.open_loop_latencies(due, done2, 1.0)[0] == 1.0


def test_labels_per_s_counts_real_positions_of_answered_requests():
    lengths = np.asarray([3, 5, 8, 14])
    done = np.asarray([0.1, np.nan, 0.5, 1.5])
    assert stats.labels_per_s(lengths, done, end=1.0, seconds=2.0) == \
        pytest.approx((3 + 8) / 2.0)


def test_plane_scores_counts_by_hand():
    # cap=64 planes of d=4004 against w: 64*4004 multiply-adds; reads the
    # (64, 4005) planes with offsets and w (4004) in float32
    ops, nbytes = flops.plane_scores(64, 4004)
    assert ops == 2 * 64 * 4004 == 512512
    assert nbytes == (64 * 4005 + 4004) * 4 == 1041296


def test_viterbi_step_counts_by_hand():
    # B=32 rows, C=26 labels: an add and a compare per (b, c', c); reads
    # m (32x26) and T (26x26), writes m_out and back (32x26 each)
    ops, nbytes = flops.viterbi_step(32, 26)
    assert ops == 2 * 32 * 26 * 26 == 43264
    assert nbytes == (3 * 32 * 26 + 26 * 26) * 4 == 12688


def test_oracle_counts_by_hand():
    # one word of 8 positions, 128 features, 26 labels
    assert flops.chain_oracle(8, 128, 26) == \
        2 * 8 * 128 * 26 + 2 * 7 * 26 * 26 + 2 * 8 * 128
    assert flops.block_update(4004) == 8 * 4005


def test_peaks_table_is_keyed_by_device_kind():
    p = device.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_generators_are_deterministic_per_seed():
    from benchkit.tasks import chain as mod

    cfg = {"n": 8, "f": 4, "num_labels": 3, "mean_len": 4, "min_len": 2,
           "max_len": 6, "noise": 1.5, "trans_strength": 1.0,
           "data_seed": 0}
    big = 2 ** 31 + 12345
    a = mod.make_data(cfg, device.seed_key(big))
    b = mod.make_data(cfg, device.seed_key(big))
    c = mod.make_data(cfg, device.seed_key(big + 1))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    # another seed: the same examples (the same work), in another order
    assert not np.array_equal(np.asarray(a["x"]), np.asarray(c["x"]))
    key = lambda d: sorted(np.asarray(d["x"]).reshape(
        len(d["x"]), -1).sum(axis=1).round(4).tolist())
    assert key(a) == key(c)
    L = np.asarray(a["mask"]).sum(axis=1)
    assert L.min() >= 2 and L.max() <= 6
    assert not np.asarray(a["x"])[~np.asarray(a["mask"])].any()


def test_serving_arrivals_fill_the_window_with_the_same_gaps():
    from benchkit.drivers import serve

    r1 = np.random.default_rng(1)
    r2 = np.random.default_rng(2)
    d1, d2 = serve.arrivals(1000, 10.0, r1), serve.arrivals(1000, 10.0, r2)
    assert d1[0] == 0.0 and d1[-1] < 10.0 and np.all(np.diff(d1) > 0)
    # the same multiset of gaps, in another order
    g1 = np.sort(np.diff(np.concatenate([[0], d1])))
    g2 = np.sort(np.diff(np.concatenate([[0], d2])))
    assert not np.array_equal(d1, d2)
    assert math.isclose(d1[-1], d2[-1], rel_tol=0.05)
    assert np.median(g1) == pytest.approx(np.median(g2), rel=0.05)


class _StalledServer:
    """Admits requests and never serves one."""

    def __init__(self):
        self.queue = []

    @property
    def pending(self):
        return len(self.queue)

    def submit(self, example, t=None):
        self.queue.append(example)
        return len(self.queue) - 1

    def step(self):
        return []


def test_open_loop_admits_no_more_than_max_queued():
    from benchkit.drivers import serve

    w = serve.plan({"rate_per_s": 400.0}, 0.3, np.full(16, 5), seed=3)
    server = _StalledServer()
    serve.open_loop(server, [{}] * 16, w, set(), max_queued=10)
    assert server.pending == w.submitted == 10
    assert np.isnan(w.done).all()
    # the requests held back count from their due time to the close
    lat = stats.open_loop_latencies(w.due, w.done, w.end)
    assert lat == pytest.approx(w.end - w.due)
