"""Serving cells: an open loop of decode requests on the real clock.

Set-up makes the model's weights and a pool of words on the device from
the seed, builds :class:`repro.serve.StructuredServer`, and serves one
full round of every padding bucket the pool holds, which compiles (or
loads) each bucket's program.  The window then offers ``rate`` requests
per second for ``--seconds``: the requests' count is fixed by the rate
and the window, their inter-arrival gaps are the quantiles of an
exponential distribution (Poisson arrivals) in an order drawn from the
seed, and request ``r`` is pool word ``r mod pool`` of a pool shuffled by
the seed, so every seed offers the same lengths and gaps in another
order.  The server's queue holds at most ``MAX_QUEUED`` requests, as
behind a router that keeps its overflow: a due request waits on the
generator's side until there is room, so the host never pays to admit
requests that the window will not serve.  Each request is timed from
its due time; one still waiting when the window closes counts with the
window's end as its completion.

A traced run (``--trace 1``) then offers the same rate for
``trace_seconds`` more under the profiler; the window itself is never
traced.  After the window, a sample of the answered requests, drawn
from the seed, is decoded again by the plain reference (float64 NumPy
Viterbi over the same unaries), and the check compares how far each
served labeling's score lies below the best; requests that were neither
answered nor still queued count as lost.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import List

import numpy as np

from .. import device as dev
from .. import stats
from .. import trace as tr
from ..outcome import Outcome, judge
from ..reference import F64, Precision
from ..reference.chain import path_score, viterbi

# The server's queue bound: 32 full batches, enough that every length
# bucket fills its rounds at any rate the server can answer.
MAX_QUEUED = 1024


def make_weights(cfg: dict, traffic: dict, key):
    """The served model's weights, drawn on the device: the unary block
    ``N(0, unary_std^2)``, the pairwise block ``N(0, pair_std^2)``."""
    import jax
    import jax.numpy as jnp

    C, f = int(cfg["num_labels"]), int(cfg["f"])
    ku, kp = jax.random.split(key)
    w = traffic["weights"]
    return jnp.concatenate([
        float(w["unary_std"]) * jax.random.normal(ku, (C * f,), jnp.float32),
        float(w["pair_std"]) * jax.random.normal(kp, (C * C,), jnp.float32)])


def arrivals(n_req: int, seconds: float, rng) -> np.ndarray:
    """Due times of ``n_req`` Poisson arrivals filling ``seconds``: the
    exponential quantiles as gaps, shuffled, scaled to the window."""
    u = (np.arange(n_req) + 0.5) / n_req
    gaps = rng.permutation(-np.log1p(-u))
    due = np.cumsum(gaps)
    return due * (seconds / due[-1]) - gaps[0] * (seconds / due[-1])


@dataclass
class Window:
    due: np.ndarray
    word: np.ndarray                  # pool index of each request
    length: np.ndarray
    seconds: float
    submitted: int = 0
    done: np.ndarray = None           # completion time, NaN if not done
    start: np.ndarray = None          # start of the request's round
    labels: dict = field(default_factory=dict)
    rounds: List[tuple] = field(default_factory=list)
    #      (start, end, requests, real positions, bucket length)
    late: List[float] = field(default_factory=list)    # due -> admitted
    end: float = 0.0


def open_loop(server, pool: list, w: Window, keep: set,
              max_queued: int = MAX_QUEUED) -> None:
    """Offer ``w``'s requests to ``server`` on the real clock, admitting
    a due request while the server holds fewer than ``max_queued``."""
    n = len(w.due)
    w.done = np.full(n, np.nan)
    w.start = np.full(n, np.nan)
    rid0 = None
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        if now >= w.seconds:
            break
        room = max_queued - server.pending
        if room > 0 and i < n and w.due[i] <= now:
            with tr.span("submit"):
                while room > 0 and i < n and w.due[i] <= now:
                    rid = server.submit(pool[w.word[i]], t=t0 + w.due[i])
                    if rid0 is None:
                        rid0 = rid
                    w.late.append(now - w.due[i])
                    i += 1
                    room -= 1
        if server.pending:
            s0 = time.perf_counter()
            with tr.span("step"):
                reqs = server.step()
            s1 = time.perf_counter()
            for r in reqs:
                k = r.rid - rid0
                if not 0 <= k < n:      # an earlier window's backlog
                    continue
                w.done[k] = r.t_done - t0
                w.start[k] = s0 - t0
                if k in keep:
                    w.labels[k] = r.labels
            if reqs:
                w.rounds.append((s0 - t0, s1 - t0, len(reqs),
                                 sum(int(r.key[0]) for r in reqs),
                                 int(reqs[0].bucket[0])))
        elif i < n:
            wait = w.due[i] - (time.perf_counter() - t0)
            if wait > 2e-3:
                time.sleep(wait - 1e-3)
    w.end = time.perf_counter() - t0
    w.submitted = i


def setup(cell, seed: int):
    """Weights, request pool, server (warmed up on every bucket)."""
    import jax

    from repro.serve import ServableModel, StructuredServer

    cfg, traffic = cell.config, cell.traffic
    k_w, k_pool = jax.random.split(dev.seed_key(seed))
    with tr.span("make_data"):
        w = make_weights(cfg, traffic, k_w)
        data = cell.task.make_data(cfg, k_pool, n=int(traffic["pool"]))
        host = {k: np.asarray(v) for k, v in jax.device_get(data).items()}
    lengths = host["mask"].sum(axis=1)
    pool = [{"x": host["x"][j, :L], "y": host["y"][j, :L],
             "mask": host["mask"][j, :L]} for j, L in enumerate(lengths)]
    model = ServableModel(spec=cell.task.spec(cfg), w=w)
    server = StructuredServer(
        model, batch_size=int(traffic["batch_size"]),
        bucket_granularity=int(traffic["bucket_granularity"]))
    # One full round of each bucket the pool holds: every program the
    # window dispatches is compiled (or loaded) here.
    seen = {}
    for j, L in enumerate(lengths):
        seen.setdefault(-(-int(L) // server.granularity), j)
    for j in seen.values():
        for _ in range(server.batch_size):
            server.submit(pool[j])
        server.drain()
    return server, pool, lengths, host, np.asarray(jax.device_get(w))


def plan(traffic: dict, seconds: float, pool_lengths, seed: int,
         rate: float = None) -> Window:
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    rate = float(traffic["rate_per_s"]) if rate is None else rate
    n_req = max(int(round(rate * seconds)), 1)
    order = rng.permutation(len(pool_lengths))
    word = order[np.arange(n_req) % len(order)]
    return Window(due=arrivals(n_req, seconds, rng), word=word,
                  length=np.asarray(pool_lengths)[word], seconds=seconds)


def sample(w: Window, size: int, seed: int) -> set:
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    n = len(w.due)
    return set(rng.choice(n, size=min(size, n), replace=False).tolist())


def label_gaps(cfg: dict, weights: np.ndarray, host: dict, w: Window,
               served: dict) -> List[float]:
    """For each served labeling: how far its score lies below the best,
    over the scale of the scores (both from the float64 reference)."""
    C, f = int(cfg["num_labels"]), int(cfg["f"])
    wu = np.asarray(weights[: C * f], np.float64).reshape(C, f)
    wp = np.asarray(weights[C * f:], np.float64).reshape(C, C)
    gaps = []
    for k, labels in served.items():
        j, L = w.word[k], w.length[k]
        x = host["x"][j, :L].astype(np.float64)
        y = host["y"][j, :L]
        u = x @ wu.T + (1.0 - np.eye(C)[y]) / L
        _, best = viterbi(u, wp, F64)
        got = path_score(u, wp, np.asarray(labels, np.int64), F64)
        scale = np.sum(np.max(np.abs(u), axis=1)) + (L - 1) * np.max(
            np.abs(wp))
        gaps.append((best - got) / scale)
    return gaps


def reference_labels(cfg: dict, weights: np.ndarray, host: dict, w: Window,
                     keys, prec: Precision) -> dict:
    """The reference's own labelings at ``prec`` (the control)."""
    C, f = int(cfg["num_labels"]), int(cfg["f"])
    wq = prec.q(weights)
    wu, wp = wq[: C * f].reshape(C, f), wq[C * f:].reshape(C, C)
    out = {}
    for k in keys:
        j, L = w.word[k], w.length[k]
        x = prec.q(host["x"][j, :L])
        y = host["y"][j, :L]
        u = prec.q(prec.q(x @ wu.T) + (1.0 - np.eye(C)[y]) / L)
        out[k] = viterbi(u, wp, prec)[0]
    return out


def check_values(cfg, weights, host, w: Window, lost: int) -> dict:
    gaps = label_gaps(cfg, weights, host, w, w.labels)
    return {"lost": float(lost),
            "label_gap": max(gaps) if gaps else float("inf")}


def run(cell, seed: int, seconds: float, trace: bool, clock0: float,
        devices, counter, trace_dir) -> Outcome:
    traffic = cell.traffic
    server, pool, lengths, host, weights = setup(cell, seed)
    # One second of the cell's own traffic before the window, so that the
    # host path (admission, padding, transfers) is warm under load too.
    open_loop(server, pool, plan(traffic, 1.0, lengths, seed + 2), set())
    server.drain()
    w = plan(traffic, seconds, lengths, seed)
    keep = sample(w, int(traffic["check_sample"]), seed)
    gc.collect()
    gc.disable()
    counter.armed = True
    setup_s = time.perf_counter() - clock0
    try:
        open_loop(server, pool, w, keep)
    finally:
        gc.enable()
    counter.armed = False
    device = dev.describe(devices)
    # Checked and measured before a traced tail can add to the queue.
    lost = w.submitted - int(np.sum(~np.isnan(w.done))) - server.pending

    lat = stats.open_loop_latencies(w.due, w.done, w.end)
    e2e = {"setup_s": setup_s,
           "serve_p95_ms": 1e3 * stats.p95(lat),
           "serve_labels_per_s": stats.labels_per_s(w.length, w.done, w.end,
                                                    seconds)}
    trace_data, trace_window_s = None, None
    if trace:
        tail = plan(traffic, float(traffic["trace_seconds"]), lengths,
                    seed + 1)
        capture = tr.Capture(trace_dir)
        capture.start()
        open_loop(server, pool, tail, set())
        trace_window_s = tail.end
        trace_data = capture.stop()
    t_ref = time.perf_counter()
    values = check_values(cell.config, weights, host, w, lost)
    ref_s = time.perf_counter() - t_ref
    compared = judge(values, cell.limits.get("limits", {}))
    answered = int(np.sum(~np.isnan(w.done)))
    ctx = {"kind": "serve", "config": cell.config, "traffic": traffic,
           "window": w, "batch_size": server.batch_size,
           "device": device,
           "trace": trace_data, "trace_window_s": trace_window_s}
    late = np.asarray(w.late) if w.late else np.zeros(1)
    due_by_close = int(np.sum(w.due < w.end))
    # The tail slice by slice: where in the window it was made.
    cuts = np.searchsorted(w.due, np.arange(0.0, seconds, 5.0))
    slices = [lat[a:b] for a, b in zip(cuts, list(cuts[1:]) + [len(lat)])]
    notes = [f"reference_s {ref_s!r} window_s {w.end!r}",
             counter.note(),
             f"requests_due {len(w.due)} submitted {w.submitted} "
             f"answered {answered} "
             f"backlog_at_close {w.submitted - answered - int(lost)} "
             f"held_back_at_close {due_by_close - w.submitted} "
             f"rounds {len(w.rounds)}",
             f"admitted_late_ms median {1e3 * float(np.median(late))!r} "
             f"max {1e3 * float(np.max(late))!r} "
             f"at_s {float(w.due[int(np.argmax(late))])!r}",
             f"latency_ms p50 {1e3 * float(np.median(lat))!r} "
             f"p95 {1e3 * stats.p95(lat)!r} max {1e3 * float(lat.max())!r}",
             "p95_ms_by_5s " + " ".join(
                 f"{1e3 * stats.p95(x):.2f}" for x in slices if len(x)),
             f"checked_sample {len(w.labels)}"]
    if w.rounds:
        slow = max(w.rounds, key=lambda r: r[1] - r[0])
        notes.append(f"slowest_round_ms {1e3 * (slow[1] - slow[0])!r} "
                     f"at_s {slow[0]!r}")
    return Outcome(attempted=len(w.due), failed=int(values["lost"]),
                   end_to_end=e2e, ctx=ctx, compared=compared, notes=notes,
                   trace=trace_data, trace_window_s=trace_window_s)
