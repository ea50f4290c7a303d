"""Length-bucketed continuous batching for structured decode.

:class:`StructuredServer` generalizes the fixed-slot round loop of
``repro.launch.serve`` (the LM demo) to structured prediction: requests
are admitted into per-bucket FIFO queues (bucket = the engine's
:meth:`~repro.serve.engine.DecodeEngine.shape_key` rounded up to a
coarse grid), and every :meth:`step` serves ONE bucket with ONE dispatch
of that bucket's compiled fixed-shape program — short batches are padded
with filler rows so the batch shape never changes and ``jax.jit`` reuses
the executable.  Rows decode independently (the engines' batched
programs have no cross-row reductions), so fillers and padding cannot
perturb results: every served labeling is bit-for-bit the model's
per-example ``spec.decode`` (the round-trip tests pin this).

Round structure is *asserted*, not hoped for: the
:class:`~repro.serve.metrics.ServeLedger` brackets each round and raises
unless it dispatched exactly once.  Each round is a ``repro:round``
profiler span (:mod:`repro.obs.spans`) holding ``pick``, ``pad``,
``stack``, ``decode``, ``sync`` and ``answer`` spans.
Latency/queue/throughput series ride
:class:`~repro.serve.metrics.ServeMetrics`, and an optional
:class:`~repro.obs.recorder.RunRecorder` gets schema-v1 ``serve_round``
spans + per-request events, so serving traces replay through the same
``repro.obs`` tooling as training traces.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs import spans
from .engine import DecodeEngine, ShapeKey, decode_engine_for
from .export import ServableModel
from .metrics import ServeLedger, ServeMetrics


@dataclass
class ServeRequest:
    """One admitted decode request and, after its round, the result."""

    rid: int
    example: Any                      # host-side example pytree
    key: ShapeKey                     # true shape signature
    bucket: ShapeKey                  # padded bucket geometry
    t_submit: float
    t_done: Optional[float] = None
    labels: Optional[np.ndarray] = None

    @property
    def latency(self) -> float:
        if self.t_done is None:
            raise RuntimeError(f"request {self.rid} not served yet")
        return self.t_done - self.t_submit


def bucket_key(key: ShapeKey, granularity: int = 4) -> ShapeKey:
    """Round each variable dim up to a multiple of ``granularity``.

    Coarse buckets trade a little padding compute for executable reuse:
    the number of distinct compiled programs is bounded by the number of
    occupied grid points, not by the number of distinct request shapes.
    """
    g = max(int(granularity), 1)
    return tuple(-(-max(int(k), 1) // g) * g for k in key)


class StructuredServer:
    """Round-based batched serving of one :class:`ServableModel`.

    Drive it directly (``submit`` + ``step`` / ``drain``) or from a load
    generator (:mod:`benchmarks.serving_bench`).  ``clock`` is injectable
    so tests and the cost-model bench can run on a virtual clock.
    """

    def __init__(self, model: ServableModel, *, batch_size: int = 8,
                 bucket_granularity: int = 4,
                 engine: Optional[DecodeEngine] = None,
                 metrics: Optional[ServeMetrics] = None,
                 recorder=None, clock=time.perf_counter):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.model = model
        self.engine = engine if engine is not None \
            else decode_engine_for(model)
        self.batch_size = int(batch_size)
        self.granularity = int(bucket_granularity)
        self.ledger = ServeLedger()
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.recorder = recorder
        self.clock = clock
        self._rid = itertools.count()
        # bucket -> FIFO of waiting requests; dict preserves insertion
        # order, and round scheduling picks the bucket holding the oldest
        # head-of-line request (no bucket starves).
        self._queues: Dict[ShapeKey, List[ServeRequest]] = {}
        if self.recorder is not None:
            self.recorder.open_custom(
                algo=f"serve:{type(self.model.spec).__name__}",
                n=self.batch_size, d=self.model.d,
                engine_budgets={"dispatches_per_round": 1,
                                "host_syncs_per_round": 1})

    # -- admission ----------------------------------------------------------

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def submit(self, example: Any, t: Optional[float] = None) -> int:
        """Admit one example; returns its request id."""
        key = self.engine.shape_key(example)
        bucket = bucket_key(key, self.granularity)
        req = ServeRequest(rid=next(self._rid), example=example, key=key,
                           bucket=bucket,
                           t_submit=self.clock() if t is None else t)
        self._queues.setdefault(bucket, []).append(req)
        self.metrics.set_queue_depth(self.pending)
        return req.rid

    # -- the round loop ------------------------------------------------------

    def _pick_bucket(self) -> Optional[ShapeKey]:
        oldest, pick = None, None
        for bucket, q in self._queues.items():
            if q and (oldest is None or q[0].rid < oldest):
                oldest, pick = q[0].rid, bucket
        return pick

    def step(self) -> List[ServeRequest]:
        """Serve one round: one bucket, one dispatch, one sync.

        Returns the completed requests of the round ([] when idle).
        """
        with spans.span(spans.ROUND) as round_span:
            with spans.span(spans.PICK):
                bucket = self._pick_bucket()
                if bucket is None:
                    return []
                queue = self._queues[bucket]
                reqs = queue[: self.batch_size]
                del queue[: len(reqs)]
                if not queue:
                    del self._queues[bucket]
            round_span.set_metadata(bucket=",".join(map(str, bucket)),
                                    batch=len(reqs))

            t0 = self.clock()
            with spans.span(spans.PAD):
                padded = [self.engine.pad(r.example, bucket) for r in reqs]
                # Filler rows keep the batch shape fixed so the bucket's
                # compiled executable is reused; rows decode
                # independently, so fillers cannot perturb the real rows.
                padded.extend([padded[-1]] * (self.batch_size - len(padded)))
            with spans.span(spans.STACK):
                batch = self.engine.stack(padded)

            self.ledger.begin_round()
            with spans.span(spans.DECODE):
                out = self.engine.decode(batch)
            self.ledger.dispatched()
            with spans.span(spans.SYNC):
                labels = self.ledger.sync(out)
            self.ledger.commit_round()

            t1 = self.clock()
            with spans.span(spans.ANSWER):
                self._answer(reqs, labels, bucket, t0, t1)
        return reqs

    def _answer(self, reqs, labels, bucket, t0: float, t1: float) -> None:
        """Unpad each request's labels and record the round."""
        for i, req in enumerate(reqs):
            req.labels = np.asarray(self.engine.unpad(labels[i], req.key))
            req.t_done = t1
            self.metrics.observe_request(req.latency, req.labels.size)
            if self.recorder is not None:
                self.recorder.event("serve_request", t=t1, rid=req.rid,
                                    latency=req.latency,
                                    labels=int(req.labels.size))
        self.metrics.observe_round(
            batch=len(reqs), fill=len(reqs) / self.batch_size,
            round_s=t1 - t0, bucket=bucket)
        self.metrics.set_queue_depth(self.pending)
        if self.recorder is not None:
            self.recorder.span_record("serve_round", t0, t1,
                                      timebase="host",
                                      bucket=list(bucket),
                                      batch=len(reqs),
                                      slots=self.batch_size)

    def drain(self) -> List[ServeRequest]:
        """Run rounds until every admitted request is served."""
        done: List[ServeRequest] = []
        while self.pending:
            done.extend(self.step())
        return done

    # -- convenience ---------------------------------------------------------

    def serve(self, examples: List[Any]) -> List[np.ndarray]:
        """Batch-serve a list of examples, results in submission order."""
        rids = [self.submit(ex) for ex in examples]
        by_rid = {r.rid: r for r in self.drain()}
        return [by_rid[rid].labels for rid in rids]
