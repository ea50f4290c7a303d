"""Chain labeling (OCR): loss-augmented Viterbi, joint features, planes.

The model scores a labeling ``y`` of a word ``x`` (``L`` positions of
``f`` features, ``C`` labels) as

    S_w(x, y) = sum_l <w_u[y_l], x_l> + sum_{l<L-1} w_p[y_l, y_{l+1}]

with ``w = [w_u (C*f, row-major by label), w_p (C*C, row-major)]``.  The
loss is the Hamming distance over the valid positions divided by ``L``.
The oracle plane of example ``i`` at a labeling ``y'`` is
``[(psi(y') - psi(y_i)) / n, Delta(y_i, y') / n]`` (paper eq. 5).
"""
from __future__ import annotations

import numpy as np

from .precision import F64, Precision


def viterbi(unary: np.ndarray, trans: np.ndarray, prec: Precision = F64):
    """argmax_y sum_l unary[l, y_l] + sum_l trans[y_l, y_{l+1}].

    ``unary (L, C)``, ``trans (C, C)``.  Ties go to the lowest label.
    Returns ``(labels (L,) int, best score)``.
    """
    q = prec.q
    L = unary.shape[0]
    m = q(unary[0])
    backs = []
    for t in range(1, L):
        cand = q(m[:, None] + trans)
        backs.append(np.argmax(cand, axis=0))
        m = q(np.max(cand, axis=0) + unary[t])
    y = np.empty(L, np.int64)
    y[L - 1] = int(np.argmax(m))
    for t in range(L - 1, 0, -1):
        y[t - 1] = backs[t - 1][y[t]]
    return y, float(np.max(m))


def viterbi_batch(unary: np.ndarray, trans: np.ndarray,
                  lengths: np.ndarray, prec: Precision = F64) -> np.ndarray:
    """:func:`viterbi` for a padded batch: ``unary (B, Lmax, C)``; row
    ``b`` uses its first ``lengths[b]`` positions.  Returns ``(B, Lmax)``
    labels (0 past each row's length)."""
    q = prec.q
    B, Lmax, C = unary.shape
    m = q(unary[:, 0])
    back = np.zeros((B, Lmax, C), np.int64)
    for t in range(1, Lmax):
        cand = q(m[:, :, None] + trans[None])
        back[:, t] = np.argmax(cand, axis=1)
        step = q(np.max(cand, axis=1) + unary[:, t])
        m = np.where((t < lengths)[:, None], step, m)
    y = np.zeros((B, Lmax), np.int64)
    rows = np.arange(B)
    y[rows, lengths - 1] = np.argmax(m, axis=1)
    for t in range(Lmax - 1, 0, -1):
        prev = back[rows, t, y[:, t]]
        y[:, t - 1] = np.where(t <= lengths - 1, prev, y[:, t - 1])
    return np.where(np.arange(Lmax)[None] < lengths[:, None], y, 0)


def path_score(unary: np.ndarray, trans: np.ndarray, y: np.ndarray,
               prec: Precision = F64) -> float:
    """Score of labeling ``y (L,)`` under ``unary (L, C)`` and ``trans``."""
    q = prec.q
    L = len(y)
    s = q(np.sum(q(unary[np.arange(L), y])))
    return float(q(s + q(np.sum(q(trans[y[:-1], y[1:]])))))


class ChainTask:
    """One chain dataset, seen by the reference MP-BCFW.

    ``x (n, Lmax, f)``, ``y (n, Lmax)``, ``mask (n, Lmax)`` as the
    benchmark generated them.
    """

    def __init__(self, x, y, mask, num_labels: int,
                 prec: Precision = F64):
        self.prec = prec
        self.x = prec.q(x)
        self.y = np.asarray(y, np.int64)
        self.lengths = np.asarray(mask, bool).sum(axis=1).astype(np.int64)
        self.n, self.Lmax, self.f = self.x.shape
        self.C = int(num_labels)
        self.d = self.C * self.f + self.C * self.C

    def split(self, w):
        C, f = self.C, self.f
        return w[: C * f].reshape(C, f), w[C * f:].reshape(C, C)

    def truth(self, i: int) -> np.ndarray:
        return self.y[i, : self.lengths[i]]

    def unary(self, i: int, w) -> np.ndarray:
        """Plain (not loss-augmented) unaries ``(L, C)`` of example i."""
        wu, _ = self.split(w)
        return self.prec.q(self.x[i, : self.lengths[i]] @ wu.T)

    def decode(self, i: int, w) -> np.ndarray:
        """Loss-augmented Viterbi: the exact max-oracle's labeling."""
        L = self.lengths[i]
        _, wp = self.split(w)
        aug = self.prec.q(self.unary(i, w) + (1.0 - np.eye(self.C)[
            self.truth(i)]) / L)
        return viterbi(aug, wp, self.prec)[0]

    def scores(self, i: int, w, ys: np.ndarray) -> np.ndarray:
        """``<phi^{i y}, [w 1]>`` for each labeling row of ``ys (k, L)``."""
        q = self.prec.q
        L = self.lengths[i]
        u = self.unary(i, w)
        _, wp = self.split(w)
        yt = self.truth(i)
        pos = np.arange(L)

        def score(yy):
            return q(q(np.sum(q(u[pos, yy]), axis=-1))
                     + q(np.sum(q(wp[yy[..., :-1], yy[..., 1:]]), axis=-1)))

        loss = np.sum(ys != yt, axis=-1) / L
        return q(q(q(score(ys) - score(yt)) + loss) / self.n)

    def plane(self, i: int, yy: np.ndarray) -> np.ndarray:
        """The dense oracle plane ``(d+1,)`` of labeling ``yy``."""
        q = self.prec.q
        L = self.lengths[i]
        x = self.x[i, :L]
        eye = np.eye(self.C)
        yt = self.truth(i)
        unary = q((eye[yy] - eye[yt]).T @ x)
        pair = np.zeros((self.C, self.C))
        np.add.at(pair, (yy[:-1], yy[1:]), 1.0)
        np.add.at(pair, (yt[:-1], yt[1:]), -1.0)
        loss = np.sum(yy != yt) / L
        return q(np.concatenate([unary.ravel(), pair.ravel(), [loss]])
                 / self.n)

    def alter(self, i: int, yy: np.ndarray) -> np.ndarray:
        """A wrong answer: the first label moved to the next one."""
        out = yy.copy()
        out[0] = (out[0] + 1) % self.C
        return out

    def hinge_sum(self, w) -> float:
        """``sum_i max_y <phi^{iy}, [w 1]>`` by a batched exact decode."""
        q = self.prec.q
        wu, wp = self.split(w)
        u = q(np.einsum("nlf,cf->nlc", self.x, wu))
        valid = np.arange(self.Lmax)[None] < self.lengths[:, None]
        yt = np.where(valid, self.y, 0)
        aug = q(u + (1.0 - np.eye(self.C)[yt]) / self.lengths[:, None, None])
        yh = viterbi_batch(aug, wp, self.lengths, self.prec)
        rows = np.arange(self.n)[:, None]
        pos = np.arange(self.Lmax)[None]
        pv = valid[:, 1:]

        def score(yy):
            s_u = np.sum(np.where(valid, q(u[rows, pos, yy]), 0.0), axis=1)
            s_p = np.sum(np.where(pv, q(wp[yy[:, :-1], yy[:, 1:]]), 0.0),
                         axis=1)
            return q(q(s_u) + q(s_p))

        loss = np.sum((yh != yt) & valid, axis=1) / self.lengths
        return float(q(np.sum(q(q(score(yh) - score(yt)) + loss))
                       / self.n))
