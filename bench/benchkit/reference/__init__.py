"""Plain NumPy references for the benchmark's correctness checks.

Nothing in this package imports the system under test (``repro``) or
JAX: it is a second, straightforward implementation of the same
mathematics, written from the paper (Lacoste-Julien et al. 2013,
Shah et al. 2015) and the task definitions, and it takes only the
benchmark's own inputs (data made from ``--seed``), never anything the
program computed.

Every routine takes a :class:`Precision`: ``F64`` is the reference, and
``BF16`` rounds every stored value and every result to bfloat16, which is
the control that a sound comparison has to reject.
"""
from .precision import BF16, F64, Precision

__all__ = ["BF16", "F64", "Precision"]
