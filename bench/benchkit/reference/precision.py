"""Working precision of a reference run."""
from __future__ import annotations

from dataclasses import dataclass

import ml_dtypes
import numpy as np


@dataclass(frozen=True)
class Precision:
    """``q(x)`` rounds an array (or scalar) to the working precision.

    ``float64`` keeps full double precision.  ``bfloat16`` computes each
    operation in float32 and rounds its result, and every stored value,
    to bfloat16: the arithmetic of a program that kept its state and its
    intermediates in bfloat16.
    """

    name: str

    @property
    def dtype(self):
        return np.float64 if self.name == "float64" else np.float32

    def q(self, x):
        if self.name == "float64":
            return np.asarray(x, np.float64)
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
            .astype(np.float32)


F64 = Precision("float64")
BF16 = Precision("bfloat16")
