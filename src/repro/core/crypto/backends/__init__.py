"""Curve-arithmetic backends behind the ``crypto.set_backend`` seam.

``python`` hosts the three pure-Python backends (naive / batch / glv).
"""

from repro.core.crypto.backends.python import (BatchOps, CurveOps, NaiveOps,
                                               RLCItem)

__all__ = ["CurveOps", "NaiveOps", "BatchOps", "RLCItem"]
