"""Agreement with a reference within a written tolerance.

Where a test compares two different programs (a chunked and a one-shot
prefill, a batched and a per-image pass), they sum the same float32
terms in different orders and may part in the last bits.  Such a test
states its tolerance as named constants, checks `miss(got, want) <= 1`,
and plants a fault that the tolerance must catch by FAULT_MARGIN.
Where both sides run one program, tests compare bitwise instead.
"""
import numpy as np

FAULT_MARGIN = 10.0  # a planted fault misses by at least this many tolerances


def miss(got, want, *, rtol: float, atol_frac: float) -> float:
    """The largest |got - want| over its allowance atol + rtol |want|,
    where atol = atol_frac * max|want|: at most 1 where got agrees."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    atol = atol_frac * np.max(np.abs(want))
    return float(np.max(np.abs(got - want) / (atol + rtol * np.abs(want))))
