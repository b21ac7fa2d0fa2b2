"""95th percentile over every gap between consecutive output tokens of
every request in the window."""

import numpy as np

from chipbench.record import percentile


def read(run):
    gaps = [g for r in run.requests for g in np.diff(r.times)]
    return percentile(gaps, 95)
