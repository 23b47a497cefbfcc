"""95th percentile of the client-side latency of every request due in
the window (missing requests at the time they had waited), in s. The
tail spreads too widely between runs on one chip to carry a bound, so it
is read here, beside the median that does."""

import numpy as np


def read(view):
    latency = view.record.get("latency_s")
    return float(np.percentile(latency, 95)) if latency else None
