"""95th percentile of how late the load generator submitted a request
after its scheduled arrival, in ms."""

import numpy as np


def read(view):
    lag = view.record.get("submit_lag_s")
    return 1000.0 * float(np.percentile(lag, 95)) if lag else None
