"""99th percentile of how late the load generator submitted a request
after its due time."""
import numpy as np


def read(run):
    lag = run["record"].get("lag_ms")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(lag, 99))
