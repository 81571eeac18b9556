"""95th percentile of every gap between consecutive streamed tokens of a
request, both inside the window, from the host time at which the step
that made each token returned (numpy's linear interpolation)."""

import numpy as np


def read(rec):
    if not rec["gaps_s"]:
        return None
    return 1e3 * float(np.percentile(np.asarray(rec["gaps_s"]), 95))
