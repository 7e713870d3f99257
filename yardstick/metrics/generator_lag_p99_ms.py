"""The load generator itself. 99th percentile of (sent - due) over the
window's requests: a starved generator is not a fast server."""

import numpy as np


def read(obs):
    lags = obs.facts.get("lags_ms")
    return float(np.percentile(lags, 99)) if lags is not None and len(lags) \
        else None
