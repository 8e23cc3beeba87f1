"""95th percentile of how late the load generator submitted each request
after its due time (ms). A large value means the host starved the
generator, and the latencies above it are the generator's as much as the
server's."""

import numpy as np


def read(ctx):
    lag = ctx.get("gen_lag_ms")
    if lag is None:
        return None
    lag = lag[np.isfinite(lag)]
    return float(np.percentile(lag, 95)) if lag.size else None
