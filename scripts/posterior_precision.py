#!/usr/bin/env python3
"""Does matmul precision move the posterior read on the default device?

    python3 scripts/posterior_precision.py

Builds one tenant table on the host CPU (4,096 slots, 8 batches of 256
observed waits through ``serve.asa.decision_step``), then reads every
slot's posterior features (``core.asa.posterior_features``: MAP wait,
posterior mean, entropy) three ways from that same table: on the host
CPU, and on the default device at default and at ``highest`` matmul
precision. It prints, per feature, the largest relative difference of
each device read from the CPU read and how many slots differ. On a TPU
the default precision runs an f32 ``jnp.dot`` as one bf16 pass, so if
the posterior mean's dot mattered, the ``default`` line would show a
larger difference than the ``highest`` line.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

N_SLOTS = 4096
BATCH = 256
N_BATCHES = 8


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.core import asa
    from repro.core.bins import make_bins
    from repro.serve import asa as serve_asa

    cpu = jax.devices("cpu")[0]
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    with jax.default_device(cpu):
        bins = jnp.asarray(make_bins(53), jnp.float32)
        table = serve_asa.init_table(N_SLOTS)
        for _ in range(N_BATCHES):
            q = serve_asa.QueryBatch(
                slot=jnp.asarray(rng.permutation(N_SLOTS)[:BATCH], jnp.int32),
                observed_wait=jnp.asarray(rng.uniform(0, 5e4, BATCH),
                                          jnp.float32),
                has_obs=jnp.ones(BATCH, bool))
            table, _ = serve_asa.decision_step(table, q, jnp.ones(BATCH, bool))

    def read(where, precision=None):
        fn = jax.jit(jax.vmap(asa.posterior_features, in_axes=(0, None)))
        args = jax.device_put((table, bins), where)
        with jax.default_matmul_precision(precision):
            return np.asarray(fn(*args))

    ref = read(cpu)
    print(f"device: {dev.platform} {dev.device_kind}; {N_SLOTS} slots, "
          "features [map, mean, entropy]")
    for precision in ("default", "highest"):
        x = read(dev, None if precision == "default" else precision)
        rel = np.abs(x - ref) / np.maximum(np.abs(ref), 1e-30)
        print(f"{precision}: largest relative difference from the CPU "
              f"{rel.max(axis=0).tolist()}, slots that differ "
              f"{(x != ref).sum(axis=0).tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
