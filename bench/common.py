"""Small helpers the configurations and traffic kinds share."""
from __future__ import annotations

import numpy as np

# independent random streams drawn from one --seed
STREAM_WEIGHTS, STREAM_TRAFFIC, STREAM_SAMPLE, STREAM_DATA = 1, 2, 3, 4


def np_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for one stream of a run; any whole seed works."""
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def jax_key(seed: int, stream: int):
    """A JAX key for one stream of a run. Seeds wider than 32 bits keep
    their high bits (``PRNGKey`` alone would wrap them)."""
    import jax

    seed = int(seed) & (2**64 - 1)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def path_name(path) -> str:
    """'/'-joined names of a pytree path."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation); NaN when empty."""
    if len(values) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def least_time_s(calls, peaks: dict) -> float:
    """Least time the chip could take for (flops, bytes) calls: each at the
    larger of its FLOPs over the bf16 peak and its bytes over the HBM
    bandwidth, summed."""
    return sum(max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
               for f, b in calls)
