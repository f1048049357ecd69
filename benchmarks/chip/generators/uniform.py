"""The uniform u-set graph (the dynamic-graph literature's uniform-N):
``V`` vertices with random, non-contiguous IDs of ``id_bits`` bits, and
``E`` undirected edge inserts whose two endpoints are drawn uniformly
and independently, each with a weight uniform in ``weights``."""
import numpy as np


def generate(cfg: dict, V: int, E: int, rng: np.random.Generator) -> dict:
    lo, hi = cfg["weights"]
    ids = rng.choice(1 << cfg["id_bits"], V, replace=False).astype(np.uint64)
    su = rng.integers(0, V, E)
    sv = rng.integers(0, V, E)
    w = rng.uniform(lo, hi, E).astype(np.float32)
    return dict(ids=ids, su=su, sv=sv, w=w)
