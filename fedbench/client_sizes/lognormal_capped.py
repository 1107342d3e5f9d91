"""exp(N(mu, sigma)) rounded, held to [1, cap], drawn from the law's own
``seed``: a heavy tail with the paper's cap on sequences per client."""
import numpy as np


def sizes(law: dict, population: int) -> np.ndarray:
    g = np.random.default_rng(int(law["seed"]))
    raw = np.exp(g.normal(law["mu"], law["sigma"], population))
    return np.clip(np.rint(raw), 1, int(law["cap"])).astype(np.int64)


def cap(law: dict) -> int:
    return int(law["cap"])
