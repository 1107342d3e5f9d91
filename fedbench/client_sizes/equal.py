"""Every client holds ``samples`` samples."""
import numpy as np


def sizes(law: dict, population: int) -> np.ndarray:
    return np.full(population, int(law["samples"]), np.int64)


def cap(law: dict) -> int:
    return int(law["samples"])
