"""repro.data — deterministic synthetic data (embeddings, conformations)."""

from repro.data.synthetic import conformations, gaussian_mixture

__all__ = [
    "conformations",
    "gaussian_mixture",
]
