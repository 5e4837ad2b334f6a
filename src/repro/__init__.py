"""LSH-MoE reproduction (arXiv 2411.08446) on JAX 0.9 + Pallas."""

__version__ = "0.1.0"
