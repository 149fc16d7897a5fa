"""repro: Dory-JAX — persistent homology at scale."""
__version__ = "1.0.0"
