"""repro: Dory-JAX — persistent homology at scale + multi-pod LM framework."""
__version__ = "1.0.0"
