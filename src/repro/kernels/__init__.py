"""Pallas TPU kernels for the compute hot spots (pairwise distances, GF(2)
bit-packed reduction) with jit wrappers (ops) and pure-jnp oracles (ref)."""
