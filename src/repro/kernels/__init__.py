"""Pallas kernels (paged attention, flash prefill, SSD scan) and their
pure-jnp oracles in ``ref``."""
