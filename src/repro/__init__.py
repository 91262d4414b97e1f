"""SP-MoE on JAX/TPU: speculative decoding + SD-aware expert prefetching as a
production multi-pod framework.  See README.md."""
__version__ = "1.0.0"
