"""Chip benchmark of SP-MoE serving: one cell per run (see run.py)."""
