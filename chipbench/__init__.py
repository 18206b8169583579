"""Chip benchmark of the multi-tenant exact CP serving engines (see run.py)."""
