"""Drivers: one per kind of configuration (served LM, batched CNN)."""
