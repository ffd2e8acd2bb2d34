"""Small shared utilities (padding)."""
from repro.utils.padding import pad_to_multiple

__all__ = ["pad_to_multiple"]
