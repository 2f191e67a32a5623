"""Chip benchmark of the clustering library (see README.md)."""
