"""Answered rows x the ensemble's supports x the contraction, as a share
of the bf16 peak over the traced window (``bench/readers.py``)."""
from bench.readers import mfu as read  # noqa: F401
