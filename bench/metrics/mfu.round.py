"""The traced round's operations (every fit, every scoring of own
splits, every ensemble evaluation) as a share of the bf16 peak
(``bench/readers.py``)."""
from bench.readers import mfu as read  # noqa: F401
