"""Share of the traced window in which no operation ran on the device,
in a serve cell (``bench/readers.py``)."""
from bench.readers import idle_share as read  # noqa: F401
