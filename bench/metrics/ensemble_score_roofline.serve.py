"""``ensemble_score``'s share of its roofline when it serves requests
(``bench/readers.py``)."""
from bench.readers import ensemble_score_roofline as read  # noqa: F401
