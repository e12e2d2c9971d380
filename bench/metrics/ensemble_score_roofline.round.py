"""``ensemble_score``'s share of its roofline in a round: the ensembles
evaluated on the devices' test splits and the distillation's teacher
(``bench/readers.py``)."""
from bench.readers import ensemble_score_roofline as read  # noqa: F401
