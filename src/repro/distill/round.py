"""The distillation leg of one one-shot round, in one place.

``run_protocol`` (core) and ``run_population`` (sim) both end the round
the same way: draw proxy data on the distillation stage's own seed
stream, distill the best selected ensemble, push the student through
its download codec onto the ledger at exact wire size, and hand back
the DECODED student for evaluation — the same server-side plumbing
``comm.ModelExchange`` centralizes for the upload leg. ``distill_round``
is that logic once, so the two runners cannot drift.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Sequence

import numpy as np

from repro.distill.config import DistillConfig
from repro.distill.proxy import make_proxy
from repro.distill.solvers import distill_rng, distill_teacher
from repro.obs.trace import span


@dataclasses.dataclass
class DistilledRound:
    """What the distillation leg hands back to a runner."""

    student: object      # the student AS DEVICES DECODE IT
    codec: str           # the download codec actually used
    nbytes: int          # exact wire size, as recorded on the ledger
    proxy_size: int      # proxy rows actually drawn


def distill_round(
    teacher_predict: Callable[[np.ndarray], np.ndarray],
    devices: Optional[Sequence],
    cfg: DistillConfig,
    seed: int,
    round_codec: str,
    ledger,
    dim: Optional[int] = None,
    default_proxy_params: Optional[Mapping] = None,
    split_counts=None,
    fetch_split=None,
) -> DistilledRound:
    """Proxy draw -> solve -> wire -> ledger, for one round.

    ``default_proxy_params`` backstop the config's ``proxy_params``
    (the population runner defaults the ``scenario`` source to its own
    federation); the student download codec defaults to the round's
    upload codec. Streamed rounds pass ``devices=None`` plus the lazy
    ``split_counts``/``fetch_split`` pair (see ``proxy.ProxyContext``).
    """
    from repro.comm import decode, encode  # deferred: comm <-> core cycle

    with span("distill.round", cat="distill", solver=cfg.solver,
              proxy=cfg.proxy, proxy_size=cfg.proxy_size):
        params = dict(cfg.proxy_params)
        for key, val in dict(default_proxy_params or {}).items():
            params.setdefault(key, val)
        proxy = make_proxy(cfg.proxy, n=cfg.proxy_size, rng=distill_rng(seed),
                           devices=devices, dim=dim,
                           split_counts=split_counts, fetch_split=fetch_split,
                           **params)
        student = distill_teacher(teacher_predict, proxy, cfg=cfg, seed=seed)
        codec = cfg.codec or round_codec
        wire = encode(student, codec)
        ledger.record("down", "student_download", len(wire),
                      codec=codec, tag="download_distilled")
    return DistilledRound(decode(wire), codec, len(wire), len(proxy))
