"""Operations and bytes of the work, counted from shapes.

Only the contractions count as operations: a multiply-add is 2. The
RBF epilogue (norms, exp) runs beside them on the vector units and is
left out, so a share of the bf16 peak is a share of what the matrix
units could do. Bytes are each operand read once and the result
written once, at 4 bytes an element (every operand here is float32).
"""
from __future__ import annotations

import re
from typing import Iterable, Optional, Tuple


def batched_rbf_gram(g: int, m: int, n: int, d: int) -> Tuple[float, float]:
    """(g, m, d) x (g, n, d) -> (g, m, n) Gram matrices."""
    return 2.0 * g * m * n * d, 4.0 * (g * m * d + g * n * d + g + g * m * n)


def ensemble_score(b: int, k: int, n: int, d: int) -> Tuple[float, float]:
    """(b, d) queries against k members of n padded supports -> (b,)."""
    return 2.0 * b * k * n * (d + 1), 4.0 * (b * d + k * n * d + k * n + k + b)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)


def svm_fit(n: int, d: int, epochs: int) -> float:
    """Gram of n points, then ``epochs`` cyclic SDCA sweeps: each of the
    n coordinate steps is one length-n dot."""
    return 2.0 * n * n * d + epochs * 2.0 * n * n


def scoring(rows: int, supports: int, d: int) -> float:
    """rows x supports kernel values, each weighted into its score."""
    return 2.0 * rows * supports * (d + 1)


def round_ops(local: Iterable[Tuple[int, int]], d: int, epochs: int,
              evals: Iterable[Tuple[int, int]], fits: Iterable[int] = ()) -> float:
    """One round: every eligible device's fit plus the scoring of its
    own val and test rows (``local``: (n_train, n_val + n_test) pairs),
    extra fits (the pooled ideal), and every ensemble evaluation
    (``evals``: (rows scored, supports over all members) pairs)."""
    ops = sum(svm_fit(n, d, epochs) + scoring(q, n, d) for n, q in local)
    ops += sum(svm_fit(n, d, epochs) for n in fits)
    return ops + sum(scoring(r, s, d) for r, s in evals)


# The jit wrappers that call each Pallas kernel (``repro.kernels.ops``):
# the trace names a kernel's custom call after its wrapper.
WRAPPERS = {"batched_rbf_gram": "_bgram_tpu", "ensemble_score": "_ens_tpu"}
_CALL = re.compile(r'^%(?P<inst>[\w.\-]+) = \S+ custom-call\((?P<args>.*?)\), '
                   r'custom_call_target="tpu_custom_call"')
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def call_shapes(op_text: str) -> Optional[Tuple[str, list]]:
    """(instruction name, operand shapes) of a TPU custom call's HLO text."""
    m = _CALL.match(op_text)
    if m is None:
        return None
    shapes = [tuple(int(x) for x in dims.split(",") if x) for dims in _SHAPE.findall(m["args"])]
    return m["inst"], shapes


def cost_of(kernel: str, op_text: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one call of ``kernel`` from its operand
    shapes in the trace, or None when the op is not such a call."""
    if "custom-call" not in op_text:
        return None
    parsed = call_shapes(op_text)
    if parsed is None or parsed[0].split(".")[0] != WRAPPERS[kernel]:
        return None
    shapes = parsed[1]
    if kernel == "batched_rbf_gram":
        (g, m, d), (_, n, _) = shapes[0], shapes[1]
        return batched_rbf_gram(g, m, n, d)
    (b, d), (k, n, _) = shapes[0], shapes[1]
    return ensemble_score(b, k, n, d)
