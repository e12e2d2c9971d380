"""``batched_rbf_gram``'s share of its roofline: the least time its
calls could take at the chip's peaks (operations and bytes from each
call's shapes, ``bench/counting.py``) over their time on the device."""

from bench.trace_reduce import kernel_roofline_share


def read(run):
    return kernel_roofline_share(run, "batched_rbf_gram")
