"""Roofline share of the fused decompress+matmul kernel: the least time of
the calls in the traced passes (``flops.matmul_least_s``) over the kernel's
summed device time in the trace."""

from bench import flops, trace


def is_kernel(op: str) -> bool:
    """The kernel's op carries the name of the function that calls it."""
    return (trace.op_name(op) == "decompress_matmul"
            and "tpu_custom_call" in op)


def read(rec):
    if rec.trace is None:
        return None
    sec, n = trace.kernel_time(rec.trace, is_kernel)
    least = flops.matmul_least_s(rec.geometry.get("packed", []),
                                 rec.traced_steps(), rec.traced_admits(),
                                 rec.peaks)
    if not n or least is None:
        return None
    return 100.0 * least / sec
