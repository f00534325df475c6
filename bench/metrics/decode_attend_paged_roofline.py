"""Roofline share of the paged attention kernel: the least time of the
calls the window made (``flops.attend_least_s``, live tokens only) over
the kernel's summed device time in the trace."""

import re

from bench import flops, trace

# The kernel carries no name of its own in the trace yet: it is the Mosaic
# custom call whose result is the (out, m, l) triple of an online softmax,
# f32 (slots, heads, head_dim), (slots, heads, 1), (slots, heads, 1).  Once
# its pallas_call has ``name="decode_attend_paged"``, the name matches too.
TRIPLE = re.compile(r" = \(f32\[(\d+),(\d+),\d+\][^ ]* "
                    r"f32\[\1,\2,1\][^ ]* f32\[\1,\2,1\]")


def is_kernel(op: str) -> bool:
    if trace.op_name(op) == "decode_attend_paged":
        return True
    return "tpu_custom_call" in op and bool(TRIPLE.search(op))


def read(rec):
    if rec.trace is None:
        return None
    sec, n = trace.kernel_time(rec.trace, is_kernel)
    g = rec.geometry
    if not n or not g.get("page_bytes"):
        return None
    least = flops.attend_least_s(rec.dims, rec.traced_steps(),
                                 g["page_bytes"], g["block"], rec.peaks)
    return 100.0 * least / sec
