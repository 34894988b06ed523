"""Kernels: the changed-block mask's share of its HBM roofline.  Least bytes:
both leaves read once (2 x blocks x 4 KiB, blocks read from each mask call's
operand shape in the trace); time: device time of the mask programs."""

from bench import trace


def read(w):
    if w.trace is None:
        return None
    blocks = trace.op_block_counts(w.trace.ops, "changed_block_mask")
    seconds = w.trace.program_seconds("jit_changed_block_mask")
    return trace.roofline_share(sum(map(trace.block_diff_bytes, blocks)), seconds,
                                w.peaks["hbm_bytes_per_s"])
