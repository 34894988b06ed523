"""Kernels: ``_compact``'s share of its HBM roofline.  Least bytes: each
changed block read once and written once (2 x 4 KiB x ``changed_blocks``,
summed from the ``delta.encode_delta`` spans' counter); time: device time
of the ``jit__compact`` programs."""

from bench import trace


def read(w):
    if w.trace is None:
        return None
    changed = sum(s.attrs.get("changed_blocks", 0) for s in w.spans
                  if s.name == "delta.encode_delta")
    return trace.roofline_share(2 * trace.BLOCK_BYTES * changed,
                                w.trace.program_seconds("jit__compact"),
                                w.peaks["hbm_bytes_per_s"])
