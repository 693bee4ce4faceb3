"""Combine kernel (``kernels/segment_combine``, the program's Pallas
kernel): share of the memory roofline.

The least time is the bytes the combine needs over the chip's HBM
bandwidth (``peaks.json``); the kernel is memory-bound, having no matrix
work.  Each superstep of both jobs combines every edge once (PageRank:
the Ch_msg and mirror fan-out lanes; S-V: the neighbour-minimum
broadcast), so the bytes are 8 per edge lane (a 4-byte value and a
4-byte destination index) plus 4 per vertex that receives a message (its
combined output), times the supersteps of the traced jobs: a lower bound
whatever implements the combine.  The time is the summed device time of
the kernel's events: the custom calls whose target is
``tpu_custom_call``, the one Pallas kernel on the program's path.
Returns None where the trace shows no such op."""
UNIT = "%"
MARKER = 'custom_call_target="tpu_custom_call"'


def is_kernel(ev) -> bool:
    return MARKER in ev[0]


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    if t is None or peaks is None:
        return None
    kernel_s = sum(e - s for _, s, e, ev in t["ops"] if is_kernel(ev)) / 1e9
    steps = sum(j["supersteps"] for j in rec["jobs"])
    if not kernel_s or not steps:
        return None
    g = rec["graph"]
    need = steps * (8 * g["m"] + 4 * g["n_recv"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / kernel_s
