"""decode_mfu.longgen: the decode step's required time at the chip's peaks
(the larger of its FLOPs and bytes, ``bench/work_mla.decode_step``) over
its measured device time, in %."""

import numpy as np

from bench import work_mla
from bench.readers import DECODE
from bench.trace_reduce import module_time


def read(rec):
    n, secs = module_time(rec["reduced"], DECODE)
    live = [x for x in rec["decode_live"] if x]
    if not n or not live:
        return None
    need = [work_mla.roofline_s(*work_mla.decode_step(rec["model"], x),
                                rec["peaks"]) for x in live]
    memory = sum(1 for _, b in need if b == "memory")
    rec.setdefault("notes", []).append(
        f"decode_mfu: {memory} of {len(need)} decode steps bound by "
        f"memory, the rest by compute")
    return float(np.mean([t for t, _ in need])) / (secs / n) * 100.0
