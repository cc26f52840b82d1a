"""mla_attn_roofline.longgen: the latent-attention decode kernel's
required time per call (the absorbed queries, each live latent row once,
the output; ``bench/work_mla.mla_attention_call``) over its measured
device time per call, in %.  The kernel's ops are named
``mla_decode_attention``."""

import numpy as np

from bench import work_mla
from bench.trace_reduce import op_time

KERNEL = "mla_decode_attention"


def read(rec):
    n, secs = op_time(rec["reduced"], KERNEL)
    live = [x for x in rec["decode_live"] if x]
    if not n or not live:
        return None
    need = [work_mla.roofline_s(*work_mla.mla_attention_call(rec["model"], x),
                                rec["peaks"])[0] for x in live]
    return float(np.mean(need)) / (secs / n) * 100.0
