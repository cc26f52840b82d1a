"""Batched serving example: prefill a request batch, decode with a donated
KV cache; works for every family (dense GQA, MoE, xLSTM O(1)-state, ...).

    PYTHONPATH=src python examples/serve_batch.py --arch xlstm-1.3b-smoke

Speculative decoding rides the same entry point: ``--spec-k 4`` drafts 4
tokens per slot from each request's own history and verifies them in one
step (``--spec-k auto`` lets the tuner pick from the trace's measured
repetitiveness).  Streams are bit-identical to ``--spec-k 0``; on a
repetitive trace the accepted-tokens/verify-step figure printed below
clears 1 and decode finishes in fewer steps:

    PYTHONPATH=src python examples/serve_batch.py \
        --arch picolm-4-smoke --trace repetitive --decode 48 --spec-k 4
"""

import argparse

from repro.launch.serve import TRACES, serve_main


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="deepseek-7b-smoke")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prefill", type=int, default=64)
    p.add_argument("--decode", type=int, default=16)
    p.add_argument("--trace", choices=TRACES, default="uniform")
    p.add_argument("--spec-k", default="0",
                   help="draft tokens per verify step (0=off, 'auto'=tuner)")
    a = p.parse_args()
    spec_k = None if a.spec_k == "auto" else int(a.spec_k)
    out = serve_main(arch=a.arch, batch=a.batch, prefill_len=a.prefill,
                     decode_tokens=a.decode, trace=a.trace,
                     spec_k=spec_k)
    msg = (f"\n{a.arch}: {out['decode_tok_per_s']:.1f} decode tok/s "
           f"(batch={a.batch}); first tokens of request 0: {out['sample']}")
    if out.get("spec_verify_steps"):
        msg += (f"\nspeculative: k={out['spec_k']}, "
                f"{out['accepted_per_verify']:.2f} tokens/verify-step "
                f"({out['spec_accepted_tokens']}/{out['spec_drafted_tokens']}"
                f" drafts accepted over {out['spec_verify_steps']} verifies)")
    print(msg)


if __name__ == "__main__":
    main()
