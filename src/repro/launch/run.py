"""Command dispatcher for EASEY execution specs (paper §3: execution
commands are 'bash (serial) or mpi-based'; ours are train/serve/lulesh)."""

from __future__ import annotations

import shlex
from pathlib import Path


def run_command(command: str, job=None, workdir: Path | None = None,
                spec=None, build_result=None):
    log = job.log if job is not None else print
    argv = shlex.split(command)
    # strip ch-run wrappers if a paper-style command was given
    if argv and argv[0] == "ch-run":
        # ch-run -b src:dst image -- cmd args...
        if "--" in argv:
            argv = argv[argv.index("--") + 1:]
    name = Path(argv[0]).name if argv else ""

    if name.startswith("train"):
        from repro.launch.train import train_main
        kw = _parse_kw(argv[1:])
        ckpt = kw.get("ckpt-dir")
        if ckpt is None and workdir is not None:
            ckpt = str(workdir / "ckpt")
        return train_main(
            arch=kw.get("arch", _arch_from(build_result, "deepseek-7b-smoke")),
            steps=int(kw.get("steps", 10)),
            seq_len=int(kw.get("seq-len", 64)),
            global_batch=int(kw.get("global-batch", 4)),
            ckpt_dir=ckpt, ckpt_every=int(kw.get("ckpt-every", 5)),
            log=log)
    if name.startswith("serve"):
        from repro.launch.serve import serve_main
        kw = _parse_kw(argv[1:])
        return serve_main(
            arch=kw.get("arch", _arch_from(build_result, "deepseek-7b-smoke")),
            batch=int(kw.get("batch", 4)),
            prefill_len=int(kw.get("prefill", 64)),
            decode_tokens=int(kw.get("decode", 8)),
            mode=kw.get("mode", "continuous"),
            requests=int(kw.get("requests", 0)),
            max_len=int(kw.get("max-len", 0)),
            page_size=int(kw.get("page-size", 0)),
            temperature=float(kw.get("temperature", 0.0)),
            top_k=int(kw.get("top-k", 0)),
            replicas=int(kw.get("replicas", 1)),
            route_policy=kw.get("route-policy", "least_loaded"),
            prefix_cache=str(kw.get("prefix-cache", "")).lower()
            in ("true", "1", "yes"),
            trace=kw.get("trace", "uniform"), log=log)
    if "lulesh" in name:
        import time
        from repro.models import lulesh
        kw = _parse_kw(argv[1:])
        iters = int(kw.get("i", kw.get("iters", 10)))
        size = int(kw.get("s", kw.get("size", 16)))
        cfg = lulesh.LuleshConfig(grid=size, iters=iters)
        state = lulesh.init_state(cfg)
        t0 = time.perf_counter()
        state = lulesh.run(state, cfg, iters)
        state["e"].block_until_ready()
        dt = time.perf_counter() - t0
        f = lulesh.fom(size ** 3, iters, dt)
        log(f"[lulesh] grid={size}^3 iters={iters} time={dt:.3f}s FOM={f:,.0f}")
        return {"fom": f, "seconds": dt, "grid": size, "iters": iters}
    raise ValueError(f"unknown EASEY command: {command!r}")


def _parse_kw(argv: list[str]) -> dict:
    kw, i = {}, 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--"):
            key = a[2:]
            if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
                kw[key] = argv[i + 1]
                i += 2
            else:
                kw[key] = "true"
                i += 1
        elif a.startswith("-") and len(a) == 2:
            kw[a[1:]] = argv[i + 1] if i + 1 < len(argv) else "true"
            i += 2
        else:
            i += 1
    return kw


def _arch_from(build_result, default):
    if build_result is not None:
        return build_result.appspec.arch
    return default
