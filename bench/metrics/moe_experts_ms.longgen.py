"""moe_experts_ms.longgen: device ms per decode step in ops under the
program's ``layers/moe/experts`` scope (the held routed experts), from
the decode step's per-scope times (``rec["scopes"]``, which
``bench/drivers/serve_mla.py`` sets in a traced run)."""


def read(rec):
    sc = rec.get("scopes")
    if not sc:
        return None
    t = [v for path, v in sc["scopes"].items()
         if "experts" in path.split("/")]
    return sum(t) * 1e3 if t else None
