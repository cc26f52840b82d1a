"""Rule ``vmem-budget``: Pallas kernels must fit the target's VMEM.

For every module-level function containing a ``pl.pallas_call``, the
rule statically sums the VMEM-resident bytes its block shapes imply:

* each lexical ``pl.BlockSpec((dims...), ...)`` site contributes
  ``prod(dims) * 4`` bytes (input dtypes are unknown statically — f32 is
  the conservative assumption), doubled for the pipeline's
  double-buffering; a ``[BlockSpec(...)] * N`` list-multiply counts N
  copies;
* each ``pltpu.VMEM((dims...), dtype)`` scratch shape contributes
  ``prod(dims) * sizeof(dtype)`` once (scratch is not double-buffered).

A ``None`` block dimension is squeezed (one element).  Other
dimensions resolve through, in order: constant-propagated local
assignments (``bx = min(block_x, n)`` resolves because ``min`` of the
resolvable subset is a sound upper bound), the function's own integer
keyword defaults (``block_q: int = 128``), and the declared bounds table
(``[vmem.bounds]`` in ``allow.toml``) for free model dimensions like
``dh`` or ``page_size``.  A dimension that resolves through none of
them is a *dynamically-shaped block* — an error, because an unbounded
block is exactly how a kernel silently outgrows VMEM when a config
scales.

The budget comes from ``core/tuning.vmem_budget_bytes`` over the
``[vmem] target`` in ``allow.toml`` (falling back to the same fraction
of ``TargetSpec.vmem_bytes`` when JAX is unavailable — kept in sync by
test).  Every kernel gets an ``info`` finding reporting its estimate;
crossing the budget is an ``error``.

The estimate is lexical: a BlockSpec built in a helper and passed N
times through runtime list construction counts once.  It is a floor,
not an exact occupancy — the point is catching order-of-magnitude
inflation at review time, not replacing the compiler.
"""

from __future__ import annotations

import ast
import math

from repro.analysis.lint.core import Finding, Source, dotted

# fallback when core/tuning is unimportable (no JAX in the venv);
# test_lint asserts this equals tuning.VMEM_BUDGET_FRACTION
VMEM_BUDGET_FRACTION = 0.9

DTYPE_BYTES = {"float32": 4, "f32": 4, "int32": 4, "uint32": 4,
               "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
               "int8": 1, "uint8": 1, "bool_": 1, "float64": 8,
               "int64": 8}

HINT = ("shrink the block shape, add the free dimension to "
        "[vmem.bounds] in allow.toml, or raise the target budget "
        "knowingly — VMEM overflows surface as compile failures on "
        "real TPUs only")


def _budget_bytes(target_name: str) -> float:
    from repro.core.target import get_target
    t = get_target(target_name)
    try:
        from repro.core.tuning import vmem_budget_bytes
        return vmem_budget_bytes(t)
    except Exception:
        return VMEM_BUDGET_FRACTION * t.vmem_bytes


class _Unresolved(Exception):
    def __init__(self, why: str):
        super().__init__(why)
        self.why = why


def _eval_dim(node: ast.AST, env: dict) -> int:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Constant) and node.value is None:
        return 1                   # a squeezed block dim: one element
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _Unresolved(f"unbounded dimension `{node.id}`")
    if isinstance(node, ast.BinOp):
        ops = {ast.Add: lambda a, b: a + b, ast.Sub: lambda a, b: a - b,
               ast.Mult: lambda a, b: a * b,
               ast.FloorDiv: lambda a, b: a // max(b, 1),
               ast.Pow: lambda a, b: a ** b}
        fn = ops.get(type(node.op))
        if fn is not None:
            return fn(_eval_dim(node.left, env), _eval_dim(node.right, env))
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) and \
            node.func.id in ("min", "max") and node.args:
        vals, missing = [], 0
        for a in node.args:
            try:
                vals.append(_eval_dim(a, env))
            except _Unresolved:
                missing += 1
        if node.func.id == "min" and vals:
            return min(vals)       # min over a subset is an upper bound
        if node.func.id == "max" and vals and not missing:
            return max(vals)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_dim(node.operand, env)
    raise _Unresolved(f"dimension `{ast.unparse(node)}` is not statically "
                      f"evaluable")


def _fn_env(fn, bounds: dict) -> dict:
    env = dict(bounds)
    a = fn.args
    pos = a.posonlyargs + a.args
    for arg, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if isinstance(default, ast.Constant) and \
                isinstance(default.value, int):
            env[arg.arg] = default.value
    for arg, default in zip(a.kwonlyargs, a.kw_defaults):
        if isinstance(default, ast.Constant) and \
                isinstance(default.value, int):
            env[arg.arg] = default.value
    # one forward constant-propagation pass over simple top-level
    # assigns.  Because the estimate only needs an *upper bound*, a name
    # is propagatable when it has exactly one plain assignment and every
    # other store is a shrinking AugAssign (`br -= 1`, `bk //= 2`):
    # `rows = 1` followed by `rows *= s` in a loop must not freeze rows
    # at 1, but `br = min(block_rows, rows)` stays a bound through the
    # `while rows % br: br -= 1` alignment loop.
    SHRINKING = (ast.Sub, ast.FloorDiv, ast.RShift)
    plain: dict[str, int] = {}        # Name stores outside AugAssign
    growing: set[str] = set()
    aug_targets: set[ast.Name] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.AugAssign) and \
                isinstance(node.target, ast.Name):
            aug_targets.add(node.target)
            if not isinstance(node.op, SHRINKING):
                growing.add(node.target.id)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store) \
                and node not in aug_targets:
            plain[node.id] = plain.get(node.id, 0) + 1
    for st in fn.body:
        if isinstance(st, ast.Assign) and len(st.targets) == 1 and \
                isinstance(st.targets[0], ast.Name) and \
                plain.get(st.targets[0].id) == 1 and \
                st.targets[0].id not in growing:
            try:
                env[st.targets[0].id] = _eval_dim(st.value, env)
            except _Unresolved:
                pass
    return env


def _dtype_bytes(node: ast.AST | None) -> int:
    if node is None:
        return 4
    d = dotted(node)
    if d:
        leaf = d.split(".")[-1]
        return DTYPE_BYTES.get(leaf, 4)
    return 4


class VmemBudgetRule:
    id = "vmem-budget"

    def check(self, src: Source, cfg) -> list[Finding]:
        has_pallas = any(
            isinstance(n, ast.Call) and
            (dotted(n.func) or "").split(".")[-1] == "pallas_call"
            for n in ast.walk(src.tree))
        if not has_pallas:
            return []
        try:
            budget = _budget_bytes(cfg.vmem_target)
        except KeyError as e:
            return [Finding(self.id, src.rel, 1, 0,
                            f"cannot resolve VMEM budget: {e}", hint=HINT)]
        findings: list[Finding] = []
        for node in src.tree.body:
            if isinstance(node, ast.FunctionDef) and any(
                    isinstance(c, ast.Call) and
                    (dotted(c.func) or "").split(".")[-1] == "pallas_call"
                    for c in ast.walk(node)):
                self._check_kernel_fn(node, src, cfg, budget, findings)
        return findings

    def _check_kernel_fn(self, fn, src: Source, cfg, budget: float,
                         findings: list[Finding]) -> None:
        env = _fn_env(fn, cfg.vmem_bounds)
        blockspec_bytes = 0.0
        scratch_bytes = 0.0
        resolved = True

        def site_bytes(call: ast.Call, shape_node, dtype_node, mult: int,
                       kind: str):
            nonlocal blockspec_bytes, scratch_bytes, resolved
            if not isinstance(shape_node, (ast.Tuple, ast.List)):
                resolved = False
                findings.append(Finding(
                    self.id, src.rel, call.lineno, call.col_offset,
                    f"`{fn.name}`: {kind} shape is not a literal tuple — "
                    f"dynamically-shaped blocks defeat the static VMEM "
                    f"check", hint=HINT))
                return
            elems = 1
            for dim in shape_node.elts:
                try:
                    elems *= max(_eval_dim(dim, env), 1)
                except _Unresolved as e:
                    resolved = False
                    findings.append(Finding(
                        self.id, src.rel, dim.lineno, dim.col_offset,
                        f"`{fn.name}`: {kind} has a dynamic block "
                        f"dimension — {e.why}", hint=HINT))
                    return
            nbytes = elems * _dtype_bytes(dtype_node) * mult
            if kind == "scratch":
                scratch_bytes += nbytes
            else:
                blockspec_bytes += nbytes

        def visit(node, mult: int):
            if isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Mult):
                # [BlockSpec(...)] * N — count N copies of each site
                for seq, count in ((node.left, node.right),
                                   (node.right, node.left)):
                    if isinstance(seq, (ast.List, ast.Tuple)) and \
                            isinstance(count, ast.Constant) and \
                            isinstance(count.value, int):
                        visit(seq, mult * count.value)
                        visit(count, mult)
                        return
            if isinstance(node, ast.Call):
                leaf = (dotted(node.func) or "").split(".")[-1]
                if leaf == "BlockSpec":
                    shape = node.args[0] if node.args else None
                    for kw in node.keywords:
                        if kw.arg == "block_shape":
                            shape = kw.value
                    if shape is not None:
                        site_bytes(node, shape, None, mult, "BlockSpec")
                elif leaf == "VMEM":
                    shape = node.args[0] if node.args else None
                    dtype = node.args[1] if len(node.args) > 1 else None
                    site_bytes(node, shape, dtype, mult, "scratch")
            for child in ast.iter_child_nodes(node):
                visit(child, mult)

        visit(fn, 1)
        # in/out blocks are double-buffered by the pallas pipeline
        estimate = 2 * blockspec_bytes + scratch_bytes
        kib = estimate / 1024
        findings.append(Finding(
            self.id, src.rel, fn.lineno, fn.col_offset,
            f"`{fn.name}`: estimated VMEM ~{kib:,.0f} KiB "
            f"(2x{blockspec_bytes / 1024:,.0f} KiB blocks + "
            f"{scratch_bytes / 1024:,.0f} KiB scratch) of "
            f"{budget / 2**20:,.0f} MiB budget on {cfg.vmem_target}"
            + ("" if resolved else " — LOWER BOUND, dynamic dims above"),
            severity="info"))
        if estimate > budget:
            over = estimate / max(budget, 1)
            findings.append(Finding(
                self.id, src.rel, fn.lineno, fn.col_offset,
                f"`{fn.name}`: estimated VMEM {estimate / 2**20:,.1f} MiB "
                f"exceeds the {budget / 2**20:,.0f} MiB budget on "
                f"{cfg.vmem_target} ({over:.1f}x)", hint=HINT))
        if math.isnan(estimate):   # defensive; never expected
            findings.append(Finding(
                self.id, src.rel, fn.lineno, fn.col_offset,
                f"`{fn.name}`: VMEM estimate is NaN", hint=HINT))
