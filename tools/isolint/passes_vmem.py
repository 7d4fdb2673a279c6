"""Pass 3 — Pallas kernel budget and compiled-path lints.

For every ``pallas_call`` site the pass statically derives the per-grid-
step VMEM footprint:

    sum(in_spec block bytes) + sum(out_spec block bytes) + scratch bytes

BlockSpec shape expressions are evaluated symbolically: module-level int
constants (including ones imported from other repo modules, e.g.
``permcheck.ENTRY_TILE`` reused by ``fabric_egress``), enclosing-function
assignments and parameter defaults, and — for genuinely dynamic dims like
a padded shard's entry count — the architectural worst-case bindings in
``config.WORST_CASE_DIMS`` (``np_`` -> MAX_ENTRIES, ``h`` -> 255 hosts,
...).  Output dtypes come from the paired ``jax.ShapeDtypeStruct``;
operand dtypes are not statically visible on a BlockSpec, so inputs assume
``config.DEFAULT_ITEMSIZE`` (4 B — every egress kernel here moves 32-bit
words).  When the call is marked ``dimension_semantics`` *parallel*,
Mosaic double-buffers the operand stream, so the gated figure is
``2 x (in + out) + scratch``.

Specs bound to local names or built by a local helper are followed to
their ``pl.BlockSpec`` call, ``grid_spec=`` calls (scalar prefetch) are
read like top-level keywords, and blocks placed in SMEM count zero bytes
(Mosaic's own verdict on SMEM comes from tests/test_chip_compile.py).

A site whose ``in_specs`` variable has several branch-dependent
assignments (the flat/hier/adaptive permcheck variants) yields one table
row per variant, labelled by the branch's compared constant.

Side lints at each site / file:

  * ``interpret-hardcoded`` — ``interpret=True`` as a call literal or a
    wrapper parameter default: the kernel can never compile, so every
    "speedup" it reports is interpreter arithmetic;
  * ``missing-dimension-semantics`` — a gridded call that can compile but
    never tells Mosaic which grid dims are parallel (no double buffering,
    no cross-step overlap);
  * ``closure-captured-operand`` — ``jax.jit(lambda ...)`` whose body
    captures an array built in the enclosing scope: XLA constant-folds it,
    so the measured path is not the shipped path (the PR 6 bug class).
"""
from __future__ import annotations

import ast
import pathlib

from tools.isolint import config
from tools.isolint.astutil import (call_name, dotted_name, function_scopes,
                                   name_root, parent_map, scope_nodes)
from tools.lintlib import Finding

RULE_BUDGET = "vmem-budget"
RULE_UNRESOLVED = "vmem-unresolved"
RULE_INTERPRET = "interpret-hardcoded"
RULE_DIMSEM = "missing-dimension-semantics"
RULE_CLOSURE = "closure-captured-operand"


# ---------------------------------------------------------------------------
# Symbolic int evaluation
# ---------------------------------------------------------------------------

class _Unresolved(Exception):
    def __init__(self, name: str):
        super().__init__(name)
        self.name = name


def _eval_int(node: ast.AST, env: dict[str, int]) -> int:
    """Evaluate an int-valued shape expression under `env`; raises
    `_Unresolved(name)` at the first unknown symbol."""
    if isinstance(node, ast.Constant):
        if isinstance(node.value, bool) or not isinstance(node.value, int):
            raise _Unresolved(repr(node.value))
        return node.value
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _Unresolved(node.id)
    if isinstance(node, ast.BinOp):
        a = _eval_int(node.left, env)
        b = _eval_int(node.right, env)
        op = type(node.op)
        table = {ast.Add: lambda: a + b, ast.Sub: lambda: a - b,
                 ast.Mult: lambda: a * b, ast.FloorDiv: lambda: a // b,
                 ast.Mod: lambda: a % b, ast.Pow: lambda: a ** b,
                 ast.LShift: lambda: a << b, ast.RShift: lambda: a >> b}
        if op in table:
            return table[op]()
        raise _Unresolved(ast.dump(node.op))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_int(node.operand, env)
    if isinstance(node, ast.Call):
        name = call_name(node)
        if name in ("min", "max") and not node.keywords:
            vals = [_eval_int(a, env) for a in node.args]
            return min(vals) if name == "min" else max(vals)
        if name == "int" and len(node.args) == 1:
            return _eval_int(node.args[0], env)
        raise _Unresolved(name or "<call>")
    if isinstance(node, ast.Attribute):
        raise _Unresolved(dotted_name(node) or node.attr)
    raise _Unresolved(type(node).__name__)


def _module_consts(tree: ast.Module, root: pathlib.Path, path: str,
                   _cache: dict | None = None,
                   _depth: int = 0) -> dict[str, int]:
    """Module-level int constants, following ``from repro.x import NAME``
    imports into the source tree (depth-limited, memoized)."""
    cache = _cache if _cache is not None else {}
    if path in cache:
        return cache[path]
    env: dict[str, int] = {}
    cache[path] = env
    if _depth < 3:
        for node in tree.body:
            if not isinstance(node, ast.ImportFrom) or node.level:
                continue
            mod = node.module or ""
            top = mod.split(".")[0]
            if top not in config.MODULE_ROOTS:
                continue
            rel = config.MODULE_ROOTS[top] + "/" + \
                "/".join(mod.split(".")[1:]) + ".py"
            src = root / rel
            if not src.exists():
                continue
            try:
                sub = ast.parse(src.read_text())
            except SyntaxError:
                continue
            sub_env = _module_consts(sub, root, rel, cache, _depth + 1)
            for alias in node.names:
                if alias.name in sub_env:
                    env[alias.asname or alias.name] = sub_env[alias.name]
    # two fixpoint rounds: module constants defined in terms of each other
    for _ in range(2):
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                try:
                    env[node.targets[0].id] = _eval_int(node.value, env)
                except _Unresolved:
                    pass
    return env


def _function_env(fn: ast.AST, module_env: dict[str, int]) -> dict[str, int]:
    """module env + the function's parameter defaults + every simple local
    assignment that evaluates, iterated to a small fixpoint."""
    env = dict(module_env)
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        args = fn.args
        pos = args.posonlyargs + args.args
        for arg, default in zip(pos[len(pos) - len(args.defaults):],
                                args.defaults):
            try:
                env[arg.arg] = _eval_int(default, env)
            except _Unresolved:
                pass
        for arg, default in zip(args.kwonlyargs, args.kw_defaults):
            if default is not None:
                try:
                    env[arg.arg] = _eval_int(default, env)
                except _Unresolved:
                    pass
    for _ in range(3):
        for node in scope_nodes(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                try:
                    # a local derived from a dynamic dim (rows = sb // 128)
                    # takes that dim's worst-case binding
                    env[node.targets[0].id] = _eval_int(
                        node.value, {**config.WORST_CASE_DIMS, **env})
                except _Unresolved:
                    pass
    return env


def _eval_dim(node: ast.AST, env: dict[str, int]) -> int:
    """A single block dim: the function env first, then the architectural
    worst-case bindings for dynamic symbols."""
    try:
        return _eval_int(node, env)
    except _Unresolved as e:
        if e.name in config.WORST_CASE_DIMS:
            return config.WORST_CASE_DIMS[e.name]
        raise


# ---------------------------------------------------------------------------
# BlockSpec / out_shape / scratch parsing
# ---------------------------------------------------------------------------

def _resolve_list(node: ast.AST, fn: ast.AST) -> list[list[ast.AST]]:
    """Resolve a spec-list expression to one or more candidate element
    lists (one per branch-dependent assignment of a Name)."""
    if isinstance(node, (ast.List, ast.Tuple)):
        return [list(node.elts)]
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        lefts = _resolve_list(node.left, fn)
        rights = _resolve_list(node.right, fn)
        return [lt + rt for lt in lefts for rt in rights]
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult) \
            and isinstance(node.right, ast.Constant):   # [spec] * 3
        return [v * node.right.value for v in _resolve_list(node.left, fn)]
    if isinstance(node, ast.Name):
        variants = []
        for n in scope_nodes(fn):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name) \
                    and n.targets[0].id == node.id:
                variants.extend(_resolve_list(n.value, fn))
        return variants
    return [[node]]        # single spec object (out_specs may be bare)


def _variant_labels(name_node: ast.AST, fn: ast.AST) -> list[str]:
    """Labels for a Name's branch-dependent assignments: the string
    constant its enclosing ``if`` compares against, else ``branch@line``."""
    if not isinstance(name_node, ast.Name):
        return [""]
    parents = parent_map(fn)
    labels = []
    for n in scope_nodes(fn):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name) \
                and n.targets[0].id == name_node.id:
            label = f"branch@{n.lineno}"
            cur = parents.get(n)
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if isinstance(cur, ast.If):
                    consts = [c.value for c in ast.walk(cur.test)
                              if isinstance(c, ast.Constant)
                              and isinstance(c.value, str)]
                    if consts and n in ast.walk(cur):
                        in_body = any(n is x or n in ast.walk(x)
                                      for x in cur.body)
                        label = consts[0] if in_body else label
                        break
                cur = parents.get(cur)
            labels.append(label)
    return labels or [""]


def _local_def(fn: ast.AST, name: str):
    """The single local assignment value or nested ``def`` bound to
    `name` in `fn`, else None."""
    found = [n.value for n in scope_nodes(fn)
             if isinstance(n, ast.Assign) and len(n.targets) == 1
             and isinstance(n.targets[0], ast.Name)
             and n.targets[0].id == name]
    found += [n for n in ast.walk(fn)
              if isinstance(n, ast.FunctionDef) and n.name == name
              and n is not fn]
    return found[0] if len(found) == 1 else None


def _resolve_spec(spec: ast.AST, fn: ast.AST, env: dict[str, int]):
    """Follow a spec bound to a local name (``block = pl.BlockSpec(...)``)
    or built by a local helper (``shard_row(np_)``, whose parameters bind
    to the call's evaluated arguments) down to its BlockSpec call."""
    for _ in range(4):
        if isinstance(spec, ast.Name):
            bound = _local_def(fn, spec.id)
            if bound is None or isinstance(bound, ast.FunctionDef):
                break
            spec = bound
        elif isinstance(spec, ast.Call) and isinstance(spec.func, ast.Name):
            helper = _local_def(fn, spec.func.id)
            if not isinstance(helper, ast.FunctionDef):
                break
            rets = [n.value for n in ast.walk(helper)
                    if isinstance(n, ast.Return) and n.value is not None]
            if len(rets) != 1:
                break
            env = dict(env)
            for arg, val in zip(helper.args.args, spec.args):
                try:
                    env[arg.arg] = _eval_dim(val, env)
                except _Unresolved:
                    pass          # surfaces only if the block shape needs it
            spec = rets[0]
        else:
            break
    return spec, env


def _in_smem(spec: ast.Call) -> bool:
    return any(k.arg == "memory_space" and dotted_name(k.value) is not None
               and dotted_name(k.value).endswith("SMEM")
               for k in spec.keywords)


def _block_bytes(spec: ast.AST, env: dict[str, int], itemsize: int,
                 fn: ast.AST | None = None) -> int:
    """VMEM bytes of one BlockSpec's block: prod(shape) * itemsize, 0 for a
    block placed in SMEM.  A spec that does not resolve to a BlockSpec
    call raises _Unresolved."""
    if fn is not None:
        spec, env = _resolve_spec(spec, fn, env)
    if not isinstance(spec, ast.Call):
        raise _Unresolved(ast.dump(spec)[:40])
    if _in_smem(spec):
        return 0
    shape = None
    if spec.args:
        shape = spec.args[0]
    for kw in spec.keywords:
        if kw.arg == "block_shape":
            shape = kw.value
    if not isinstance(shape, (ast.Tuple, ast.List)):
        raise _Unresolved("block_shape")
    n = 1
    for dim in shape.elts:
        if isinstance(dim, ast.Constant) and dim.value is None:
            continue                       # None dim = full axis mapped once
        n *= _eval_dim(dim, env)
    return n * itemsize


def _dtype_bytes(node: ast.AST) -> int:
    """Itemsize of a ``jnp.<dtype>`` attribute, else the default."""
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    return config.DTYPE_BYTES.get(name or "", config.DEFAULT_ITEMSIZE)


def _call_kwargs(call: ast.Call) -> dict[str, ast.AST]:
    """The pallas_call's keywords, with those of a ``grid_spec=`` call
    (``pltpu.PrefetchScalarGridSpec(grid=..., in_specs=..., ...)``)
    lifted to the top level."""
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    gs = kw.get("grid_spec")
    if isinstance(gs, ast.Call):
        kw.update({k.arg: k.value for k in gs.keywords if k.arg})
    return kw


def _out_entries(call: ast.Call, fn: ast.AST):
    """Pair out_specs with out_shape dtypes, returning
    ``[(spec_node, itemsize), ...]`` (dtype defaulting when unpaired)."""
    kw = _call_kwargs(call)
    specs_node = kw.get("out_specs")
    shapes_node = kw.get("out_shape")
    specs = _resolve_list(specs_node, fn)[0] if specs_node is not None else []
    shapes = (_resolve_list(shapes_node, fn)[0]
              if shapes_node is not None else [])
    sizes = []
    for sh in shapes:
        if isinstance(sh, ast.Call):
            args = list(sh.args) + [k.value for k in sh.keywords]
            sizes.append(_dtype_bytes(args[1]) if len(args) > 1
                         else config.DEFAULT_ITEMSIZE)
        else:
            sizes.append(config.DEFAULT_ITEMSIZE)
    out = []
    for i, spec in enumerate(specs):
        out.append((spec, sizes[i] if i < len(sizes)
                    else config.DEFAULT_ITEMSIZE))
    return out


def _scratch_bytes(call: ast.Call, env: dict[str, int]) -> int:
    """Total bytes of ``scratch_shapes`` VMEM allocations."""
    node = _call_kwargs(call).get("scratch_shapes")
    if node is None:
        return 0
    if not isinstance(node, (ast.List, ast.Tuple)):
        raise _Unresolved("scratch_shapes")
    total = 0
    for el in node.elts:
        if not isinstance(el, ast.Call):
            raise _Unresolved("scratch entry")
        shape = el.args[0] if el.args else None
        dtype = el.args[1] if len(el.args) > 1 else None
        if not isinstance(shape, (ast.Tuple, ast.List)):
            raise _Unresolved("scratch shape")
        n = 1
        for dim in shape.elts:
            n *= _eval_dim(dim, env)
        total += n * _dtype_bytes(dtype)
    return total


def _has_dimension_semantics(call: ast.Call) -> tuple[bool, bool]:
    """(mentions dimension_semantics, any dim marked "parallel").  A call
    to one of ``config.COMPILER_PARAMS_HELPERS`` passes its string
    arguments as the semantics."""
    mentions = parallel = False
    for node in ast.walk(call):
        if isinstance(node, ast.Call) and \
                call_name(node) in config.COMPILER_PARAMS_HELPERS:
            mentions = True
            parallel |= any(isinstance(a, ast.Constant) and
                            a.value == "parallel" for a in node.args)
        if isinstance(node, ast.keyword) and \
                node.arg == "dimension_semantics":
            mentions = True
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and c.value == "parallel":
                    parallel = True
        if isinstance(node, ast.Constant) and \
                node.value == "dimension_semantics":
            mentions = True
    return mentions, parallel


def _interpret_literal_true(call: ast.Call) -> bool:
    for k in call.keywords:
        if k.arg == "interpret" and isinstance(k.value, ast.Constant) \
                and k.value.value is True:
            return True
    return False


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------

def analyze_file(tree: ast.Module, path: str, root: pathlib.Path,
                 *, budget: int):
    """(findings, vmem_rows) for one file."""
    findings: list[Finding] = []
    rows: list[dict] = []
    module_env = _module_consts(tree, root, path)

    # hardcoded interpret=True parameter defaults on kernel wrappers
    for scope, qual in function_scopes(tree):
        if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        args = scope.args
        pairs = list(zip((args.posonlyargs + args.args)[
            len(args.posonlyargs + args.args) - len(args.defaults):],
            args.defaults))
        pairs += [(a, d) for a, d in zip(args.kwonlyargs, args.kw_defaults)
                  if d is not None]
        for arg, default in pairs:
            if arg.arg == "interpret" and \
                    isinstance(default, ast.Constant) and \
                    default.value is True:
                findings.append(Finding(
                    RULE_INTERPRET, path, scope.lineno,
                    f"`{qual}` defaults interpret=True — the kernel never "
                    f"compiles; default to None + resolve_interpret",
                    key=f"{qual}:default"))

    # pallas_call sites
    for scope, qual in function_scopes(tree):
        for call in [n for n in scope_nodes(scope)
                     if isinstance(n, ast.Call)
                     and call_name(n) == "pallas_call"]:
            env = _function_env(scope, module_env)
            kw = _call_kwargs(call)
            literal_interp = _interpret_literal_true(call)
            if literal_interp:
                findings.append(Finding(
                    RULE_INTERPRET, path, call.lineno,
                    f"pallas_call in `{qual}` hardcodes interpret=True",
                    key=f"{qual}:call"))
            mentions, parallel = _has_dimension_semantics(call)
            if "grid" in kw and not mentions and not literal_interp:
                findings.append(Finding(
                    RULE_DIMSEM, path, call.lineno,
                    f"compiled-path pallas_call in `{qual}` has a grid but "
                    f"no dimension_semantics (no double buffering)",
                    key=f"{qual}:dimsem"))

            in_node = kw.get("in_specs")
            in_variants = (_resolve_list(in_node, scope)
                           if in_node is not None else [[]])
            labels = (_variant_labels(in_node, scope)
                      if in_node is not None else [""])
            if len(labels) != len(in_variants):
                labels = [f"v{i}" for i in range(len(in_variants))]
            out_entries = _out_entries(call, scope)
            for label, specs in zip(labels, in_variants):
                row = {"path": path, "line": call.lineno, "kernel": qual,
                       "variant": label, "budget_bytes": budget}
                try:
                    in_b = sum(_block_bytes(s, env, config.DEFAULT_ITEMSIZE,
                                            scope) for s in specs)
                    out_b = sum(_block_bytes(s, env, isz, scope)
                                for s, isz in out_entries)
                    scr_b = _scratch_bytes(call, env)
                except _Unresolved as e:
                    findings.append(Finding(
                        RULE_UNRESOLVED, path, call.lineno,
                        f"pallas_call in `{qual}` ({label or 'single'}): "
                        f"cannot resolve `{e.name}` — add it to "
                        f"WORST_CASE_DIMS or simplify the spec",
                        key=f"{qual}:{label}:{e.name}"))
                    row["unresolved"] = e.name
                    rows.append(row)
                    continue
                per_step = in_b + out_b + scr_b
                buffered = (2 * (in_b + out_b) + scr_b
                            if parallel else per_step)
                row.update({
                    "in_bytes": in_b, "out_bytes": out_b,
                    "scratch_bytes": scr_b, "per_step_bytes": per_step,
                    "double_buffered": parallel,
                    "gated_bytes": buffered,
                    "within_budget": buffered <= budget,
                })
                rows.append(row)
                if buffered > budget:
                    findings.append(Finding(
                        RULE_BUDGET, path, call.lineno,
                        f"pallas_call in `{qual}` ({label or 'single'}) "
                        f"needs {buffered} B VMEM per grid step "
                        f"(budget {budget} B)",
                        key=f"{qual}:{label}"))

    # jax.jit(lambda ...) closure captures
    findings += _closure_findings(tree, path)
    return findings, rows


def _array_producers(scope: ast.AST) -> set[str]:
    """Names in `scope` bound from array-producing expressions."""
    names: set[str] = set()
    for node in scope_nodes(scope):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        root = name_root(call.func)
        name = call_name(call)
        if root in config.ARRAY_PRODUCER_ROOTS or \
                name in config.ARRAY_PRODUCER_CALLS:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


def _lambda_free_names(lam: ast.Lambda) -> set[str]:
    bound = {a.arg for a in (lam.args.posonlyargs + lam.args.args
                             + lam.args.kwonlyargs)}
    if lam.args.vararg:
        bound.add(lam.args.vararg.arg)
    if lam.args.kwarg:
        bound.add(lam.args.kwarg.arg)
    return {n.id for n in ast.walk(lam.body)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
            and n.id not in bound}


def _closure_findings(tree: ast.Module, path: str) -> list[Finding]:
    out: list[Finding] = []
    for scope, qual in function_scopes(tree):
        producers = _array_producers(scope)
        if not producers:
            continue
        for node in scope_nodes(scope):
            if not (isinstance(node, ast.Call)
                    and call_name(node) == "jit" and node.args
                    and isinstance(node.args[0], ast.Lambda)):
                continue
            captured = sorted(_lambda_free_names(node.args[0]) & producers)
            for name in captured:
                out.append(Finding(
                    RULE_CLOSURE, path, node.lineno,
                    f"jax.jit(lambda ...) in `{qual}` closure-captures "
                    f"array `{name}` — XLA constant-folds it; pass it as "
                    f"a runtime operand",
                    key=f"{qual}:{name}"))
    return out
