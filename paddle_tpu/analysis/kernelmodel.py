"""Static model of every ``pl.pallas_call`` site (docs/ANALYSIS.md,
kernel-verification section).

Pure ``ast`` like the rest of the package: for each call site the model
recovers — through a flow-insensitive local-variable environment — the
grid (and ``PrefetchScalarGridSpec``), every ``BlockSpec`` with its block
shape and index_map (lambda, local/module ``def``, or a
``functools.partial`` over one), the scalar-prefetch count, scratch
shapes/dtypes, ``out_shape`` ShapeDtypeStructs, ``input_output_aliases``
and the resolved kernel body function.  A small abstract interpreter then
walks each index_map over its grid domain: grid ids are bounded by
construction, constants are exact, and scalar-prefetch table reads are
*unbounded* unless syntactically routed through a clamp
(``jnp.clip``/``minimum``/``maximum``/``where``/``%``) — the idiom every
shipped page map uses, and the thing whose absence is the silent-OOB bug
class (rule PK101).

Everything here degrades to "unknown" rather than guessing: a spec list
built by a helper function, a computed alias dict, or a ``*refs`` kernel
simply opts that call site out of the checks that need the missing piece.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import (FunctionInfo, ModuleInfo, PackageIndex, _last_name,
                        partial_inner, walk_shallow)

#: call names that bound their result (syntactic clamp idioms)
CLAMP_FUNCS = {"clip", "minimum", "maximum", "where", "mod", "remainder"}

#: sub-f32 dtype attribute names (PK104)
SUB_F32_DTYPES = {"bfloat16", "float16", "float8_e4m3fn", "float8_e5m2"}


def unparse(node: ast.AST, limit: int = 60) -> str:
    try:
        s = ast.unparse(node)
    except Exception:  # pragma: no cover - exotic node
        s = type(node).__name__
    s = " ".join(s.split())
    return s if len(s) <= limit else s[: limit - 3] + "..."


def _int_const(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _int_const(node.operand)
        return -v if v is not None else None
    return None


def _seq_elts(node: ast.AST) -> Optional[List[ast.AST]]:
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return None


# ---------------------------------------------------------------------------
# local-variable environment
# ---------------------------------------------------------------------------

class Env:
    """Flow-insensitive name -> value-AST map for one enclosing scope
    chain (module globals, then each enclosing function outer-to-inner,
    so inner bindings win). Tuple-unpacking targets are recorded as
    *unknown* by omission."""

    def __init__(self, mi: ModuleInfo, fi: Optional[FunctionInfo]):
        self.mi = mi
        self.fi = fi
        self.values: Dict[str, ast.AST] = {}
        for node in mi.tree.body:
            self._record(node)
        if fi is not None:
            parts = fi.qualname.split(".")
            for i in range(1, len(parts) + 1):
                qn = ".".join(parts[:i])
                anc = mi.functions.get(qn)
                if anc is not None and not isinstance(anc.node, ast.Lambda):
                    for node in walk_shallow(anc.node):
                        self._record(node)

    def _record(self, node: ast.AST) -> None:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            self.values[node.targets[0].id] = node.value
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            self.values[node.target.id] = node.value

    def resolve(self, node: Optional[ast.AST],
                _depth: int = 0) -> Optional[ast.AST]:
        """Chase simple ``Name`` indirections (bounded)."""
        while isinstance(node, ast.Name) and _depth < 8:
            nxt = self.values.get(node.id)
            if nxt is None or nxt is node:
                break
            node = nxt
            _depth += 1
        return node


# ---------------------------------------------------------------------------
# index maps / block specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IndexMapModel:
    params: List[str]                       # positional, partial-bound removed
    returns: List[List[ast.AST]]            # one list of components per return
    body: List[ast.stmt]                    # statements to scan for clamps
    node: ast.AST                           # the lambda / def AST
    text: str = ""


@dataclasses.dataclass
class BlockSpecModel:
    node: ast.AST                           # the pl.BlockSpec(...) call
    block_shape: Optional[List[ast.AST]]    # None: absent or non-literal
    index_map: Optional[IndexMapModel]      # None: absent or unresolvable
    memory_space: Optional[str] = None      # "ANY"/"SMEM"/... when given
    resolved: bool = True                   # False: element was not a BlockSpec

    @property
    def rank(self) -> Optional[int]:
        return len(self.block_shape) if self.block_shape is not None else None


@dataclasses.dataclass
class KernelCallSite:
    mi: ModuleInfo
    fi: Optional[FunctionInfo]              # enclosing function (innermost)
    call: ast.Call                          # the pl.pallas_call(...) node
    grid_len: Optional[int] = None
    grid_elts: Optional[List[ast.AST]] = None       # grid component exprs
    n_prefetch: int = 0
    in_specs: Optional[List[BlockSpecModel]] = None
    out_specs: Optional[List[BlockSpecModel]] = None
    out_shapes: Optional[List[ast.AST]] = None      # one expr per output
    scratch: Optional[List[ast.AST]] = None
    aliases: Optional[Dict[int, int]] = None
    has_alias_kw: bool = False
    kernel_fi: Optional[FunctionInfo] = None
    kernel_bound_kw: Set[str] = dataclasses.field(default_factory=set)
    kernel_bound_pos: int = 0               # positional args bound via partial
    arg_exprs: Optional[List[ast.AST]] = None       # the (...)(*args) args

    @property
    def line(self) -> int:
        return self.call.lineno

    @property
    def qualname(self) -> str:
        return self.fi.qualname if self.fi is not None else "<module>"

    @property
    def top_qualname(self) -> str:
        """Outermost enclosing def — the certification unit for PK105."""
        return self.qualname.split(".")[0]

    def kernel_positional_params(self) -> Optional[List[str]]:
        """Kernel-ref parameter names in operand order, or None when the
        kernel is unresolved / uses ``*refs``."""
        if self.kernel_fi is None or isinstance(self.kernel_fi.node,
                                                ast.Lambda):
            return None
        a = self.kernel_fi.node.args
        if a.vararg is not None:
            return None
        params = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        params = params[self.kernel_bound_pos:]
        return [p for p in params if p not in self.kernel_bound_kw]


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _lookup_def(mi: ModuleInfo, fi: Optional[FunctionInfo],
                name: str) -> Optional[FunctionInfo]:
    if fi is not None:
        parts = fi.qualname.split(".")
        for i in range(len(parts), -1, -1):
            qn = ".".join(parts[:i] + [name]) if i else name
            if qn in mi.functions:
                return mi.functions[qn]
    return mi.functions.get(name)


def _fn_positional(node: ast.AST) -> List[str]:
    a = node.args
    return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]


def build_index_map(expr: Optional[ast.AST], mi: ModuleInfo,
                    fi: Optional[FunctionInfo],
                    env: Env) -> Optional[IndexMapModel]:
    expr = env.resolve(expr)
    if expr is None:
        return None
    bound_kw: Set[str] = set()
    bound_pos = 0
    inner = partial_inner(expr)
    while inner is not None:
        bound_kw |= {kw.arg for kw in expr.keywords if kw.arg}
        bound_pos += len(expr.args) - 1
        expr = env.resolve(inner)
        inner = partial_inner(expr) if expr is not None else None
    if isinstance(expr, ast.Lambda):
        params = _fn_positional(expr)
        body = expr.body
        comps = list(body.elts) if isinstance(body, ast.Tuple) else [body]
        return IndexMapModel(params=params, returns=[comps],
                             body=[ast.Expr(body)], node=expr,
                             text=unparse(expr))
    if isinstance(expr, ast.Name):
        target = _lookup_def(mi, fi, expr.id)
        if target is None or isinstance(target.node, ast.Lambda):
            return None
        expr = target.node
    if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef)):
        params = [p for p in _fn_positional(expr)[bound_pos:]
                  if p not in bound_kw]
        rets: List[List[ast.AST]] = []
        for node in walk_shallow(expr):
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                rets.append(list(v.elts) if isinstance(v, ast.Tuple)
                            else [v])
        return IndexMapModel(params=params, returns=rets,
                             body=list(expr.body), node=expr,
                             text=unparse(expr.name
                                          if hasattr(expr, "name") else expr))
    return None


def build_block_spec(expr: Optional[ast.AST], mi: ModuleInfo,
                     fi: Optional[FunctionInfo],
                     env: Env) -> Optional[BlockSpecModel]:
    expr = env.resolve(expr)
    if not isinstance(expr, ast.Call) or _last_name(expr.func) != "BlockSpec":
        return (BlockSpecModel(node=expr, block_shape=None, index_map=None,
                               resolved=False)
                if isinstance(expr, ast.AST) else None)
    shape_expr = expr.args[0] if expr.args else _kw(expr, "block_shape")
    map_expr = (expr.args[1] if len(expr.args) > 1
                else _kw(expr, "index_map"))
    mspace = _kw(expr, "memory_space")
    shape = _seq_elts(env.resolve(shape_expr)) if shape_expr is not None \
        else None
    imap = build_index_map(map_expr, mi, fi, env) if map_expr is not None \
        else None
    return BlockSpecModel(node=expr, block_shape=shape, index_map=imap,
                          memory_space=_last_name(mspace)
                          if mspace is not None else None)


def _spec_list(expr: Optional[ast.AST], mi: ModuleInfo,
               fi: Optional[FunctionInfo],
               env: Env) -> Optional[List[BlockSpecModel]]:
    expr = env.resolve(expr)
    if expr is None:
        return None
    elts = _seq_elts(expr)
    if elts is None:
        # a single BlockSpec is a 1-output/1-input spec
        one = build_block_spec(expr, mi, fi, env)
        return [one] if one is not None and one.resolved else None
    out = []
    for e in elts:
        spec = build_block_spec(e, mi, fi, env)
        if spec is None:
            return None
        out.append(spec)
    return out


def _alias_dict(expr: Optional[ast.AST]) -> Optional[Dict[int, int]]:
    if not isinstance(expr, ast.Dict):
        return None
    out: Dict[int, int] = {}
    for k, v in zip(expr.keys, expr.values):
        ki, vi = (_int_const(k) if k is not None else None), _int_const(v)
        if ki is None or vi is None:
            return None
        out[ki] = vi
    return out


def _resolve_kernel(site: KernelCallSite, index: PackageIndex,
                    env: Env) -> None:
    expr = env.resolve(site.call.args[0]) if site.call.args else None
    if expr is None:
        return
    inner = partial_inner(expr)
    while inner is not None:
        site.kernel_bound_kw |= {kw.arg for kw in expr.keywords if kw.arg}
        site.kernel_bound_pos += len(expr.args) - 1
        expr = env.resolve(inner)
        inner = partial_inner(expr) if expr is not None else None
    if isinstance(expr, ast.Name):
        target = _lookup_def(site.mi, site.fi, expr.id)
        if target is not None:
            site.kernel_fi = target
    if site.kernel_fi is None and site.call.args:
        # factory-built kernels (`kern = make_kernel(...)`): the call
        # graph already resolves factory products and partial locals
        keys = index._funcs_from_arg(site.mi, site.fi, site.call.args[0])
        if len(keys) == 1:
            fi = index.functions.get(next(iter(keys)))
            if fi is not None and not isinstance(fi.node, ast.Lambda):
                site.kernel_fi = fi


def _parse_site(mi: ModuleInfo, fi: Optional[FunctionInfo], call: ast.Call,
                outer: Optional[ast.Call],
                index: PackageIndex) -> KernelCallSite:
    env = Env(mi, fi)
    site = KernelCallSite(mi=mi, fi=fi, call=call)
    site.arg_exprs = list(outer.args) if outer is not None else None

    grid_expr = env.resolve(_kw(call, "grid"))
    in_specs_expr = _kw(call, "in_specs")
    out_specs_expr = _kw(call, "out_specs")
    scratch_expr = _kw(call, "scratch_shapes")

    gs = env.resolve(_kw(call, "grid_spec"))
    if isinstance(gs, ast.Call) and _last_name(gs.func) in (
            "PrefetchScalarGridSpec", "GridSpec"):
        npf = _int_const(env.resolve(_kw(gs, "num_scalar_prefetch"))
                         or ast.Constant(0))
        site.n_prefetch = npf or 0
        grid_expr = env.resolve(_kw(gs, "grid"))
        in_specs_expr = _kw(gs, "in_specs")
        out_specs_expr = _kw(gs, "out_specs")
        scratch_expr = _kw(gs, "scratch_shapes")

    grid_elts = _seq_elts(grid_expr) if grid_expr is not None else None
    site.grid_len = len(grid_elts) if grid_elts is not None else None
    site.grid_elts = list(grid_elts) if grid_elts is not None else None

    site.in_specs = _spec_list(in_specs_expr, mi, fi, env)
    site.out_specs = _spec_list(out_specs_expr, mi, fi, env)

    os_expr = env.resolve(_kw(call, "out_shape"))
    if os_expr is not None:
        elts = _seq_elts(os_expr)
        site.out_shapes = ([env.resolve(e) for e in elts]
                           if elts is not None else [os_expr])

    sc = env.resolve(scratch_expr)
    sc_elts = _seq_elts(sc) if sc is not None else None
    if sc_elts is not None:
        site.scratch = [env.resolve(e) for e in sc_elts]

    alias_expr = _kw(call, "input_output_aliases")
    if alias_expr is not None:
        site.has_alias_kw = True
        site.aliases = _alias_dict(env.resolve(alias_expr))

    _resolve_kernel(site, index, env)
    return site


def collect_kernel_calls(index: PackageIndex) -> List[KernelCallSite]:
    sites: List[KernelCallSite] = []
    for mi in index.modules.values():
        # map inner pallas_call Call -> outer invocation Call (the
        # `pl.pallas_call(...)(args)` idiom) so runtime args are visible
        outer_of: Dict[int, ast.Call] = {}
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Call):
                outer_of[id(node.func)] = node
        seen: Set[int] = set()
        for fi in mi.functions.values():
            for _, bare, call in fi.calls:
                if bare == "pallas_call" and id(call) not in seen:
                    seen.add(id(call))
                    sites.append(_parse_site(mi, fi, call,
                                             outer_of.get(id(call)), index))
        for node in walk_shallow(mi.tree):
            if isinstance(node, ast.Call) \
                    and _last_name(node.func) == "pallas_call" \
                    and id(node) not in seen:
                seen.add(id(node))
                sites.append(_parse_site(mi, None, node,
                                         outer_of.get(id(node)), index))
    sites.sort(key=lambda s: (s.mi.rel, s.line))
    return sites


# ---------------------------------------------------------------------------
# abstract interpretation over the grid domain
# ---------------------------------------------------------------------------

def _subscript_root(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Subscript):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def unclamped_prefetch_reads(imap: IndexMapModel,
                             n_grid: Optional[int]) -> List[ast.AST]:
    """Scalar-prefetch table reads in an index_map that are not routed
    through any clamp call. Grid-id params are bounded by the grid domain
    ([0, grid[k]) by construction); a raw ``tab[i, j]`` read is the
    silent-OOB shape — the table may hold sentinel/-1 entries or garbage
    for dead slots, and Mosaic will DMA whatever address falls out."""
    if n_grid is None:
        # grid length unknown: assume every param beyond the block-rank
        # gap could be a table — be permissive (report nothing) rather
        # than guess wrong
        return []
    prefetch = set(imap.params[n_grid:])
    if not prefetch:
        return []
    offending: List[ast.AST] = []

    def visit(node: ast.AST, clamped: bool) -> None:
        if isinstance(node, ast.Call):
            inner_clamped = clamped or _last_name(node.func) in CLAMP_FUNCS
            for child in ast.iter_child_nodes(node):
                visit(child, inner_clamped)
            return
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
            for child in ast.iter_child_nodes(node):
                visit(child, True)
            return
        if isinstance(node, ast.Subscript) and not clamped:
            root = _subscript_root(node)
            if root in prefetch:
                offending.append(node)
                return  # don't double-report nested reads
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        for child in ast.iter_child_nodes(node):
            visit(child, clamped)

    for stmt in imap.body:
        visit(stmt, False)
    return offending


def negative_components(imap: IndexMapModel) -> List[ast.AST]:
    """Index_map return components that are literal negative ints —
    always out of the block-index domain."""
    out = []
    for comps in imap.returns:
        for c in comps:
            v = _int_const(c)
            if v is not None and v < 0:
                out.append(c)
    return out


def scratch_dtype_name(expr: ast.AST) -> Optional[str]:
    """dtype attribute of a ``pltpu.VMEM(shape, dtype)``-style scratch
    entry (None for semaphores / unresolved)."""
    if isinstance(expr, ast.Call) and _last_name(expr.func) in (
            "VMEM", "SMEM", "ANY") and len(expr.args) >= 2:
        return _last_name(expr.args[1])
    return None


def shape_dtype_struct(expr: ast.AST) -> Optional[Tuple[ast.AST, ast.AST]]:
    if isinstance(expr, ast.Call) \
            and _last_name(expr.func) == "ShapeDtypeStruct" \
            and len(expr.args) >= 2:
        return expr.args[0], expr.args[1]
    return None


# ---------------------------------------------------------------------------
# numeric transfer evaluation (ISSUE 11: the cost-model cross-check)
# ---------------------------------------------------------------------------
#
# The cost registry (`observability.costmodel`) states each kernel's HBM
# bytes in closed form; these helpers derive the same quantity from the
# committed BlockSpecs so the two can never drift apart silently.  The
# model is Pallas's fetch rule: a block is (re)copied at every grid step
# whose block index differs from the previous step's.  For an index_map
# that references grid dims S (directly or through body locals), over a
# lexicographic grid sweep the index changes whenever any dim at or
# outside max(S) ticks, so
#
#     fetch_runs = prod(grid[0 .. max(S)])        (1 when S is empty)
#
# and the spec's transfer is fetch_runs * block elements * dtype bytes.
# Specs with memory_space=ANY (manual-DMA operands) evaluate to None.

def eval_int_expr(node: Optional[ast.AST],
                  bindings: Dict[str, int]) -> Optional[int]:
    """Evaluate an integer shape expression under `bindings` (Name ->
    int). Supports the arithmetic the committed call sites use
    (+ - * // % **, unary -, min/max calls); None when anything else
    appears."""
    if node is None:
        return None
    if isinstance(node, ast.Constant):
        v = node.value
        return v if isinstance(v, int) and not isinstance(v, bool) else None
    if isinstance(node, ast.Name):
        return bindings.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = eval_int_expr(node.operand, bindings)
        return -v if v is not None else None
    if isinstance(node, ast.BinOp):
        a = eval_int_expr(node.left, bindings)
        b = eval_int_expr(node.right, bindings)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, ast.FloorDiv):
            return a // b if b else None
        if isinstance(node.op, ast.Mod):
            return a % b if b else None
        if isinstance(node.op, ast.Pow):
            return a ** b
        return None
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("min", "max") and not node.keywords:
        vals = [eval_int_expr(a, bindings) for a in node.args]
        if any(v is None for v in vals) or not vals:
            return None
        return min(vals) if node.func.id == "min" else max(vals)
    return None


def grid_values(site: KernelCallSite,
                bindings: Dict[str, int]) -> Optional[List[int]]:
    """The concrete grid under `bindings`, or None when any component
    doesn't evaluate."""
    if site.grid_elts is None:
        return None
    out = []
    for e in site.grid_elts:
        v = eval_int_expr(e, bindings)
        if v is None:
            return None
        out.append(v)
    return out


def index_map_grid_refs(imap: IndexMapModel, grid_len: int) -> Set[int]:
    """Grid-dim positions the index map's return value depends on, with
    body locals expanded (the page maps return a clamped local `phys`
    computed from the grid id)."""
    local_defs: Dict[str, ast.AST] = {}
    for stmt in imap.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            local_defs[stmt.targets[0].id] = stmt.value

    names: Set[str] = set()
    pending = [c for comps in imap.returns for c in comps]
    seen_exprs = 0
    while pending and seen_exprs < 64:
        expr = pending.pop()
        seen_exprs += 1
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id not in names:
                names.add(n.id)
                if n.id in local_defs:
                    pending.append(local_defs[n.id])
    grid_params = imap.params[:grid_len]
    return {i for i, p in enumerate(grid_params) if p in names}


def spec_transfer_elems(spec: BlockSpecModel, grid: List[int],
                        grid_len: int,
                        bindings: Dict[str, int]) -> Optional[int]:
    """fetch_runs x block elements for one spec, or None when the spec
    stays in HBM (ANY), lacks a literal block shape, or an expression
    doesn't evaluate under `bindings`."""
    if spec.memory_space == "ANY" or spec.block_shape is None:
        return None
    elems = 1
    for e in spec.block_shape:
        v = eval_int_expr(e, bindings)
        if v is None:
            return None
        elems *= v
    if spec.index_map is None:
        return None
    refs = index_map_grid_refs(spec.index_map, grid_len)
    runs = 1
    if refs:
        last = max(refs)
        if last >= len(grid):
            return None
        for g in grid[:last]:
            runs *= g
        runs *= _axis_fetch_runs(spec.index_map, last, grid_len, grid[last],
                                 bindings)
    return runs * elems


def _axis_fetch_runs(imap: IndexMapModel, axis: int, grid_len: int,
                     extent: int, bindings: Dict[str, int]) -> int:
    """Fetches along grid dim `axis`: its extent, unless the map reads the
    dim ONLY as the index into scalar-prefetch tables (``tab[t]``: flash's
    visit table) whose number of value changes along the axis is bound as
    ``<tab>_runs`` — a block is re-copied when its index changes, and a
    table of sorted runs changes less often than the axis ticks."""
    param = imap.params[axis]
    tables = set(imap.params[grid_len:])
    nodes = [n for comps in imap.returns for c in comps for n in ast.walk(c)]
    through: Set[str] = set()
    indexing: Set[int] = set()      # the dim's uses as a table's index
    for node in nodes:
        if isinstance(node, ast.Subscript) \
                and _subscript_root(node) in tables:
            uses = {id(n) for n in ast.walk(node.slice)
                    if isinstance(n, ast.Name) and n.id == param}
            if uses:
                through.add(_subscript_root(node))
                indexing |= uses
    bare = any(isinstance(n, ast.Name) and n.id == param
               and id(n) not in indexing for n in nodes)
    bound = [bindings.get(f"{t}_runs") for t in through]
    if bare or not bound or None in bound:
        return extent
    return min(extent, max(bound))


def transfer_bytes(site: KernelCallSite, bindings: Dict[str, int],
                   in_dtype_bytes: List[Optional[int]],
                   out_dtype_bytes: List[Optional[int]]
                   ) -> Optional[Dict[str, List[Optional[int]]]]:
    """{'in': [...], 'out': [...]} per-spec transfer bytes for a call
    site under concrete shape `bindings`; entries are None for specs
    that opt out (ANY space / unresolved), the dict is None when the
    grid itself doesn't evaluate.  Dtype bytes are supplied per spec
    (an entry of None skips that spec)."""
    if site.grid_len is None:
        return None
    grid = grid_values(site, bindings)
    if grid is None:
        return None

    def _side(specs, dtypes):
        out: List[Optional[int]] = []
        for i, spec in enumerate(specs or []):
            eb = dtypes[i] if i < len(dtypes) else None
            if eb is None:
                out.append(None)
                continue
            elems = spec_transfer_elems(spec, grid, site.grid_len,
                                        bindings)
            out.append(elems * eb if elems is not None else None)
        return out

    return {"in": _side(site.in_specs, in_dtype_bytes),
            "out": _side(site.out_specs, out_dtype_bytes)}
