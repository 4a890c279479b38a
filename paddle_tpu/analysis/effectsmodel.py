"""Grid memory-effects model (ISSUE 19 tentpole).

Extends the :mod:`kernelmodel` BlockSpec/IndexMap ASTs into per-kernel
symbolic READ/WRITE sets as functions of the grid indices.  For every
``pl.pallas_call`` site the model derives

  - which grid axes REVISIT each output block (the index_map ignores
    the axis, or routes it through a scalar-prefetch table — the page
    maps), and whether the launch declares those axes ``"arbitrary"``
    (sequential) via ``compiler_params.dimension_semantics``;
  - every in-kernel ref access in execution order — loads and stores
    with their ``@pl.when`` guard classified as *first-step* (``== 0``),
    *last-step* (``== num_programs - 1``) or *other* — so the
    seed-on-first-visit, guarded-accumulator and emit idioms are
    recognized structurally, not by comment;
  - which stores scatter through dynamic indices (``pl.dslice``), their
    literal width, and whether the offset is derived from the per-step
    prefetch table (the paged-append disjointness contract);
  - which ``input_output_aliases`` pairs are live, by kernel param name.

On top sit the hazard primitives the PE rule family reports
(rules_effects.py) and :func:`compose_verdicts` — the PE505
fusion-legality verdict for every PF404 candidate plus the registered
front-half composition (ROADMAP item 1: qkv + rope + paged-append).

Pure stdlib ``ast`` like the rest of the package; degrade to unknown
(skip), never guess: a kernel with ``*refs``, an index_map the Env
cannot resolve, or a spec/param arity mismatch opts its site out.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Any, Dict, List, Optional, Set, Tuple

from . import kernelmodel as km
from . import vmemmodel as vm
from .callgraph import PackageIndex, _last_name
from .kernelmodel import KernelCallSite

__all__ = [
    "RefAccess", "RefEffects", "KernelEffects", "COMPOSITIONS",
    "build_effects", "collect_effects", "ww_hazards",
    "alias_read_hazards", "accumulator_hazards", "scatter_hazards",
    "derive_write_bytes", "compose_verdicts",
]

#: Registered fused-kernel compositions beyond the adjacent PF404 pairs.
#: ISSUE 20 CONSUMED the old ``front_half_qkv_rope_append`` entry — the
#: qkv projection + rope + paged-append now ship as one
#: fused_qkv_rope_append launch — so the registered composition is the
#: ROADMAP <=4-launch follow-on: the full decode layer body (ragged
#: attention launches between the front and back halves).  PE505
#: certifies the member effects compose without PE501-PE504 hazards.
COMPOSITIONS: List[Dict[str, Any]] = [
    {
        "name": "decode_layer_le4",
        "members": ["fused_rms_norm", "fused_qkv_rope_append",
                    "fused_oproj_norm", "fused_ffn"],
        "note": "ROADMAP <=4-launch follow-on: ragged attention "
                "launches between fused_qkv_rope_append and "
                "fused_oproj_norm; the remaining mechanical seam is "
                "the norm's 8-row block vs the front's one-token sweep "
                "(retile) and the deliberate oproj->ffn VMEM cut",
    },
]


@dataclasses.dataclass
class RefAccess:
    """One in-kernel subscript access of a ref parameter."""
    ref: str
    kind: str                     # "load" | "store"
    line: int
    col: int
    guard: Optional[str]          # None unguarded | "first" | "last" | "other"
    dynamic: bool = False         # store through pl.dslice/pl.ds
    dyn_width: Optional[int] = None   # literal dslice width
    dyn_stepped: bool = False     # offset derives from a per-step table read
    node: Optional[ast.AST] = None


@dataclasses.dataclass
class RefEffects:
    """Symbolic effect summary of one kernel ref parameter."""
    name: str
    kind: str                     # "prefetch" | "in" | "out" | "scratch"
    index: int                    # flat operand index within its kind
    spec: Optional[km.BlockSpecModel] = None
    grid_refs: Optional[Set[int]] = None      # None: index_map unknown
    table_axes: Set[int] = dataclasses.field(default_factory=set)
    revisit_axes: Optional[Set[int]] = None   # None: unknown
    loads: List[RefAccess] = dataclasses.field(default_factory=list)
    stores: List[RefAccess] = dataclasses.field(default_factory=list)
    #: the ref's bare name escapes into calls/locals the model cannot
    #: follow (DMA handles, helper tuples) — effects unknown, so the
    #: initialization rules must not claim anything about it
    escapes: bool = False


@dataclasses.dataclass
class KernelEffects:
    """Per-site effects model: every ref's read/write set plus the
    launch-level declarations that make revisiting writes legal."""
    site: KernelCallSite
    params: List[str]
    refs: Dict[str, RefEffects]
    dim_semantics: Optional[List[str]]        # None: undeclared
    alias_pairs: List[Tuple[RefEffects, RefEffects]]

    def of_kind(self, kind: str) -> List[RefEffects]:
        return [r for r in self.refs.values() if r.kind == kind]

    @property
    def outputs(self) -> List[RefEffects]:
        return self.of_kind("out")

    def declared_arbitrary(self, axis: int) -> bool:
        ds = self.dim_semantics
        return ds is not None and axis < len(ds) and ds[axis] == "arbitrary"


# ---------------------------------------------------------------------------
# index-map effect derivation
# ---------------------------------------------------------------------------

def _imap_locals(imap: km.IndexMapModel) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    for stmt in imap.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            out[stmt.targets[0].id] = stmt.value
    return out


def table_axes(imap: km.IndexMapModel, grid_len: int) -> Set[int]:
    """Grid dims that feed a scalar-prefetch TABLE read inside the index
    map — the block index then changes data-dependently along those dims
    (``page_map(t, pg, off) -> (0, clip(pg[t], ...), 0, 0)``), so the
    output block may revisit even though the dim is "referenced"."""
    grid_params = {p: i for i, p in enumerate(imap.params[:grid_len])}
    tables = set(imap.params[grid_len:])
    if not tables or not grid_params:
        return set()
    axes: Set[int] = set()
    exprs = [c for comps in imap.returns for c in comps]
    exprs += list(_imap_locals(imap).values())
    for e in exprs:
        for n in ast.walk(e):
            if isinstance(n, ast.Subscript) \
                    and km._subscript_root(n) in tables:
                for m in ast.walk(n):
                    if isinstance(m, ast.Name) and m.id in grid_params:
                        axes.add(grid_params[m.id])
    return axes


def revisit_axes(spec: Optional[km.BlockSpecModel],
                 grid_len: Optional[int]) -> Optional[Set[int]]:
    """Grid axes along which the spec's block index can repeat: the dims
    the index_map does not reference, plus the table-driven dims.  None
    when the map (or the grid) is unknown — degrade, don't guess."""
    if spec is None or spec.index_map is None or grid_len is None:
        return None
    refs = km.index_map_grid_refs(spec.index_map, grid_len)
    return (set(range(grid_len)) - refs) \
        | table_axes(spec.index_map, grid_len)


def _dimension_semantics(site: KernelCallSite) -> Optional[List[str]]:
    env = km.Env(site.mi, site.fi)
    cp = env.resolve(km._kw(site.call, "compiler_params"))
    if not isinstance(cp, ast.Call):
        return None
    ds = env.resolve(km._kw(cp, "dimension_semantics"))
    elts = km._seq_elts(ds) if ds is not None else None
    if elts is None:
        return None
    out: List[str] = []
    for e in elts:
        e = env.resolve(e)
        if isinstance(e, ast.Constant) and isinstance(e.value, str):
            out.append(e.value)
        else:
            return None
    return out


# ---------------------------------------------------------------------------
# kernel-body access collection
# ---------------------------------------------------------------------------

def _when_expr(fn: ast.AST) -> Optional[ast.AST]:
    for dec in getattr(fn, "decorator_list", []):
        if isinstance(dec, ast.Call) and _last_name(dec.func) == "when" \
                and dec.args:
            return dec.args[0]
    return None


def _contains_call(node: ast.AST, name: str, kenv: Dict[str, ast.AST],
                   _depth: int = 0) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and _last_name(n.func) == name:
            return True
        if isinstance(n, ast.Name) and _depth < 4:
            v = kenv.get(n.id)
            if v is not None and _contains_call(v, name, kenv, _depth + 1):
                return True
    return False


def _guard_kind(expr: ast.AST, kenv: Dict[str, ast.AST]) -> str:
    """Classify a ``pl.when`` guard: "first" when it contains an
    ``== 0`` comparison (seed/init idioms, including the disjunctive
    ``(t == 0) | (page changed)`` seed guard), "last" when it compares
    equal against a ``num_programs``-derived bound (emit idiom),
    "other" for everything else."""
    first = last = False
    for n in ast.walk(expr):
        if isinstance(n, ast.Compare) and len(n.ops) == 1 \
                and isinstance(n.ops[0], ast.Eq):
            for side in (n.left, n.comparators[0]):
                if km._int_const(side) == 0:
                    first = True
                elif _contains_call(side, "num_programs", kenv):
                    last = True
    if first:
        return "first"
    if last:
        return "last"
    return "other"


def _kernel_env(fn: ast.AST) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name) \
                and n.targets[0].id not in out:
            out[n.targets[0].id] = n.value
    return out


def _resolve_local(node: ast.AST, kenv: Dict[str, ast.AST],
                   _depth: int = 0) -> ast.AST:
    while isinstance(node, ast.Name) and _depth < 4:
        nxt = kenv.get(node.id)
        if nxt is None or nxt is node:
            break
        node = nxt
        _depth += 1
    return node


def _dslice_of(store: ast.Subscript) -> Optional[ast.Call]:
    for n in ast.walk(store.slice):
        if isinstance(n, ast.Call) and _last_name(n.func) in ("dslice",
                                                              "ds"):
            return n
    return None


def _offset_stepped(offset: ast.AST, kenv: Dict[str, ast.AST],
                    prefetch: Set[str]) -> bool:
    """True when the scatter offset is a per-grid-step scalar-prefetch
    table read (``off_ref[t]`` with ``t = pl.program_id(k)``) — the
    engine's append contract makes those destinations disjoint — or
    such a read plus a term (``base + j``: row j of the step's run)."""
    offset = _resolve_local(offset, kenv)
    if isinstance(offset, ast.BinOp) and isinstance(offset.op, ast.Add):
        return _offset_stepped(offset.left, kenv, prefetch) \
            or _offset_stepped(offset.right, kenv, prefetch)
    if isinstance(offset, ast.Subscript) \
            and km._subscript_root(offset) in prefetch:
        idx = offset.slice
        if _contains_call(idx, "program_id", kenv):
            return True
    return False


#: calls that read only metadata from a ref (never its buffer) — a bare
#: ref name passed to these does NOT make its effects unknown
_SHAPE_ONLY_CALLS = {"zeros_like", "full_like", "ones_like",
                     "empty_like"}
#: ref attributes that expose metadata, not an aliasing handle (`.at`
#: IS an aliasing handle: DMA copies write through it)
_META_ATTRS = {"shape", "dtype", "ndim", "aval"}


def _escaped_refs(fn: ast.AST, pset: Set[str]) -> Set[str]:
    """Ref params whose bare name flows somewhere the access scanner
    cannot follow — `buf.at[...]` DMA handles, tuple-unpacked helper
    locals, user helper calls.  Their effects degrade to unknown."""
    parent: Dict[ast.AST, ast.AST] = {}
    for n in ast.walk(fn):
        for c in ast.iter_child_nodes(n):
            parent[c] = n
    escaped: Set[str] = set()
    for n in ast.walk(fn):
        if not (isinstance(n, ast.Name) and n.id in pset
                and isinstance(n.ctx, ast.Load)):
            continue
        p = parent.get(n)
        if isinstance(p, ast.Subscript) and p.value is n:
            continue                       # ref[...] — tracked access
        if isinstance(p, ast.Attribute) and p.attr in _META_ATTRS:
            continue                       # ref.shape / ref.dtype
        if isinstance(p, ast.Call) \
                and _last_name(p.func) in _SHAPE_ONLY_CALLS:
            continue                       # jnp.zeros_like(ref)
        escaped.add(n.id)
    return escaped


def _collect_accesses(site: KernelCallSite, params: List[str],
                      prefetch: Set[str]) -> Dict[str, List[RefAccess]]:
    fn = site.kernel_fi.node
    kenv = _kernel_env(fn)
    referenced = {n.id for n in ast.walk(fn)
                  if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
    pset = set(params)
    acc: Dict[str, List[RefAccess]] = {p: [] for p in params}

    def record(sub: ast.Subscript, guard: Optional[str]) -> None:
        name = sub.value.id
        is_store = isinstance(sub.ctx, (ast.Store, ast.Del))
        a = RefAccess(ref=name, kind="store" if is_store else "load",
                      line=sub.lineno, col=sub.col_offset, guard=guard,
                      node=sub)
        if is_store:
            ds = _dslice_of(sub)
            if ds is not None:
                a.dynamic = True
                if len(ds.args) > 1:
                    a.dyn_width = km._int_const(ds.args[1])
                if ds.args:
                    a.dyn_stepped = _offset_stepped(ds.args[0], kenv,
                                                    prefetch)
        acc[name].append(a)

    def scan(node: ast.AST, guard: Optional[str]) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                w = _when_expr(ch)
                if w is not None:
                    scan(ch, _guard_kind(w, kenv))
                elif ch.name in referenced:
                    # plain nested helper, executed when called;
                    # UNreferenced defs are dead code (e.g. an init
                    # whose @pl.when decorator was deleted) and must
                    # not count as initialization
                    scan(ch, guard)
                continue
            if isinstance(ch, ast.AugAssign) \
                    and isinstance(ch.target, ast.Subscript) \
                    and isinstance(ch.target.value, ast.Name) \
                    and ch.target.value.id in pset:
                # ref[...] += x reads AND writes the ref
                load = RefAccess(ref=ch.target.value.id, kind="load",
                                 line=ch.lineno, col=ch.col_offset,
                                 guard=guard, node=ch.target)
                acc[ch.target.value.id].append(load)
            if isinstance(ch, ast.Subscript) \
                    and isinstance(ch.value, ast.Name) \
                    and ch.value.id in pset:
                record(ch, guard)
            scan(ch, guard)

    scan(fn, None)
    return acc


# ---------------------------------------------------------------------------
# the per-site model
# ---------------------------------------------------------------------------

def _site_specs(site: KernelCallSite
                ) -> Tuple[Optional[List[km.BlockSpecModel]],
                           Optional[List[km.BlockSpecModel]]]:
    """The site's specs, rebuilt through the module's `_specs` helper
    when the flow-insensitive Env left index_maps unresolved (the
    flash/flashmask tuple-unpack idiom)."""
    in_specs, out_specs = site.in_specs, site.out_specs

    def unresolved(specs):
        return any(s.index_map is None and s.memory_space not in
                   ("ANY", "SMEM") for s in specs or [])

    if (unresolved(in_specs) or unresolved(out_specs)) \
            and "_specs" in site.mi.functions:
        ri, ro = vm.rebuild_helper_specs(site)
        if ri is not None:
            in_specs = ri
        if ro is not None:
            out_specs = ro
    return in_specs, out_specs


def build_effects(site: KernelCallSite) -> Optional[KernelEffects]:
    """The effects model for one call site, or None when the kernel/spec
    structure does not resolve (``*refs`` kernels, helper-built spec
    lists the Env cannot see) — those sites opt out of the PE rules."""
    params = site.kernel_positional_params()
    if params is None:
        return None
    in_specs, out_specs = _site_specs(site)
    if in_specs is None or out_specs is None:
        return None
    n_pf = site.n_prefetch
    n_in, n_out = len(in_specs), len(out_specs)
    n_scratch = len(site.scratch or [])
    if len(params) != n_pf + n_in + n_out + n_scratch:
        return None                    # arity mismatch: PK102 territory

    refs: Dict[str, RefEffects] = {}
    for i, name in enumerate(params[:n_pf]):
        refs[name] = RefEffects(name=name, kind="prefetch", index=i)
    for i, name in enumerate(params[n_pf:n_pf + n_in]):
        spec = in_specs[i]
        refs[name] = RefEffects(
            name=name, kind="in", index=n_pf + i, spec=spec,
            grid_refs=(km.index_map_grid_refs(spec.index_map,
                                              site.grid_len)
                       if spec.index_map is not None
                       and site.grid_len is not None else None),
            table_axes=(table_axes(spec.index_map, site.grid_len)
                        if spec.index_map is not None
                        and site.grid_len is not None else set()),
            revisit_axes=revisit_axes(spec, site.grid_len))
    for i, name in enumerate(params[n_pf + n_in:n_pf + n_in + n_out]):
        spec = out_specs[i]
        refs[name] = RefEffects(
            name=name, kind="out", index=i, spec=spec,
            grid_refs=(km.index_map_grid_refs(spec.index_map,
                                              site.grid_len)
                       if spec.index_map is not None
                       and site.grid_len is not None else None),
            table_axes=(table_axes(spec.index_map, site.grid_len)
                        if spec.index_map is not None
                        and site.grid_len is not None else set()),
            revisit_axes=revisit_axes(spec, site.grid_len))
    for i, name in enumerate(params[n_pf + n_in + n_out:]):
        refs[name] = RefEffects(name=name, kind="scratch", index=i)

    if site.kernel_fi is not None and not isinstance(site.kernel_fi.node,
                                                     ast.Lambda):
        prefetch = set(params[:n_pf])
        for name, accesses in _collect_accesses(site, params,
                                                prefetch).items():
            for a in accesses:
                (refs[name].stores if a.kind == "store"
                 else refs[name].loads).append(a)
        for name in _escaped_refs(site.kernel_fi.node, set(params)):
            refs[name].escapes = True

    pairs: List[Tuple[RefEffects, RefEffects]] = []
    for k, v in sorted((site.aliases or {}).items()):
        # flat input indices INCLUDE the scalar-prefetch operands
        if k < n_pf + n_in and v < n_out:
            pairs.append((refs[params[k]], refs[params[n_pf + n_in + v]]))

    return KernelEffects(site=site, params=params, refs=refs,
                         dim_semantics=_dimension_semantics(site),
                         alias_pairs=pairs)


def collect_effects(index: PackageIndex) -> List[KernelEffects]:
    out = []
    for site in km.collect_kernel_calls(index):
        eff = build_effects(site)
        if eff is not None:
            out.append(eff)
    return out


# ---------------------------------------------------------------------------
# hazard primitives (PE501-PE504; rules_effects turns these into
# Findings, compose_verdicts re-checks them per fusion member)
# ---------------------------------------------------------------------------

def ww_hazards(eff: KernelEffects) -> List[Dict[str, Any]]:
    """PE501: an output block is revisited along a grid axis that is not
    declared "arbitrary" (sequential) — two grid steps write the same
    block and Mosaic is free to reorder/parallelize them."""
    out = []
    for ref in eff.outputs:
        if not ref.stores or ref.revisit_axes is None:
            continue
        bad = sorted(a for a in ref.revisit_axes
                     if not eff.declared_arbitrary(a))
        if not bad:
            continue
        axes = ",".join(str(a) for a in bad)
        why = ("dimension_semantics is not declared"
               if eff.dim_semantics is None else
               "the axis is not declared \"arbitrary\"")
        out.append({
            "rule": "PE501", "ref": ref.name,
            "detail": f"ww:{ref.name}:ax{axes}",
            "message": f"output ref `{ref.name}` is revisited along grid "
                       f"dim(s) {axes} (its index_map repeats the block "
                       f"index there) but {why} — overlapping writes "
                       f"from different grid steps can race",
            "hint": "declare compiler_params=..."
                    "dimension_semantics with \"arbitrary\" on every "
                    "revisited axis (see ops/pallas_flash.py _CPARAMS)",
        })
    return out


def alias_read_hazards(eff: KernelEffects) -> List[Dict[str, Any]]:
    """PE502: the kernel re-reads a donated input after a store to its
    aliased output — on TPU both names are ONE buffer, so the read
    observes the new value (the hazard fused.py's seed-then-scatter
    ordering exists to avoid)."""
    out = []
    for in_ref, out_ref in eff.alias_pairs:
        if not out_ref.stores:
            continue
        first_store = min(s.line for s in out_ref.stores)
        late = [a for a in in_ref.loads if a.line > first_store]
        if not late:
            continue
        out.append({
            "rule": "PE502", "ref": in_ref.name, "line": late[0].line,
            "col": late[0].col,
            "detail": f"radw:{in_ref.name}->{out_ref.name}",
            "message": f"kernel reads donated input `{in_ref.name}` at "
                       f"line {late[0].line} after storing to its "
                       f"aliased output `{out_ref.name}` (first store "
                       f"line {first_store}) — input_output_aliases "
                       f"makes them the same buffer, so the read "
                       f"observes the overwritten value",
            "hint": "read the donated input only before the first "
                    "aliased store (the seed-on-first-visit idiom), or "
                    "drop the alias",
        })
    return out


def accumulator_hazards(eff: KernelEffects) -> List[Dict[str, Any]]:
    """PE503: an accumulator ref (scratch, or a revisited output that is
    read back) lacks a sound initialization.  A value carried across
    grid steps (read under a last-step emit guard) must be seeded under
    a first-step ``@pl.when(... == 0)`` guard — an unconditional store
    would re-zero it every step, a missing one reads garbage."""
    out = []
    for ref in eff.refs.values():
        if ref.kind == "scratch":
            pass
        elif ref.kind == "out" and ref.revisit_axes:
            pass
        else:
            continue
        if not ref.loads or ref.escapes:
            # an escaping ref (DMA double-buffer filled through
            # buf.at[...] handles) has effects the scanner cannot
            # order — degrade to unknown rather than cry wolf
            continue
        carried = any(a.guard == "last" for a in ref.loads)
        first_init = any(s.guard == "first" for s in ref.stores)
        first_load = min(a.line for a in ref.loads)
        # a store on the first load's own line is that statement's
        # read-modify-write (the right side is evaluated first)
        uncond_init = any(s.guard is None and s.line < first_load
                          for s in ref.stores)
        if carried and not first_init:
            out.append({
                "rule": "PE503", "ref": ref.name,
                "detail": f"acc:{ref.name}",
                "message": f"accumulator `{ref.name}` is read by a "
                           f"last-step emit (carried across the "
                           f"revisiting grid axis) but has no "
                           f"first-step-guarded init store — state "
                           f"from the previous sweep (or garbage) "
                           f"leaks into the accumulation",
                "hint": "seed it under @pl.when(<innermost id> == 0) "
                        "before the first read",
            })
        elif not carried and not (first_init or uncond_init):
            out.append({
                "rule": "PE503", "ref": ref.name,
                "detail": f"acc:{ref.name}",
                "message": f"ref `{ref.name}` is read at line "
                           f"{first_load} with no prior unconditional "
                           f"or first-step-guarded store — scratch "
                           f"memory is uninitialized at launch",
                "hint": "store an initial value before the first read",
            })
    return out


def scatter_hazards(eff: KernelEffects) -> Tuple[List[Dict[str, Any]],
                                                 List[Dict[str, Any]]]:
    """PE504: (errors, contract_notes).  A dynamic in-kernel scatter
    store is provable-disjoint only in the width-1 per-step-table form
    (each grid step writes ONE row at ``table[t]`` — the paged-append
    contract).  Wider slices can straddle two steps' destinations;
    step-independent offsets make every revisit write the same slice."""
    errors: List[Dict[str, Any]] = []
    notes: List[Dict[str, Any]] = []
    for ref in eff.refs.values():
        if ref.kind not in ("out", "in"):
            continue
        dyn = [s for s in ref.stores if s.dynamic]
        if not dyn:
            continue
        bad = False
        for s in dyn:
            if s.dyn_width != 1:
                w = "?" if s.dyn_width is None else str(s.dyn_width)
                errors.append({
                    "rule": "PE504", "ref": ref.name, "line": s.line,
                    "col": s.col, "detail": f"scatter:{ref.name}:w{w}",
                    "message": f"dynamic scatter store into `{ref.name}` "
                               f"has slice width {w} — disjointness "
                               f"across grid steps cannot be proven "
                               f"from the index expressions (adjacent "
                               f"table offsets may differ by 1)",
                    "hint": "scatter one row per grid step "
                            "(pl.dslice(offset, 1)) or restructure so "
                            "the block index carries the position",
                })
                bad = True
            elif not s.dyn_stepped:
                errors.append({
                    "rule": "PE504", "ref": ref.name, "line": s.line,
                    "col": s.col,
                    "detail": f"scatter:{ref.name}:static-offset",
                    "message": f"dynamic scatter store into `{ref.name}` "
                               f"uses an offset that is not derived "
                               f"from a per-grid-step prefetch table "
                               f"read — every revisit writes the same "
                               f"slice",
                    "hint": "index the offset table by pl.program_id "
                            "(off_ref[t]) so each step owns a distinct "
                            "destination row",
                })
                bad = True
        if not bad:
            notes.append({
                "rule": "PE504", "ref": ref.name,
                "detail": f"scatter-contract:{ref.name}",
                "message": f"scatter into `{ref.name}` is width-1 at a "
                           f"per-step table offset — disjoint under the "
                           f"paged-append adjacency contract",
                "hint": "",
            })
    return errors, notes


def member_hazards(eff: KernelEffects) -> List[Dict[str, Any]]:
    """All PE501-PE504 hazards of one kernel (PE505 composes these)."""
    errors, _ = scatter_hazards(eff)
    return (ww_hazards(eff) + alias_read_hazards(eff)
            + accumulator_hazards(eff) + errors)


# ---------------------------------------------------------------------------
# PE505 — fusion-legality verdicts
# ---------------------------------------------------------------------------

def _comp0_sig(spec: Optional[km.BlockSpecModel],
               grid_len: Optional[int]) -> Optional[str]:
    """Normalized signature of the index_map's leading component: 'g<k>'
    for a bare grid id, '<int>' for a constant, 'expr:<src>' else."""
    if spec is None or spec.index_map is None:
        return None
    imap = spec.index_map
    if not imap.returns or not imap.returns[0]:
        return None
    comp = imap.returns[0][0]
    grid_params = {p: i for i, p in enumerate(imap.params[:grid_len or 0])}
    if isinstance(comp, ast.Name) and comp.id in grid_params:
        return f"g{grid_params[comp.id]}"
    v = km._int_const(comp)
    if v is not None:
        return str(v)
    return "expr:" + km.unparse(comp)


def _arg_roots(site: KernelCallSite, idxs) -> Set[str]:
    roots: Set[str] = set()
    for k in idxs:
        if site.arg_exprs and k < len(site.arg_exprs):
            expr: Any = site.arg_exprs[k]
            while isinstance(expr, (ast.Attribute, ast.Subscript)):
                expr = expr.value
            if isinstance(expr, ast.Call):
                expr = expr.args[0] if expr.args else expr.func
            if isinstance(expr, ast.Name):
                roots.add(expr.id)
    return roots


def _pair_verdict(producer: str, consumer: str,
                  psite: Optional[KernelCallSite],
                  csite: Optional[KernelCallSite],
                  peff: Optional[KernelEffects],
                  ceff: Optional[KernelEffects]) -> Dict[str, Any]:
    hazards: List[str] = []
    notes: List[str] = []
    if psite is None or csite is None or peff is None or ceff is None:
        return {"verdict": "unknown", "hazards": [],
                "notes": [f"{producer}->{consumer}: a member site did "
                          f"not resolve — no verdict"]}
    for name, eff in ((producer, peff), (consumer, ceff)):
        for h in member_hazards(eff):
            hazards.append(f"{name}: {h['rule']} on `{h['ref']}` "
                           f"({h['detail']})")
    p_out = peff.outputs[0] if peff.outputs else None
    c_in = next((r for r in ceff.of_kind("in")), None)
    psig = _comp0_sig(p_out.spec if p_out else None, psite.grid_len)
    csig = _comp0_sig(c_in.spec if c_in else None, csite.grid_len)
    if psig is None or csig is None:
        notes.append("leading index components did not resolve; tiling "
                     "compatibility unchecked")
    elif psig != csig:
        hazards.append(
            f"read/write inversion: {producer} writes "
            f"`{p_out.name}` block {psig} while {consumer} reads "
            f"`{c_in.name}` block {csig} — fused, step g would read a "
            f"block the producer has not written yet")
    else:
        p_lead = vm._leading_sweep(p_out.spec if p_out else None,
                                   psite.grid_len)
        c_lead = vm._leading_sweep(c_in.spec if c_in else None,
                                   csite.grid_len)
        pb = km.eval_int_expr(
            p_lead, vm.site_bindings(vm.CANONICAL.get(psite.qualname, {
                "bindings": {}}))) if p_lead is not None else None
        cb = km.eval_int_expr(
            c_lead, vm.site_bindings(vm.CANONICAL.get(csite.qualname, {
                "bindings": {}}))) if c_lead is not None else None
        if pb is not None and cb is not None and pb != cb:
            notes.append(f"retile: producer emits {pb} token row(s) per "
                         f"step, consumer reads {cb} — the fused grid "
                         f"must renest the token loop")
        else:
            notes.append("aligned: identical leading sweep — fusable "
                         "as-is")
    # cross-member donation: a buffer donated by one member must not be
    # re-read (by root name) by a later member of the fused launch
    donated = _arg_roots(psite, (peff.site.aliases or {}).keys())
    consumed = _arg_roots(csite, range(len(csite.arg_exprs or [])))
    for root in sorted(donated & consumed):
        hazards.append(
            f"donated buffer `{root}` from {producer} is consumed by "
            f"{consumer} — fused, the read observes the in-place write")
    return {"verdict": "legal" if not hazards else "hazard",
            "hazards": hazards, "notes": notes}


def compose_verdicts(index: PackageIndex) -> List[Dict[str, Any]]:
    """One machine-readable PE505 verdict per PF404 fusion candidate
    plus each registered composition: {'candidate', 'composition',
    'class', 'producer', 'consumer'/'members', 'verdict', 'hazards',
    'notes'} — JSON-serializable throughout."""
    sites = vm.canonical_sites(index)
    effs = {qn: build_effects(s) for qn, s in sites.items()}
    verdicts: List[Dict[str, Any]] = []
    for cand in vm.fusion_candidates(index):
        pq = vm._CHAIN_SITE[cand["producer"]]
        cq = vm._CHAIN_SITE[cand["consumer"]]
        v = _pair_verdict(cand["producer"], cand["consumer"],
                          sites.get(pq), sites.get(cq),
                          effs.get(pq), effs.get(cq))
        v.update(candidate=f"{cand['producer']}->{cand['consumer']}",
                 composition=None, klass=cand["class"],
                 producer=cand["producer"], consumer=cand["consumer"])
        verdicts.append(v)
    for comp in COMPOSITIONS:
        if not any(vm._CHAIN_SITE.get(m) in sites
                   for m in comp["members"]):
            continue        # none of the members are in this selection
        hazards: List[str] = []
        notes: List[str] = [comp["note"]]
        verdict = "legal"
        for p, c in zip(comp["members"], comp["members"][1:]):
            pq, cq = vm._CHAIN_SITE.get(p), vm._CHAIN_SITE.get(c)
            v = _pair_verdict(p, c, sites.get(pq), sites.get(cq),
                              effs.get(pq), effs.get(cq))
            hazards.extend(v["hazards"])
            notes.extend(v["notes"])
            if v["verdict"] == "unknown":
                verdict = "unknown"
        if hazards:
            verdict = "hazard"
        verdicts.append({
            "candidate": "->".join(comp["members"]),
            "composition": comp["name"], "klass": "composition",
            "members": list(comp["members"]),
            "verdict": verdict, "hazards": hazards, "notes": notes,
        })
    verdicts.sort(key=lambda v: v["candidate"])
    return verdicts


# ---------------------------------------------------------------------------
# PE506 — write-side cost drift
# ---------------------------------------------------------------------------

def derive_write_bytes(index: PackageIndex,
                       cost_module=None) -> List[Dict[str, Any]]:
    """One record per CANONICAL kernel: effects-model write bytes (the
    out-spec side of the BlockSpec fetch accounting) vs the registered
    ``CostEstimate.bytes_written``.  PF406 compares totals; a kernel
    that WRITES blocks the cost model does not charge can hide inside
    the total when the read side over-covers — this is the write-only
    cross-check.  status mirrors derive_cost_bytes."""
    cm = cost_module if cost_module is not None else vm.load_costmodel()
    sites = vm.canonical_sites(index)
    records: List[Dict[str, Any]] = []
    for qn, entry in vm.CANONICAL.items():
        site = sites.get(qn)
        if site is None:
            continue
        rec: Dict[str, Any] = {
            "kernel": entry["kernel"], "qualname": qn,
            "path": site.mi.rel, "line": site.line,
        }
        b = vm.site_bindings(entry)
        if not vm.grid_ok(site, b):
            rec["status"] = "skipped:grid"
            records.append(rec)
            continue
        t = vm.derive_transfer(site, entry, b)
        if t is None or t["unresolved"]:
            rec["status"] = "skipped:unresolved"
            records.append(rec)
            continue
        rec["derived"] = t["write"]
        if cm is None:
            rec["status"] = "skipped:costmodel"
            records.append(rec)
            continue
        try:
            est = cm.cost(entry["kernel"], **entry["cost_kwargs"])
        except Exception:
            rec["status"] = "skipped:cost-error"
            records.append(rec)
            continue
        expected = est.bytes_written
        if not expected:
            rec["status"] = "skipped:cost-empty"
            records.append(rec)
            continue
        rel = abs(t["write"] - expected) / expected
        rec.update(expected=expected, rel_err=rel,
                   status="ok" if rel <= vm.COST_DRIFT_RTOL else "drift")
        records.append(rec)
    return records
