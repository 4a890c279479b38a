"""paddle_tpu.observability.attribution — measured time x analytical
cost (ISSUE 11).

Joins `profiler.statistic.summarize` per-op tables with the
`costmodel` registry to answer "where do the bytes go": per-kernel
achieved GB/s and FLOP/s against the chip roofline, %-of-roofline, and
%-of-step-time.  Two consumers:

  - `tools/observatory.py` renders `attribute()` as the human roofline
    table and ships it in docs/OBSERVATORY.json (perf-gate banded);
  - the FLAGSHIP residual step-breakdown table is
    `train_step_attribution()` + `render_flagship_table()` over a traced
    train run — generated, not hand math.

Matching is by kernel name: a summarize() row whose base name equals or
contains the kernel name (device XPlane rows carry the real Mosaic
kernel names, e.g. ``ragged_paged_attention_kernel.1``) provides the
measured side.  On CPU tier-1 there are no device rows, so kernels
attribute model-only — launches from `pt_kernel_launch_total` style
counts, measured fields None — and the step-level phases still
attribute exactly.  Rows are plain dicts so they JSON-serialize into
the observatory artifact unchanged.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Callable, Dict, List, Mapping, NamedTuple, \
    Optional, Sequence, Tuple, Union

from . import costmodel

__all__ = ["attribute", "render_roofline_table",
           "train_step_attribution", "render_flagship_table",
           "SCOPES", "SCOPE_ALIASES", "scope", "RESIDUALS", "residual",
           "keeping", "keeps",
           "OpScope", "op_key", "op_scopes", "compile_named"]

_TRAIN_PHASES = ("data", "fwd", "bwd", "opt")

#: FLAGSHIP.md row labels (the generated table keeps the committed prose)
_PHASE_LABELS = {
    "data": "data (loader + host staging)",
    "fwd": "fwd (incl. loss sync — see OBSERVABILITY.md timing caveat)",
    "bwd": "bwd",
    "opt": "opt (AdamW update)",
}


def _stat_parts(stat: Any) -> Tuple[List[Dict[str, Any]],
                                    List[Dict[str, Any]], float]:
    """Normalize a StatisticResult / its to_dict() / a bare ops list to
    (ops, steps, total_us)."""
    if hasattr(stat, "ops"):
        return list(stat.ops), list(stat.steps), float(stat.total_us)
    if isinstance(stat, Mapping):
        return (list(stat.get("ops", [])), list(stat.get("steps", [])),
                float(stat.get("total_us", 0.0)))
    ops = list(stat or [])
    return ops, [], float(sum(r.get("total_us", 0.0) for r in ops))


def _match_row(ops: Sequence[Mapping[str, Any]],
               kernel: str) -> Optional[Mapping[str, Any]]:
    for r in ops:
        if r.get("name") == kernel:
            return r
    for r in ops:
        if kernel in str(r.get("name", "")):
            return r
    return None


def attribute(stat: Any,
              kernel_costs: Mapping[str, Union[costmodel.CostEstimate,
                                               Tuple[int,
                                                     costmodel.CostEstimate]]],
              *, hbm_bw: float = costmodel.HBM_BW["v5e"],
              peak_flops: Optional[float] = None,
              step_time_us: Optional[float] = None,
              launches: Optional[Mapping[str, int]] = None
              ) -> List[Dict[str, Any]]:
    """Per-kernel attribution rows, sorted by model HBM bytes descending.

    ``kernel_costs`` maps kernel name -> CostEstimate for ONE launch (or
    ``(launches, CostEstimate)`` as `decode_layer_kernels` emits).
    ``launches`` overrides the launch count per kernel (the measured
    `pt_kernel_launch_total` values); a matching summarize() row's call
    count wins over both.  ``step_time_us`` is the denominator for
    %-of-step-time (defaults to the profile's total)."""
    ops, _, total_us = _stat_parts(stat)
    denom = step_time_us if step_time_us else total_us
    rows: List[Dict[str, Any]] = []
    for kernel, entry in kernel_costs.items():
        n, est = entry if isinstance(entry, tuple) else (1, entry)
        if launches and kernel in launches:
            n = int(launches[kernel])
        row = _match_row(ops, kernel)
        measured_us = float(row["total_us"]) if row else None
        if row:
            n = int(row.get("calls", n))
        bytes_total = est.hbm_bytes * n
        flops_total = est.flops * n
        theo_us = est.theoretical_us(hbm_bw, peak_flops) * n
        out: Dict[str, Any] = {
            "kernel": kernel, "launches": n,
            "bytes": bytes_total, "bytes_per_launch": est.hbm_bytes,
            "flops": flops_total,
            "arithmetic_intensity": est.arithmetic_intensity,
            "theoretical_us": theo_us,
            "measured_us": measured_us,
            "achieved_gbps": None, "achieved_tflops": None,
            "pct_roofline": None, "pct_step_time": None,
        }
        if measured_us and measured_us > 0:
            out["achieved_gbps"] = bytes_total / measured_us / 1e3
            out["achieved_tflops"] = flops_total / measured_us / 1e6
            out["pct_roofline"] = 100.0 * theo_us / measured_us
            if denom:
                out["pct_step_time"] = 100.0 * measured_us / denom
        rows.append(out)
    rows.sort(key=lambda r: -r["bytes"])
    return rows


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"                      # pragma: no cover


def render_roofline_table(rows: Sequence[Mapping[str, Any]],
                          hbm_bw: float = costmodel.HBM_BW["v5e"]
                          ) -> str:
    """The human observatory table: kernel · launches · bytes ·
    achieved/theoretical · % step time."""
    head = (f"{'kernel':<28}{'launches':>9}{'bytes':>12}"
            f"{'GB/s ach':>10}{'GB/s roof':>10}{'%roof':>7}{'%step':>7}")
    out = [head, "-" * len(head)]
    for r in rows:
        ach = r.get("achieved_gbps")
        roof = hbm_bw / 1e9
        pct = r.get("pct_roofline")
        pstep = r.get("pct_step_time")
        out.append(
            f"{r['kernel'][:27]:<28}{r['launches']:>9}"
            f"{_fmt_bytes(r['bytes']):>12}"
            f"{(f'{ach:.1f}' if ach is not None else '—'):>10}"
            f"{roof:>10.0f}"
            f"{(f'{pct:.0f}%' if pct is not None else '—'):>7}"
            f"{(f'{pstep:.1f}%' if pstep is not None else '—'):>7}")
    return "\n".join(out)


def train_step_attribution(stat: Any) -> Dict[str, Any]:
    """The residual step breakdown FLAGSHIP.md commits: per-phase
    ms/step and % of wall from the traced train-step spans
    (`kind="train"` lifetime events + data/fwd/bwd/opt phase events in
    the chrome export), with the residual reported as *unattributed*
    instead of silently absorbed."""
    ops, steps, total_us = _stat_parts(stat)
    life = [r for r in ops if str(r.get("name", "")).startswith("train:")]
    n_steps = sum(int(r.get("calls", 0)) for r in life)
    wall_us = sum(float(r.get("total_us", 0.0)) for r in life)
    if not n_steps:                  # no lifetime spans: fall back to
        n_steps = max(int(next((s["calls"] for s in steps
                                if s["phase"] == "opt"), 1)), 1)
        wall_us = total_us
    phases = []
    attributed = 0.0
    for name in _TRAIN_PHASES:
        s = next((s for s in steps if s["phase"] == name), None)
        t = float(s["total_us"]) if s else 0.0
        attributed += t
        phases.append({
            "phase": name,
            "ms_per_step": t / n_steps / 1e3,
            "pct": 100.0 * t / wall_us if wall_us else 0.0})
    resid = max(wall_us - attributed, 0.0)
    return {"steps": n_steps,
            "wall_ms_per_step": wall_us / n_steps / 1e3,
            "phases": phases,
            "unattributed_ms_per_step": resid / n_steps / 1e3,
            "unattributed_pct": 100.0 * resid / wall_us if wall_us
            else 0.0}


def render_flagship_table(d: Mapping[str, Any]) -> str:
    """Markdown table in the committed FLAGSHIP.md §5 layout."""
    out = ["| Phase | ms/step | % of wall |", "|---|---:|---:|"]
    for p in d["phases"]:
        label = _PHASE_LABELS.get(p["phase"], p["phase"])
        out.append(f"| {label} | {p['ms_per_step']:.1f} "
                   f"| {p['pct']:.1f}% |")
    out.append(f"| unattributed (logging, bookkeeping) "
               f"| {d['unattributed_ms_per_step']:.1f} "
               f"| {d['unattributed_pct']:.1f}% |")
    out.append(f"| **wall per step** | **{d['wall_ms_per_step']:.1f}** "
               f"| 100% |")
    return "\n".join(out)


# --------------------------------------------------------------------------
# measured time x the program's own names for the parts of a step
# (ISSUE 37).  The serving step bodies and the trainer step run their
# parts under `scope(<name>)`; the compiled program carries the name in
# every instruction's ``op_name``; `op_scopes` reads it back, keyed as
# the device trace names the instruction's events.
# --------------------------------------------------------------------------

#: the ONE vocabulary, the same in every model family and in the trainer
SCOPES = ("embed", "attn_norm", "qkv_proj", "cache_write", "attention",
          "attn_out", "ffn_norm", "ffn", "routed_ffn", "shared_expert",
          "loop_norm", "head", "head_loss", "update", "qk_norm", "unmask")

#: scopes that name a kernel in the device trace (a Pallas call's
#: instruction takes its INNERMOST scope's name, and trace readers find
#: `mla_attention`, `eva_attention`, `eva_pool` so): they stay as they
#: are and answer to the vocabulary through this table.  `mla_kv` is
#: the latent row's projection, norm and rope; its row append sits in a
#: `cache_write` of its own, one scope further in.  A state-space
#: mixer's parts answer as the attention chain's do: its in-projection
#: as `qkv_proj`, the convolution (which keeps the sequence's last
#: rows) as `cache_write`, the state update and the chunk's scan as
#: `attention`, the gated norm and out-projection as `attn_out`; the
#: latent projections around the routed experts are `routed_ffn`.  A
#: KDA (delta-rule) mixer's parts answer the same way: its projections,
#: its convolutions with the heads' norm and gates, the decode rows'
#: state update and the chunk's scan (a scope each: two kernels), the
#: heads' norm, gate and out-projection.  A residual of several streams
#: (hyper-connections, `serving.engine._HyperResidual`) is mixed under
#: three names: `mhc_pre` (a sublayer's input from the stream, before the
#: sublayer's own norm), `mhc_post` (the stream's update), `mhc_merge`
#: (entry and exit).  They answer as the norms do — the parts of a
#: sublayer that are neither a projection nor an FFN — so that the sums
#: readers take over `qkv_proj` / `attn_out` / `ffn` stay what they were.
#: A Mamba-1 mixer's parts (`ssm1_*`) answer as Mamba-2's; a gated
#: memory unit (`gmu`: two projections around another block's scan
#: output) and the differential heads' combine after the launch
#: (`diff_combine`) as `attn_out`; a launch over ANOTHER block's pages
#: is `shared_attention`, told from `attention` over a block's own.
#: A gated short convolution's parts (`lfm_*`, LFM2) answer as a
#: state-space mixer's do; the copy of its tails at a page's last row
#: into the page's snapshot (`tail_snapshot`) is a `cache_write`.
#: The trainer's layers (ISSUE 66) tell a flash launch under a sliding
#: window's band (`window_attention`) from a full one (`attention`), and
#: the parts of a routed FFN around its grouped GEMMs — the choice and
#: its statistics (`moe_route`), the sort and gather of the pair rows
#: (`moe_dispatch`), the unsort and weighted sum (`moe_combine`) — from
#: the GEMMs, which stay directly under `routed_ffn`.
SCOPE_ALIASES = {"mla_q": "qkv_proj", "mla_kv": "qkv_proj",
                 "mla_attention": "attention", "mla_out": "attn_out",
                 "eva_attention": "attention", "eva_pool": "cache_write",
                 "ssm_in_proj": "qkv_proj", "ssm_conv": "cache_write",
                 "ssm_scan": "attention", "ssm_out": "attn_out",
                 "latent_proj": "routed_ffn",
                 "kda_in_proj": "qkv_proj", "kda_conv": "cache_write",
                 "kda_state_update": "attention",
                 "kda_chunk_scan": "attention", "kda_out": "attn_out",
                 "mhc_pre": "attn_norm", "mhc_post": "ffn_norm",
                 "mhc_merge": "ffn_norm",
                 "ssm1_in_proj": "qkv_proj", "ssm1_conv": "cache_write",
                 "ssm1_scan": "attention", "ssm1_out": "attn_out",
                 "gmu": "attn_out", "shared_attention": "attention",
                 "diff_combine": "attn_out",
                 "lfm_in_proj": "qkv_proj", "lfm_conv": "cache_write",
                 "tail_snapshot": "cache_write", "lfm_out": "attn_out",
                 "window_attention": "attention",
                 "moe_route": "routed_ffn", "moe_dispatch": "routed_ffn",
                 "moe_combine": "routed_ffn"}


def scope(name: str):
    """`jax.named_scope(name)` for a name of the vocabulary: metadata
    of the operations traced under it, no operation of its own."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of the step scopes {SCOPES}")
    import jax
    return jax.named_scope(name)


#: the values of a decoder layer that a checkpoint around it may KEEP for
#: the backward where it would otherwise compute them again (the
#: trainer's `remat` "full", `trainer/pretrain.py::choose_remat_plan`):
#: the flash kernel's output and its row log-sum-exp (the residuals its
#: backward reads), and the raw products of the output, the q / k / v and
#: the gate / up projections.  A scope's own name where the value IS the
#: scope's result.  The down projection is not here (nothing in the
#: backward reads it), nor the swiglu product and the norms (the
#: cheapest recomputation a byte).
#: A routed FFN's `moe_gate_up` is the sorted pair rows' gate and up
#: products (two [pairs, width] grouped GEMMs; ISSUE 66).
RESIDUALS = ("flash_o", "flash_lse", "attn_out", "qkv", "gate_up",
             "moe_gate_up")


_keeping: contextvars.ContextVar = contextvars.ContextVar(
    "residuals_kept", default=())


@contextlib.contextmanager
def keeping(names: Sequence[str]):
    """Code traced inside is under a checkpoint whose policy keeps the
    residuals `names` (`jax.checkpoint_policies.save_only_these_names`):
    `residual` names those, and only there."""
    token = _keeping.set(tuple(names))
    try:
        yield
    finally:
        _keeping.reset(token)


def keeps(name: str) -> bool:
    if name not in RESIDUALS:
        raise ValueError(f"{name!r} is not one of the residuals {RESIDUALS}")
    return name in _keeping.get()


def residual(x, name: str):
    """`x` under a name of RESIDUALS
    (`jax.ad_checkpoint.checkpoint_name`) where a checkpoint around the
    caller keeps it (`keeping`); `x` itself everywhere else — eager,
    serving, a checkpoint that keeps nothing: those programs have no
    trace of the names."""
    if not keeps(name):
        return x
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, name)


class OpScope(NamedTuple):
    """What the program says of one compiled instruction."""
    scope: Optional[str]        # a name of SCOPES; None under no scope
    direction: str              # "fwd" | "bwd" | "remat" | "-"
    kind: str                   # "compute" | "collective" | "copy" | "control"
    opcode: str
    shape: str                  # the (first) result, `bf16[288,4096]`
    scopes: Tuple[str, ...]     # a fusion's names, where it spans several
    inherited: bool             # no name of its own: its reader's, else
    #                             its operand's (layout copies, prefetches)
    reads: str                  # a copy's source parameter (`w__layers__..`)
    program: str
    #: the innermost name AS THE PROGRAM WROTE IT: `scope` itself, or
    #: the name of SCOPE_ALIASES that answered for it (`ssm_scan`)
    own: str = ""


_HLO_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\-.]+) = (\(?\w+\[[\d,]*\])")
_HLO_OPCODE = re.compile(r" ([\w\-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w\-.]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w\-.]+)")
#: computations whose instructions run inside their caller's one event
_INLINED = re.compile(r"\b(?:to_apply|called_computations)=\{?%?([\w\-.]+)")
_OPERAND = re.compile(r"%([\w\-.]+)")
_JIT_PART = re.compile(r"\bp?jit\([^()]*\)")
_WORD = re.compile(r"[A-Za-z_][\w.\-]*")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all|send|recv)(-start|-done)?$")
#: instructions the device runs nothing for
_NO_EVENT = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota"}


def _head(line: str) -> Optional[Tuple[str, str, str]]:
    """(instruction name, first result shape, opcode) of an HLO line,
    as `Compiled.as_text()` or a device trace event prints it."""
    head = _HLO_HEAD.match(line)
    if head is None:
        return None
    op = _HLO_OPCODE.search(line, head.end())
    return (head.group(1), head.group(2).lstrip("("),
            op.group(1) if op else "?")


def op_key(line: str) -> Optional[str]:
    """The key of an instruction: ``%<name> <first result shape>``,
    from its line in `Compiled.as_text()` and from its event's name in
    the device trace alike.  (The two print one instruction differently
    — the trace adds operand types and tilings — so whole lines never
    compare equal; the name is unique in a program and the shape keeps
    two programs' ``%fusion.3`` apart.)"""
    head = _head(line)
    return None if head is None else f"%{head[0]} {head[1]}"


def _path_scope(op_name: str) -> Tuple[Optional[str], str]:
    """(innermost vocabulary name, direction) of one ``op_name`` path,
    e.g. ``jit(step)/transpose(jvp(ffn))/checkpoint/mul``.  A scope
    entered outside a transformation shows inside its parentheses, so
    the path is read word by word, outermost first."""
    words = _WORD.findall(_JIT_PART.sub("", op_name))
    own = _own(words)
    name = SCOPE_ALIASES.get(own, own) or None
    if "rematted_computation" in words:
        direction = "remat"
    elif "transpose" in words:
        direction = "bwd"
    elif "jvp" in words:
        direction = "fwd"
    else:
        direction = "-"
    return name, direction


def _own(words) -> str:
    """The innermost of a path's words that is a name of the vocabulary
    or one of its aliases, as written ("" without one)."""
    own = ""
    for w in words[:-1]:            # the last word is the primitive
        if w in SCOPES or w in SCOPE_ALIASES:
            own = w
    return own


def _path_own(op_name: str) -> str:
    """`_own` of one ``op_name`` path."""
    return _own(_WORD.findall(_JIT_PART.sub("", op_name)))


def _kind(opcode: str) -> str:
    if _COLLECTIVE.match(opcode):
        return "collective"
    if opcode in ("copy", "copy-start", "copy-done"):
        return "copy"
    # its event spans the events of the computations it calls
    return "control" if opcode in ("while", "conditional", "call") \
        else "compute"


class _Inst:
    """One parsed instruction, while its computation is resolved."""
    __slots__ = ("name", "shape", "opcode", "scope", "direction", "kind",
                 "inside", "operands", "inherited", "own")

    def __init__(self, name, shape, opcode, scope, direction, kind, inside,
                 operands, own=""):
        self.name, self.shape, self.opcode = name, shape, opcode
        self.scope, self.direction, self.kind = scope, direction, kind
        self.inside, self.operands, self.inherited = inside, operands, False
        self.own = own


def _inherit(insts: List[_Inst], by_name: Mapping[str, _Inst]) -> None:
    """Instructions with no ``op_name`` of their own (the compiler's
    layout copies, prefetches into fast memory, reshapes, loops): each
    takes the (scope, direction) of the first named instruction that
    reads it, else of the first it reads, through any number of such."""
    users: Dict[str, List[str]] = {}
    for i in insts:
        for o in i.operands:
            users.setdefault(o, []).append(i.name)
    pending = [i for i in insts if i.scope is None
               and i.opcode not in ("parameter", "constant")]
    for edges in (users.get, lambda n: by_name[n].operands):
        changed = True
        while changed:
            changed = False
            for i in pending:
                if i.scope is None:
                    src = next((by_name[n] for n in edges(i.name) or ()
                                if n in by_name and by_name[n].scope), None)
                    if src is not None:
                        i.scope, i.direction = src.scope, src.direction
                        i.own = src.own
                        i.inherited = changed = True


def _source_parameter(inst: _Inst, by_name: Mapping[str, _Inst]) -> str:
    """The parameter a copy reads, through bitcasts and the like."""
    for _ in range(8):
        if not inst.operands or inst.operands[0] not in by_name:
            return ""
        inst = by_name[inst.operands[0]]
        if inst.opcode == "parameter":
            return inst.name
    return ""


def _program_scopes(text: str, program: str) -> Dict[str, OpScope]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[List[str]] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            cur.append(line)
    inlined = {m.group(1) for lines in comps.values() for ln in lines
               for m in [_CALLS.search(ln) if " fusion(" in ln
                         else _INLINED.search(ln)] if m}
    out: Dict[str, OpScope] = {}
    for comp, lines in comps.items():
        if comp in inlined:
            continue
        insts: List[_Inst] = []
        for ln in lines:
            head = _head(ln)
            if head is None:
                continue
            name, shape, opcode = head
            m = _OP_NAME.search(ln)
            own, direction = _path_scope(m.group(1)) if m else (None, "-")
            raw = _path_own(m.group(1)) if m else ""
            kind, inside = _kind(opcode), ()
            calls = _CALLS.search(ln) if opcode == "fusion" else None
            if calls:
                # a fusion answers to its root's scope (XLA gives it
                # the root's metadata); where the root has none — a
                # residual add outside every scope — to the last scoped
                # instruction it fused
                names, inner_ops = [], set()
                for inner in comps.get(calls.group(1), ()):
                    ih = _head(inner)
                    if ih:
                        inner_ops.add(_kind(ih[2]) if ih[2] not in
                                      ("convolution", "dot") else "matmul")
                    im = _OP_NAME.search(inner)
                    nm, d = _path_scope(im.group(1)) if im else (None, "-")
                    if nm is not None:
                        names.append(nm)
                        if own is None:
                            raw = _path_own(im.group(1))
                            if direction == "-":
                                direction = d
                if own is None and names:
                    own = names[-1]
                # a collective the compiler hid inside a matmul's fusion
                # (`async_collective_fusion`) leaves it a matmul
                if "collective" in inner_ops and "matmul" not in inner_ops:
                    kind = "collective"
                distinct = tuple(dict.fromkeys(names))
                inside = distinct if len(distinct) > 1 else ()
            operands = _OPERAND.findall(ln[ln.index(opcode + "("):])
            insts.append(_Inst(name, shape, opcode, own, direction, kind,
                               inside, operands, raw))
        by_name = {i.name: i for i in insts}
        _inherit(insts, by_name)
        for i in insts:
            if i.opcode in _NO_EVENT:
                continue
            reads = _source_parameter(i, by_name) if i.kind == "copy" else ""
            out[f"%{i.name} {i.shape}"] = OpScope(
                i.scope, i.direction, i.kind, i.opcode, i.shape, i.inside,
                i.inherited, reads, program, i.own)
    return out


def op_scopes(compiled: Any) -> Dict[str, OpScope]:
    """{`op_key`: `OpScope`} of every instruction the device runs an
    event for, of one compiled program (anything with ``as_text()``, or
    its text) or of a mapping ``{program name: compiled}``.  Built by
    whoever reads a trace, after the run: nothing in the program calls
    it.  A key that two programs give different answers for keeps
    neither scope (``scope`` None, both programs named)."""
    programs = compiled if isinstance(compiled, Mapping) \
        else {"program": compiled}
    table: Dict[str, OpScope] = {}
    for program, c in programs.items():
        text = c if isinstance(c, str) else c.as_text()
        for key, rec in _program_scopes(text, program).items():
            old = table.get(key)
            if old is not None and old[:3] != rec[:3]:
                rec = rec._replace(scope=None, scopes=(),
                                   program=f"{old.program}+{program}")
            table[key] = rec
    return table


_NAMED = re.compile(r"[/(\"](%s)(?=[/)\"])"
                    % "|".join(SCOPES + tuple(SCOPE_ALIASES)))


def compile_named(jitted: Any, args: Sequence[Any],
                  fresh: Callable[[], Any]) -> Any:
    """``jitted.lower(*args).compile()``, answered by this process's
    own compile of the program or by the compile cache — unless the
    answer has lost the program's names.  The cache's key leaves names
    out, so an entry an older program wrote (before it named its parts,
    or under other names) is what the new one finds, names and all.
    Then the program is traced again from ``fresh()``, a new `jax.jit`
    of the same function, and compiled with the cache off: a whole
    compile, paid once by the reader that asked."""
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    wants = set(_NAMED.findall(lowered.as_text(debug_info=True)))
    has = {n for path in _OP_NAME.findall(compiled.as_text())
           for n in _NAMED.findall("/" + path)}
    # (the compiler may fuse a small part away whole: most names, not all)
    if 2 * len(has & wants) >= len(wants):
        return compiled
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return fresh().lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
