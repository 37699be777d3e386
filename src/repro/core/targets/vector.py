"""Vector target: work-items mapped to lanes, divergence if-converted.

This is the target-specific *parallel mapping* stage of the pocl pipeline
(paper Fig. 3): the target-independent region formation has produced
parallel regions + a schedule; here every varying SSA value becomes a
``(local_size,)`` lane vector (one work-item per lane — the SIMD mapping of
§4.1), uniform values stay scalars (the §4.7 merge), and intra-region
divergent control flow is executed fully predicated (if-conversion — listed
as future work in the paper §8; on TPU it is the only option, and the natural
one).  Inter-region scheduling follows the paper's peeled-first-work-item
rule (§4.4): the branch that selects the next region is read from lane 0,
legal because OpenCL barrier semantics make it work-group-uniform.

The work-group function is emitted as either a straight-line chain of region
calls (linear schedules) or a ``lax.while_loop`` over a ``lax.switch`` of
regions (schedules with conditional barriers / b-loops).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import ir
from ..context import ContextPlan
from ..ir import CondBranch, Function, Instr, Jump, Value
from ..passes import BlockNode, LoopNode, WorkGroupPlan, build_plan
from ..regions import Region, WGInfo


# ---------------------------------------------------------------------------
# Predicates: None means "all lanes true"
# ---------------------------------------------------------------------------

def _pand(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return jnp.logical_and(a, b)


def _pnot_and(a, c):
    """a AND NOT c."""
    nc = jnp.logical_not(c)
    return nc if a is None else jnp.logical_and(a, nc)


def _por(preds: List[object]):
    if any(p is None for p in preds):
        return None
    if not preds:
        return None  # unreachable block; treated as never-executed by caller
    out = preds[0]
    for p in preds[1:]:
        out = jnp.logical_or(out, p)
    return out


# ---------------------------------------------------------------------------
# The lane executor
# ---------------------------------------------------------------------------

class LaneExec:
    """Executes parallel regions for a batch of lanes (work-items).

    ``lids_linear``: (L,) linearized local ids of the lanes in this batch —
    ``jnp.arange(local_size)`` for the vector target, a single dynamic index
    for the serial loop target.
    """

    def __init__(self, prog: "WGProgram", lids_linear, group_linear,
                 buffers: Dict[str, jnp.ndarray],
                 vregs: Dict[str, jnp.ndarray],
                 env: Optional[Dict[int, jnp.ndarray]] = None):
        self.prog = prog
        self.fn = prog.wg.fn
        self.L = lids_linear.shape[0]
        self.lids = lids_linear
        self.gl = group_linear
        self.buffers = dict(buffers)
        self.vregs = dict(vregs)
        self.env: Dict[int, jnp.ndarray] = dict(env or {})
        for nm, v in self.fn.arg_values.items():
            self.env[v.id] = prog.scalars[nm]

    # -- value plumbing ------------------------------------------------------
    def val(self, o):
        if isinstance(o, Value):
            return self.env[o.id]
        return o  # numpy literal folded by fold_constants

    def _varying(self, name: str) -> bool:
        return not self.prog.uni.vreg_uniform(name)

    def _bcast_vreg(self, name: str, x):
        if self._varying(name) and jnp.ndim(x) == 0:
            return jnp.broadcast_to(x, (self.L,))
        return x

    # -- ids -------------------------------------------------------------------
    def _id_op(self, op: str, dim: int):
        lsz = self.prog.lsz
        ngrp = self.prog.ngrp
        if op == "local_size":
            return jnp.int32(lsz[dim])
        if op == "num_groups":
            return jnp.int32(ngrp[dim])
        if op == "global_size":
            return jnp.int32(lsz[dim] * ngrp[dim])
        if op == "local_id":
            return self._local_id(dim)
        if op == "group_id":
            return self._group_id(dim)
        if op == "global_id":
            return self._group_id(dim) * lsz[dim] + self._local_id(dim)
        raise AssertionError(op)

    def _local_id(self, dim: int):
        lsz = self.prog.lsz
        lin = self.lids
        if dim == 0:
            return lax.rem(lin, jnp.int32(lsz[0]))
        if dim == 1:
            return lax.rem(lax.div(lin, jnp.int32(lsz[0])), jnp.int32(lsz[1]))
        return lax.div(lin, jnp.int32(lsz[0] * lsz[1]))

    def _group_id(self, dim: int):
        ngrp = self.prog.ngrp
        g = jnp.asarray(self.gl, jnp.int32)
        if dim == 0:
            return lax.rem(g, jnp.int32(ngrp[0]))
        if dim == 1:
            return lax.rem(lax.div(g, jnp.int32(ngrp[0])), jnp.int32(ngrp[1]))
        return lax.div(g, jnp.int32(ngrp[0] * ngrp[1]))

    # -- instruction execution --------------------------------------------------
    def exec_instr(self, ins: Instr, pred) -> None:
        op = ins.op
        if op == "vreg_read":
            name = ins.attrs["vreg"]
            if name not in self.vregs:
                dt = ins.attrs["dtype"]
                shape = (self.L,) if self._varying(name) else ()
                self.vregs[name] = jnp.zeros(shape, dt)
            r = self.vregs[name]
        elif op == "vreg_write":
            name = ins.attrs["vreg"]
            v = jnp.asarray(self.val(ins.operands[0]))
            old = self.vregs.get(name)
            if pred is None or old is None:
                nv = v if pred is None else jnp.where(pred, v, jnp.zeros_like(v))
            else:
                nv = jnp.where(pred, v, old)
            self.vregs[name] = self._bcast_vreg(name, nv)
            return
        elif op == "convert":
            r = jnp.asarray(self.val(ins.operands[0])).astype(ins.result.dtype)
        elif op in ir.BINOPS or op in ir.CMPOPS:
            a = jnp.asarray(self.val(ins.operands[0]))
            b = jnp.asarray(self.val(ins.operands[1]))
            r = _BIN_JAX[op](a, b)
            if op not in ir.CMPOPS:
                r = r.astype(ins.result.dtype)
        elif op in ir.UNOPS:
            a = jnp.asarray(self.val(ins.operands[0]))
            r = self._unop(op, a).astype(ins.result.dtype)
        elif op == "select":
            c, a, b = (jnp.asarray(self.val(o)) for o in ins.operands)
            r = jnp.where(c, a, b)
        elif op in ir.ID_OPS:
            r = self._id_op(op, ins.attrs["dim"])
        elif op == "load":
            buf = self.buffers[ins.attrs["buffer"]]
            idx = jnp.asarray(self.val(ins.operands[0]), jnp.int32)
            r = jnp.take(buf, idx, mode="clip")
        elif op == "store":
            buf = self.buffers[ins.attrs["buffer"]]
            idx = jnp.asarray(self.val(ins.operands[0]), jnp.int32)
            v = jnp.asarray(self.val(ins.operands[1]), buf.dtype)
            if pred is None:
                idx_b, v_b = jnp.broadcast_arrays(idx, v)
                self.buffers[ins.attrs["buffer"]] = buf.at[idx_b].set(v_b)
            else:
                idx_b, v_b, p = jnp.broadcast_arrays(idx, v, pred)
                safe = jnp.where(p, idx_b, jnp.int32(buf.shape[0]))
                self.buffers[ins.attrs["buffer"]] = \
                    buf.at[safe].set(v_b, mode="drop")
            return
        elif op == "barrier":
            raise AssertionError("barrier inside a parallel region")
        else:
            raise NotImplementedError(f"vector target: op {op}")
        if ins.result is not None:
            self.env[ins.result.id] = r

    def _unop(self, op: str, a):
        if self.prog.use_vml and op in _VML_OPS:
            from ... import vml
            return getattr(vml, _VML_OPS[op])(a)
        return _UN_JAX[op](a)

    # -- region execution ---------------------------------------------------------
    def exec_region(self, region: Region) -> Dict[str, object]:
        """Run a region; returns {exit barrier -> predicate} ('' for Return)."""
        if region.entry is None:
            return {}
        plan = self.prog.region_plans[region.barrier]
        exits: Dict[str, object] = {}
        self._exec_items(plan, region, entry_pred=None,
                         entry_block=region.entry, exits=exits)
        return exits

    def _exec_items(self, items: List[object], region: Region, entry_pred,
                    entry_block: str, exits: Dict[str, object]) -> None:
        fn = self.fn
        edge_preds: Dict[Tuple[str, str], object] = {}
        reached: Set[str] = set()

        def incoming(name: str, scope_blocks: Set[str]):
            ps = [edge_preds[(p, name)] for p in scope_blocks
                  if (p, name) in edge_preds]
            if name == entry_block:
                if ps:
                    return _por(ps + [entry_pred])
                return entry_pred
            if not ps:
                return "UNREACHED"
            return _por(ps)

        scope_blocks: Set[str] = set()
        for it in items:
            if isinstance(it, BlockNode):
                scope_blocks.add(it.name)
            else:
                scope_blocks |= it.blocks

        for it in items:
            if isinstance(it, BlockNode):
                name = it.name
                pred = incoming(name, scope_blocks)
                if isinstance(pred, str):
                    continue  # unreachable within this execution
                blk = fn.blocks[name]
                for ins in blk.instrs:
                    self.exec_instr(ins, pred)
                term = blk.terminator
                if isinstance(term, Jump):
                    self._route(term.target, pred, region, edge_preds, exits,
                                name)
                elif isinstance(term, CondBranch):
                    c = jnp.asarray(self.val(term.cond))
                    self._route(term.if_true, _pand(pred, c), region,
                                edge_preds, exits, name)
                    self._route(term.if_false, _pnot_and(pred, c), region,
                                edge_preds, exits, name)
                else:  # Return — terminal region
                    exits[""] = pred
            else:  # LoopNode
                pred_enter = incoming(it.header, scope_blocks)
                if isinstance(pred_enter, str):
                    continue
                self._exec_loop(it, region, pred_enter)
                self._route(it.exit_target, pred_enter, region, edge_preds,
                            exits, it.header)

    def _route(self, target: str, pred, region: Region,
               edge_preds, exits, src: str) -> None:
        if target in region.blocks:
            key = (src, target)
            if key in edge_preds:
                edge_preds[key] = _por([edge_preds[key], pred])
            else:
                edge_preds[key] = pred
        else:
            # region exit: successor barrier
            if target in exits:
                exits[target] = _por([exits[target], pred])
            else:
                exits[target] = pred

    # -- loops ------------------------------------------------------------------
    def _exec_loop(self, node: LoopNode, region: Region, pred_enter) -> None:
        fn = self.fn
        hdr = fn.blocks[node.header]
        term = hdr.terminator
        assert isinstance(term, CondBranch)
        cond_val = term.cond
        body_first = term.if_true == node.body_entry

        def exec_header(pred):
            for ins in hdr.instrs:
                self.exec_instr(ins, pred)
            c = jnp.asarray(self.val(cond_val))
            return c if body_first else jnp.logical_not(c)

        # values defined in the header survive the loop (they dominate the
        # exit block); latch them across iterations.
        header_vals = [ins.result for ins in hdr.instrs
                       if ins.result is not None]
        loop_vregs = sorted(self._vregs_written(node.blocks))
        buf_names = sorted(self.buffers)

        c0 = exec_header(pred_enter)
        scalar_path = (jnp.ndim(c0) == 0) and (
            pred_enter is None or jnp.ndim(pred_enter) == 0)

        # make sure every loop vreg exists before entering the carry
        for nm in loop_vregs:
            if nm not in self.vregs:
                dt = self._vreg_dtype(nm)
                shape = (self.L,) if self._varying(nm) else ()
                self.vregs[nm] = jnp.zeros(shape, dt)

        if scalar_path:
            # Lock-step loop with a scalar trip condition: this is the §4.6
            # horizontally-parallelized form — all work-items iterate together
            # and the body executes fully vectorized with no masks.
            c_init = c0 if pred_enter is None else jnp.logical_and(
                c0, pred_enter)
            carry0 = (jnp.asarray(c_init, jnp.bool_),
                      tuple(self.vregs[n] for n in loop_vregs),
                      tuple(self.buffers[n] for n in buf_names),
                      tuple(self.env[v.id] for v in header_vals))

            def cond_fn(carry):
                return carry[0]

            def body_fn(carry):
                _, vr, bufs, hv = carry
                sub = self._fork(vr, bufs, loop_vregs, buf_names,
                                 header_vals, hv)
                sub._exec_items(node.body_items, region,
                                entry_pred=pred_enter,
                                entry_block=node.body_entry, exits={})
                for ins in hdr.instrs:
                    sub.exec_instr(ins, pred_enter)
                c = jnp.asarray(sub.val(cond_val))
                c = c if body_first else jnp.logical_not(c)
                return (jnp.asarray(c, jnp.bool_),
                        tuple(sub.vregs[n] for n in loop_vregs),
                        tuple(sub.buffers[n] for n in buf_names),
                        tuple(sub.env[v.id] for v in header_vals))

            out = lax.while_loop(cond_fn, body_fn, carry0)
            _, vr, bufs, hv = out
        else:
            it0 = _pand(_as_lanes(pred_enter, self.L), _as_lanes(c0, self.L))
            hv0 = tuple(jnp.where(it0, self.env[v.id], self.env[v.id])
                        for v in header_vals)
            carry0 = (it0,
                      tuple(self.vregs[n] for n in loop_vregs),
                      tuple(self.buffers[n] for n in buf_names),
                      hv0)

            def cond_fn(carry):
                return jnp.any(carry[0])

            def body_fn(carry):
                it, vr, bufs, hv = carry
                sub = self._fork(vr, bufs, loop_vregs, buf_names,
                                 header_vals, hv)
                sub._exec_items(node.body_items, region, entry_pred=it,
                                entry_block=node.body_entry, exits={})
                for ins in hdr.instrs:
                    sub.exec_instr(ins, it)
                c = jnp.asarray(sub.val(cond_val))
                c = c if body_first else jnp.logical_not(c)
                new_hv = tuple(jnp.where(it, sub.env[v.id], old)
                               for v, old in zip(header_vals, hv))
                new_it = jnp.logical_and(it, _as_lanes(c, self.L))
                return (new_it,
                        tuple(sub.vregs[n] for n in loop_vregs),
                        tuple(sub.buffers[n] for n in buf_names),
                        new_hv)

            out = lax.while_loop(cond_fn, body_fn, carry0)
            _, vr, bufs, hv = out

        for n, v in zip(loop_vregs, vr):
            self.vregs[n] = v
        for n, v in zip(buf_names, bufs):
            self.buffers[n] = v
        for val, v in zip(header_vals, hv):
            self.env[val.id] = v

    def _fork(self, vr, bufs, loop_vregs, buf_names, header_vals, hv):
        sub = LaneExec.__new__(LaneExec)
        sub.prog = self.prog
        sub.fn = self.fn
        sub.L = self.L
        sub.lids = self.lids
        sub.gl = self.gl
        sub.env = dict(self.env)
        sub.vregs = dict(self.vregs)
        sub.buffers = dict(self.buffers)
        for n, v in zip(loop_vregs, vr):
            sub.vregs[n] = v
        for n, v in zip(buf_names, bufs):
            sub.buffers[n] = v
        for val, v in zip(header_vals, hv):
            sub.env[val.id] = v
        return sub

    def _vregs_written(self, blocks: Set[str]) -> Set[str]:
        out: Set[str] = set()
        for b in blocks:
            for ins in self.fn.blocks[b].instrs:
                if ins.op == "vreg_write":
                    out.add(ins.attrs["vreg"])
        return out

    def _vreg_dtype(self, name: str) -> str:
        for blk in self.fn.blocks.values():
            for ins in blk.instrs:
                if ins.op in ("vreg_read", "vreg_write") \
                        and ins.attrs["vreg"] == name:
                    return ins.attrs["dtype"]
        raise KeyError(name)


def _as_lanes(p, L: int):
    if p is None:
        return jnp.ones((L,), jnp.bool_)
    if jnp.ndim(p) == 0:
        return jnp.broadcast_to(p, (L,))
    return p


_BIN_JAX = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": lambda a, b: lax.div(a, b) if jnp.issubdtype(a.dtype, jnp.integer)
    else a / b,
    "rem": lambda a, b: lax.rem(a, b),
    "min": jnp.minimum, "max": jnp.maximum, "pow": jnp.power,
    "and": lambda a, b: a & b, "or": lambda a, b: a | b,
    "xor": lambda a, b: a ^ b,
    "shl": jnp.left_shift, "shr": jnp.right_shift,
    "lt": jnp.less, "le": jnp.less_equal, "gt": jnp.greater,
    "ge": jnp.greater_equal, "eq": jnp.equal, "ne": jnp.not_equal,
}

_UN_JAX = {
    "neg": jnp.negative,
    "not": lambda a: jnp.logical_not(a) if a.dtype == jnp.bool_ else ~a,
    "abs": jnp.abs, "exp": jnp.exp, "log": jnp.log, "sin": jnp.sin,
    "cos": jnp.cos, "tanh": jnp.tanh, "erf": jax.scipy.special.erf,
    "sqrt": jnp.sqrt, "rsqrt": lax.rsqrt, "floor": jnp.floor,
    "ceil": jnp.ceil, "rint": jnp.round,
}

# ops served by Vecmathlib (§5) when use_vml=True
_VML_OPS = {"exp": "exp", "log": "log", "sin": "sin", "cos": "cos",
            "tanh": "tanh", "erf": "erf", "sqrt": "sqrt", "rsqrt": "rsqrt"}


# ---------------------------------------------------------------------------
# Work-group program
# ---------------------------------------------------------------------------

class WGProgram:
    """A compiled work-group function for a fixed local size (the paper
    compiles one work-group function per local size at enqueue time, §4.1).

    This class is purely the target-specific *parallel mapping* half of
    the pipeline: it consumes a prebuilt, shared
    :class:`~repro.core.passes.WorkGroupPlan` (regions, schedule,
    uniformity facts, context slots, parallelism metadata) and binds it to
    a lane count.  It performs no region formation or analysis of its own —
    passing a raw :class:`Function` is a compatibility path that builds the
    plan through the pass manager first."""

    #: whether a launch may be wrapped in ``jax.jit`` (scalars become
    #: traced arguments); a target whose kernel needs them as constants
    #: says False and is launched as it is
    jittable = True

    def __init__(self, plan: "WorkGroupPlan | Function",
                 local_size: Sequence[int],
                 horizontal: bool = True, merge_uniform: bool = True,
                 use_vml: bool = False):
        self.lsz = tuple(local_size) + (1,) * (3 - len(local_size))
        self.L = int(np.prod(self.lsz))
        self.use_vml = use_vml
        self.horizontal = horizontal

        if not isinstance(plan, WorkGroupPlan):
            plan = build_plan(plan, horizontal=horizontal,
                              merge_uniform=merge_uniform)
        self.wgplan: WorkGroupPlan = plan
        self.wg: WGInfo = plan.wg
        self.uni = plan.uni
        self.plan: ContextPlan = plan.ctx
        self.region_plans = plan.region_plans
        self.md = plan.md
        self.order = self.wg.order
        self.rid_of = {b: i for i, b in enumerate(self.order)}
        self.K = len(self.order)
        # filled per launch
        self.scalars: Dict[str, jnp.ndarray] = {}
        self.ngrp = (1, 1, 1)

    # -- context helpers -------------------------------------------------------
    def _ctx_init(self):
        out = []
        for s in self.plan.slots:
            shape = () if s.uniform else (self.L,)
            out.append(jnp.zeros(shape, s.dtype))
        return tuple(out)

    def _seed(self, ex: LaneExec, ctx) -> None:
        for s, v in zip(self.plan.slots, ctx):
            if s.kind == "val":
                ex.env[s.key] = v
            else:
                ex.vregs[s.key] = v

    def _harvest(self, ex: LaneExec, ctx):
        out = []
        for s, old in zip(self.plan.slots, ctx):
            if s.kind == "val":
                v = ex.env.get(s.key, old)
            else:
                v = ex.vregs.get(s.key, old)
            if not s.uniform and jnp.ndim(v) == 0:
                v = jnp.broadcast_to(v, (self.L,))
            elif s.uniform and jnp.ndim(v) > jnp.ndim(old):
                # the executor may represent a (provably) uniform value
                # lane-broadcast; collapse to lane 0 to keep the carry
                # type stable across regions
                v = jnp.asarray(v)[0]
            out.append(jnp.asarray(v).astype(s.dtype))
        return tuple(out)

    # -- single work-group execution --------------------------------------------
    def run_wg(self, buffers: Dict[str, jnp.ndarray], group_linear,
               lids_linear=None):
        """Execute one work-group. ``buffers`` threaded functionally."""
        lids = jnp.arange(self.L, dtype=jnp.int32) if lids_linear is None \
            else lids_linear
        buf_names = sorted(buffers)
        ctx = self._ctx_init()

        def run_region(bar: str, ctx, bufs_t):
            bufs = dict(zip(buf_names, bufs_t))
            ex = LaneExec(self, lids, group_linear, bufs, {})
            self._seed(ex, ctx)
            exits = ex.exec_region(self.wg.regions[bar])
            new_ctx = self._harvest(ex, ctx)
            new_bufs = tuple(ex.buffers[n] for n in buf_names)
            # next region id from lane 0 (peeled first work-item, §4.4)
            rid = jnp.int32(self.K)
            for tgt, pred in exits.items():
                if tgt == "":
                    continue
                p0 = pred if pred is None or jnp.ndim(pred) == 0 \
                    else pred[0]
                t = jnp.int32(self.rid_of[tgt])
                rid = t if p0 is None else jnp.where(p0, t, rid)
            return rid, new_ctx, new_bufs

        bufs_t = tuple(buffers[n] for n in buf_names)
        if self.wg.is_chain():
            for bar in self.wg.chain():
                _, ctx, bufs_t = run_region(bar, ctx, bufs_t)
            return dict(zip(buf_names, bufs_t))

        # general scheduler: while(switch(rid))
        branches = [
            (lambda bar: (lambda st: run_region(bar, st[1], st[2])))(bar)
            for bar in self.order]

        def cond_fn(st):
            return st[0] < self.K

        def body_fn(st):
            return lax.switch(st[0], branches, st)

        st0 = (jnp.int32(0), ctx, bufs_t)
        _, ctx, bufs_t = lax.while_loop(cond_fn, body_fn, st0)
        return dict(zip(buf_names, bufs_t))

    # -- NDRange execution ------------------------------------------------------
    def run_ndrange(self, buffers: Dict[str, np.ndarray],
                    scalars: Optional[Dict[str, object]],
                    global_size: Sequence[int],
                    group_range: Optional[Tuple[int, int]] = None):
        """Execute the NDRange.  ``group_range=(lo, hi)`` runs only that
        contiguous range of linearized work-groups *of the full NDRange*
        (group-id decoding still uses the full grid) — the sub-range unit
        the multi-device co-execution scheduler dispatches
        (runtime/scheduler.py); ``None`` runs every group."""
        gsz = tuple(global_size) + (1,) * (3 - len(global_size))
        for g, l in zip(gsz, self.lsz):
            assert g % l == 0, "global size must divide local size"
        self.ngrp = tuple(g // l for g, l in zip(gsz, self.lsz))
        n_groups = int(np.prod(self.ngrp))
        self.scalars = {}
        scalars = scalars or {}
        for a in self.wg.fn.scalar_args:
            self.scalars[a.name] = jnp.asarray(scalars[a.name], a.dtype)

        local_defs = [a for a in self.wg.fn.buffer_args
                      if a.space == ir.LOCAL and a.name not in buffers]
        bufs = {k: jnp.asarray(v) for k, v in buffers.items()}
        global_names = sorted(bufs)

        def one_group(g, bufs_t):
            b = dict(zip(global_names, bufs_t))
            for la in local_defs:
                b[la.name] = jnp.zeros(la.size, la.dtype)
            out = self.run_wg(b, g)
            return tuple(out[n] for n in global_names)

        lo, hi = (0, n_groups) if group_range is None \
            else (int(group_range[0]), int(group_range[1]))
        assert 0 <= lo <= hi <= n_groups, \
            f"group_range {group_range} outside [0, {n_groups}]"
        bufs_t = tuple(bufs[n] for n in global_names)
        if hi - lo == 1:
            bufs_t = one_group(jnp.int32(lo), bufs_t)
        elif hi > lo:
            bufs_t = lax.fori_loop(
                lo, hi, lambda g, bt: one_group(jnp.int32(g), bt),
                bufs_t)
        return dict(zip(global_names, bufs_t))
