"""Grid-tiled Pallas lowering for canonical nests (the planner's back half).

``emit_nest`` turns one canonical nest — planned by
``repro.core.tiling.plan_nest_tiling`` — into a single ``pl.pallas_call``:

* every distinct affine access map becomes its own operand: the array is
  padded by the plan's halo and shifted so the access's origin
  (loop start + constant offset) lands on element 0 of the view, which makes
  each *view* exactly block-aligned — the BlockSpec is then read straight off
  the access map (tile sizes as the block shape, grid indices as the index
  map).  Overlapping stencil reads are separate operands of the same padded
  array, the standard Pallas way to express halos without losing pipelining;
* written arrays are passed twice — once as an input aliased onto the output
  (``input_output_aliases``) so the kernel can blend new values with old
  content under guard/bounds masks and partial tiles never clobber rows they
  do not own;
* reductions accumulate through a VMEM scratch block across an innermost
  'arbitrary' grid dimension (the GEMM pattern generalized to +, *, max,
  min), with the recipe's ``unroll`` factor splitting the in-tile reduction
  into sequentially accumulated chunks;
* guards and bounds become an in-kernel mask over broadcasted iotas; masked
  lanes keep old content (assignments) or contribute the accumulate's
  neutral element (reductions).

Everything is validated on CPU in interpret mode against the
``execute_numpy`` oracle; on a TPU the kernel compiles (grid dims are
declared parallel/arbitrary accordingly).
"""
from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.codegen import _ACC_INIT, _ACC_REDUCE, _combine
from ..core.ir import Access, Computation, Node, Program
from ..core.tiling import TilePlan, TilingError, plan_nest_tiling

# trace-time lowering counters (tests assert the Pallas path actually ran)
EMITTED = {"pallas_nest": 0, "pallas_reduce": 0}


def emit_nest(
    program: Program,
    nest: Node,
    env: dict[str, Any],
    schedule,
    lowering=jax.named_scope,
) -> dict[str, Any]:
    """Lower one canonical nest via ``pl.pallas_call``; raises ``TilingError``
    (an ``Unsupported``) when the nest is outside the tiled class.  The
    kernel is emitted under ``lowering(kind)``, ``kind`` being
    ``pallas_nest`` or ``pallas_reduce``."""
    plan = plan_nest_tiling(
        program, nest, tile=schedule.nest_tile, vmem_budget=schedule.vmem_budget
    )
    if plan.kind == "reduce" and not schedule.pallas_reduce:
        raise TilingError("reduction nest but pallas_reduce disabled")
    if plan.kind == "parallel" and not schedule.pallas_nest:
        raise TilingError("parallel nest but pallas_nest disabled")

    kind = "pallas_nest" if plan.kind == "parallel" else "pallas_reduce"
    with lowering(kind):
        out_env = _KernelBuilder(program, plan, env,
                                 unroll=max(1, int(schedule.unroll)),
                                 interpret=schedule.interpret_kernels).build()
    EMITTED[kind] += 1
    return out_env


class _KernelBuilder:
    def __init__(self, program: Program, plan: TilePlan, env, *, unroll, interpret):
        self.p = program
        self.plan = plan
        self.env = env
        self.unroll = unroll
        self.interpret = interpret
        self.axes = plan.axes
        self.axis_of = plan.axis_of
        self.iter_of = plan.iter_of
        self.n_par = len(plan.parallel)
        self._padded: dict[str, Any] = {}

    # -- host-side operand views --------------------------------------------
    def _padded_array(self, name: str):
        if name not in self._padded:
            arr = self.env[name]
            pads = self.plan.halo.get(name, ((0, 0),) * arr.ndim)
            self._padded[name] = jnp.pad(arr, pads) if any(
                lo or hi for lo, hi in pads) else arr
        return self._padded[name]

    def _view_and_spec(self, a: Access):
        """Shifted view of the padded array + the BlockSpec read off the
        access map.  Inside the view, grid block ``g`` of iterator ``it``
        covers exactly elements ``[g*tile, (g+1)*tile)``."""
        base = self._padded_array(a.array)
        pads = self.plan.halo.get(a.array, ((0, 0),) * base.ndim)
        starts, sizes, blocks, srcs = [], [], [], []
        for d, dm in enumerate(self.plan.access_dims(a)):
            lo = pads[d][0]
            if dm.iterator is None:
                starts.append(lo + dm.const)
                sizes.append(1)
                blocks.append(1)
                srcs.append(None)
            else:
                ti = self.iter_of[dm.iterator]
                starts.append(lo + ti.start + dm.const)
                sizes.append(ti.n_tiles * ti.tile)
                blocks.append(ti.tile if ti.role != "reduce_inner" else ti.trip)
                if ti.role == "parallel":
                    srcs.append(self.plan.parallel.index(ti))
                elif ti.role == "reduce_grid":
                    srcs.append(self.n_par)
                else:
                    srcs.append(None)
        view = lax.slice(base, starts, [s + z for s, z in zip(starts, sizes)])

        def index_map(*gids, _srcs=tuple(srcs)):
            return tuple(gids[s] if s is not None else 0 for s in _srcs)

        return view, pl.BlockSpec(tuple(blocks), index_map)

    # -- in-kernel helpers ---------------------------------------------------
    def _slab_shape(self, used: set[str]) -> tuple[int, ...]:
        return tuple(
            (ax.tile if ax.role != "reduce_inner" else ax.trip)
            if ax.name in used else 1
            for ax in self.axes
        )

    def _align(self, block, dims, used: set[str]):
        """Reorder a loaded block (array-dim order) into the canonical slab
        axis order, singleton-broadcasting the axes it does not own."""
        keep = [d for d, dm in enumerate(dims) if dm.iterator is not None]
        block = block.reshape([block.shape[d] for d in keep])
        order = sorted(range(len(keep)),
                       key=lambda i: self.axis_of[dims[keep[i]].iterator])
        if order != list(range(len(keep))):
            block = jnp.transpose(block, order)
        shape = [1] * len(self.axes)
        for d in keep:
            ti = self.iter_of[dims[d].iterator]
            shape[self.axis_of[ti.name]] = (
                ti.tile if ti.role != "reduce_inner" else ti.trip)
        return block.reshape(shape)

    def _to_write_layout(self, slab, wdims):
        """Project a full-rank slab onto a write block (array-dim order)."""
        w_axes = [self.axis_of[dm.iterator] for dm in wdims if dm.iterator]
        drop = [k for k in range(len(self.axes)) if k not in w_axes]
        slab = slab.reshape([s for k, s in enumerate(slab.shape) if k not in drop])
        order_axes = sorted(w_axes)
        perm = [order_axes.index(self.axis_of[dm.iterator])
                for dm in wdims if dm.iterator]
        if perm != list(range(len(perm))):
            slab = jnp.transpose(slab, perm)
        # re-insert size-1 dims for constant write subscripts
        shape = []
        it_dims = iter(range(slab.ndim))
        for dm in wdims:
            shape.append(slab.shape[next(it_dims)] if dm.iterator else 1)
        return slab.reshape(shape)

    def _iota(self, gids, it_name: str, shape):
        ti = self.iter_of[it_name]
        ax = self.axis_of[it_name]
        if ti.role == "parallel":
            base = ti.start + gids[self.plan.parallel.index(ti)] * ti.tile
        elif ti.role == "reduce_grid":
            base = ti.start + gids[self.n_par] * ti.tile
        else:
            base = ti.start
        return base + lax.broadcasted_iota(jnp.int32, shape, ax)

    def _mask(self, gids, comp: Computation, used: set[str], shape):
        m = None
        for it in used:
            ti = self.iter_of[it]
            cur = self._iota(gids, it, shape) < ti.stop
            m = cur if m is None else m & cur
        for g in comp.guards:
            val = g.const
            for it, c in g.coeffs:
                val = val + c * self._iota(gids, it, shape)
            cur = val >= 0
            m = cur if m is None else m & cur
        return m

    # -- assembly ------------------------------------------------------------
    def build(self) -> dict[str, Any]:
        plan = self.plan
        in_views, in_specs, op_of = [], [], {}

        def operand(a: Access) -> int:
            key = (a.array, a.index)
            if key not in op_of:
                view, spec = self._view_and_spec(a)
                op_of[key] = len(in_views)
                in_views.append(view)
                in_specs.append(spec)
            return op_of[key]

        written: list[str] = []
        write_acc: dict[str, Access] = {}
        for c in plan.comps:
            for r in c.reads:
                operand(r)
            if c.write.array not in written:
                written.append(c.write.array)
                write_acc[c.write.array] = c.write
        # old-content operands, aliased onto the outputs
        aliases = {}
        out_shapes, out_specs = [], []
        for oi, name in enumerate(written):
            w = write_acc[name]
            idx = operand(w)
            aliases[idx] = oi
            view, spec = self._view_and_spec(w)
            out_shapes.append(jax.ShapeDtypeStruct(view.shape, view.dtype))
            out_specs.append(spec)

        n_in = len(in_views)
        n_grid = len(plan.grid)
        scratch = []
        if plan.kind == "reduce":
            wdims = plan.access_dims(plan.comps[0].write)
            acc_shape = tuple(
                self.iter_of[dm.iterator].tile if dm.iterator else 1
                for dm in wdims
            )
            scratch.append(pltpu.VMEM(acc_shape, jnp.float32))

        kernel = functools.partial(self._kernel, n_in=n_in, n_out=len(written),
                                   written=tuple(written),
                                   write_acc=write_acc, op_of=dict(op_of),
                                   n_grid=n_grid)
        semantics = ["parallel"] * self.n_par
        if plan.reduce_grid is not None:
            semantics.append("arbitrary")
        outs = pl.pallas_call(
            kernel,
            grid=plan.grid,
            in_specs=in_specs,
            out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
            out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
            scratch_shapes=scratch,
            input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=tuple(semantics)),
            interpret=self.interpret,
        )(*in_views)
        if len(written) == 1:
            outs = [outs]

        # write the valid region of each output view back into the array
        env = dict(self.env)
        for name, out in zip(written, outs):
            arr = env[name]
            w = write_acc[name]
            starts, sizes = [], []
            for d, dm in enumerate(self.plan.access_dims(w)):
                if dm.iterator is None:
                    starts.append(dm.const)
                    sizes.append(1)
                else:
                    ti = self.iter_of[dm.iterator]
                    starts.append(ti.start + dm.const)
                    sizes.append(ti.trip)
            valid = lax.slice(out, [0] * out.ndim, sizes)
            env[name] = lax.dynamic_update_slice(
                arr, valid.astype(arr.dtype), starts)
        return env

    # -- the kernel body -----------------------------------------------------
    def _kernel(self, *refs, n_in, n_out, written, write_acc, op_of, n_grid):
        ins = refs[:n_in]
        outs = refs[n_in:n_in + n_out]
        acc_ref = refs[n_in + n_out] if len(refs) > n_in + n_out else None
        gids = [pl.program_id(d) for d in range(n_grid)]
        plan = self.plan
        slab_env: dict[str, tuple[tuple, Any]] = {}  # array -> (index, slab)

        def load(a: Access, used: set[str]):
            if a.array in slab_env and slab_env[a.array][0] == a.index:
                return slab_env[a.array][1]
            block = ins[op_of[(a.array, a.index)]][...]
            return self._align(block, plan.access_dims(a), used)

        for comp in plan.comps:
            used = {it for it in comp.iterators() if it in self.axis_of}
            shape = self._slab_shape(used)
            rvals = [load(r, used) for r in comp.reads]
            val = comp.expr(*rvals)
            val = jnp.broadcast_to(val, jnp.broadcast_shapes(jnp.shape(val), shape))
            mask = self._mask(gids, comp, used, shape)
            wdims = plan.access_dims(comp.write)
            oi = written.index(comp.write.array)

            if plan.kind == "reduce":
                old_ref = ins[op_of[(comp.write.array, comp.write.index)]]
                self._emit_reduce(comp, val, mask, wdims, gids, old_ref,
                                  outs[oi], acc_ref)
                continue

            old = load(comp.write, used)
            new = val if comp.accumulate is None else _combine(
                comp.accumulate, old, val)
            merged = jnp.where(mask, new, old) if mask is not None else new
            outs[oi][...] = self._to_write_layout(merged, wdims).astype(
                outs[oi].dtype)
            slab_env[comp.write.array] = (comp.write.index, merged)

    def _emit_reduce(self, comp, val, mask, wdims, gids, old_ref, o_ref, acc_ref):
        # the old content comes from the aliased input block: on a TPU an
        # output block is never loaded from HBM, so reading o_ref would see
        # whatever an earlier block left in that VMEM buffer
        plan = self.plan
        op = comp.accumulate
        neutral = _ACC_INIT[op]
        if mask is not None:
            val = jnp.where(mask, val, neutral)
        red_axes = [self.axis_of[a.name] for a in plan.reduce_inner]
        g_ax = self.axis_of[plan.reduce_grid.name]
        redfn = _ACC_REDUCE[op]
        if red_axes:
            val = redfn(val, axis=tuple(red_axes), keepdims=True)
        # recipe's unroll knob: accumulate the grid-tiled reduction axis in
        # `unroll` sequentially combined chunks
        tile_r = val.shape[g_ax]
        u = self.unroll if tile_r % max(1, self.unroll) == 0 else 1
        if u > 1:
            chunk = tile_r // u
            parts = None
            for k in range(u):
                piece = lax.slice_in_dim(val, k * chunk, (k + 1) * chunk,
                                         axis=g_ax)
                piece = redfn(piece, axis=g_ax, keepdims=True)
                parts = piece if parts is None else _combine(op, parts, piece)
            val = parts
        else:
            val = redfn(val, axis=g_ax, keepdims=True)
        partial = self._to_write_layout(val, wdims).astype(jnp.float32)

        k_red = gids[self.n_par]
        n_red = plan.reduce_grid.n_tiles

        @pl.when(k_red == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref) + neutral

        acc_ref[...] = _combine(op, acc_ref[...], partial)

        @pl.when(k_red == n_red - 1)
        def _done():
            o_ref[...] = _combine(op, old_ref[...],
                                  acc_ref[...]).astype(o_ref.dtype)
