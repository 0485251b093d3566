"""Flat-buffer server step: the whole aggregation round as ONE compiled
program.

The reference server step (``fl.fedavg`` + per-client ``compress_tree``)
walks a Python loop of per-leaf, per-client jnp ops — O(K x leaves) device
dispatches per round, which makes the *server* the slowest code in a repo
whose premise (paper §IV) is that the server outpaces the IoT clients.
This module replaces it with a flatten-once layout plus a fused pipeline:

* ``FlatLayout`` — computed once per parameter structure and cached: every
  leaf is assigned a block-aligned segment of one contiguous fp32 buffer
  (offset table host-side, zero padding between segments).  ``flatten`` /
  ``unflatten`` are bitwise inverses for fp32/bf16 params (pure
  reshape/pad/concat — no arithmetic), so a round-trip through the flat
  domain never perturbs a checkpoint.  Block alignment (default 1024, the
  top-k block) means no compression block ever straddles two leaves, which
  is what makes the fused top-k *equal* to the per-leaf reference — each
  block's ``(valid, k)`` metadata comes from the true leaf size
  (kernels/topk_compress density semantics).

* ``ServerStep`` — one jitted, donated program over the flat buffer:
  client deltas stacked on a leading axis ``(K, n)``, error-feedback
  carry-in, block-local top-k sparsification (Stich et al.,
  arXiv:1809.07599), optional int8 quantize->dequantize of the sent rows
  (the wire format of a compressed delta upload), weighted reduction, and
  apply-to-global — 1 device dispatch where the reference issues
  O(K x leaves).  Plain averaging is a single (K,) @ (K, n) matvec; the
  compression pipeline streams client rows through an in-program
  ``lax.scan`` so peak memory stays O(n), not O(K x n).  Executables are
  cached per ``(layout, density, quantize)`` by ``get_server_step`` and
  per ``K`` by jax's jit cache, so sync (fl/loop.py), async
  (fl/async_loop.py) and both fleet engines reuse the same compiled step
  across rounds.

Numerics contract: the fused weighted reduction is a single fp32 matvec
where the reference accumulates client-by-client — results agree to fp32
tolerance, not bitwise (the only place the PR 3 guarantees are relaxed;
see docs/API.md).  Sync and async stay *bitwise equal to each other*
because both call the same compiled programs on the same operands, and
checkpoint-resume stays bitwise because flatten/unflatten are exact.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk_compress.ops import (
    compress_tree,
    density_block_meta,
    topk_compress_flat,
)

Params = Any


class FlatLayout:
    """Flatten-once layout for one parameter structure: per-leaf
    (shape, dtype, offset, size) with offsets aligned to ``block`` so no
    compression block straddles a leaf boundary.  Instances are cached by
    ``layout_of`` — hold onto one and its jitted flatten/unflatten
    executables amortize across every round of every loop."""

    def __init__(self, tree: Params, block: int = 1024):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        self.block = int(block)
        self.treedef = treedef
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.asarray(l).dtype for l in leaves)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        self.segs = tuple(-(-sz // self.block) * self.block
                          for sz in self.sizes)
        offs, off = [], 0
        for seg in self.segs:
            offs.append(off)
            off += seg
        self.offsets = tuple(offs)
        self.size = int(sum(self.sizes))      # true element count
        self.padded = int(off)                # buffer length (block-aligned)
        # fp32 params round-trip through the flat domain without rounding,
        # so a flat master buffer never drifts from the unflattened params;
        # narrower dtypes need a resync after every unflatten (fl/loop.py)
        self.exact_fp32 = all(d == jnp.float32 for d in self.dtypes)
        self._meta: Dict[float, np.ndarray] = {}
        # x * 1.0 (not x + 0.0, which flips -0.0) forces a fresh buffer:
        # a jitted identity would alias its input, and the caller (e.g. the
        # async loop publishing to a ParamStore) keeps using the source
        self._copy = jax.jit(lambda buf: buf * jnp.float32(1.0))
        self._flatten = jax.jit(self._flatten_impl)
        self._flatten_stacked = jax.jit(self._flatten_stacked_impl)
        self._unflatten = jax.jit(self._unflatten_impl)
        self._deltas_list = jax.jit(self._deltas_list_impl)
        self._deltas_stacked = jax.jit(self._deltas_stacked_impl)

    # -- bitwise flatten / unflatten --------------------------------------
    def _flatten_impl(self, tree: Params) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(tree)
        parts = []
        for leaf, sz, seg in zip(leaves, self.sizes, self.segs):
            v = jnp.asarray(leaf).reshape(-1).astype(jnp.float32)
            parts.append(jnp.pad(v, (0, seg - sz)) if seg > sz else v)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def _flatten_stacked_impl(self, tree: Params) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(tree)
        K = leaves[0].shape[0]
        parts = []
        for leaf, sz, seg in zip(leaves, self.sizes, self.segs):
            v = jnp.asarray(leaf).reshape(K, -1).astype(jnp.float32)
            parts.append(jnp.pad(v, ((0, 0), (0, seg - sz)))
                         if seg > sz else v)
        return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]

    def _unflatten_impl(self, buf: jnp.ndarray) -> Params:
        leaves = [buf[off:off + sz].reshape(shape).astype(dtype)
                  for off, sz, shape, dtype in
                  zip(self.offsets, self.sizes, self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def _deltas_list_impl(self, rows: tuple, g_flat: jnp.ndarray
                          ) -> jnp.ndarray:
        stacked = jnp.stack([self._flatten_impl(r) for r in rows])
        return stacked - g_flat[None]

    def _deltas_stacked_impl(self, tree: Params, g_flat: jnp.ndarray
                             ) -> jnp.ndarray:
        return self._flatten_stacked_impl(tree) - g_flat[None]

    def flatten(self, tree: Params) -> jnp.ndarray:
        """Pytree -> contiguous fp32 ``(padded,)`` buffer (one dispatch)."""
        return self._flatten(tree)

    def unflatten(self, buf: jnp.ndarray) -> Params:
        """Exact inverse of ``flatten`` (padding dropped, dtypes restored)."""
        return self._unflatten(buf)

    def copy(self, buf: jnp.ndarray) -> jnp.ndarray:
        """Bitwise copy into a fresh buffer (one jitted dispatch) — the
        publish primitive of ``serving.hotswap.ParamStore``: the returned
        buffer shares no storage with ``buf``, so the producer may donate
        or overwrite its own copy immediately."""
        return self._copy(buf)

    def rows_to_deltas(self, rows, g_flat: jnp.ndarray) -> jnp.ndarray:
        """Client parameter rows -> stacked fp32 deltas ``(R, padded)`` vs
        the flat global, in one dispatch.  ``rows`` is either a list of
        per-client pytrees (sequential engine) or a ``StackedRows``-style
        pytree with a leading client axis (batched engine)."""
        from repro.fl.fleet import StackedRows
        if isinstance(rows, StackedRows):
            return self._deltas_stacked(rows.tree, g_flat)
        return self._deltas_list(tuple(rows), g_flat)

    # -- compression metadata ---------------------------------------------
    def block_meta(self, density: float) -> np.ndarray:
        """Per-block ``(valid, k)`` rows over the whole buffer: each leaf's
        blocks get their budget from the leaf's true (unpadded) element
        count, and inter-leaf padding lanes are masked out."""
        key = round(float(density), 12)
        if key not in self._meta:
            self._meta[key] = np.concatenate(
                [density_block_meta(sz, self.block, density)
                 for sz in self.sizes], axis=0)
        return self._meta[key]


class ShardedFlatLayout(FlatLayout):
    """FlatLayout over a ``(data, model)`` device mesh
    (``parallel.sharding.make_flat_mesh``): the flat parameter vector is
    laid out along the ``model`` axis in whole compression blocks, stacked
    client rows along ``data``.

    The buffer gains a tail pad of ``flat_shard_tail(...)`` elements so its
    block count divides the model-axis size — the flat-vector fix for the
    ``AxisRules`` divisibility fallback, which would otherwise *replicate*
    (see parallel/sharding.py).  The tail is masked out of the compression
    metadata with ``(valid=0, k=1)`` rows and is zero in every delta / EF
    row by construction, so it never contributes to an update.
    ``flatten`` / ``rows_to_deltas`` hand back mesh-resident buffers
    (computed single-device, then placed with ``jax.device_put``);
    ``unflatten`` reads only the true leaf segments, so round-trips stay
    bitwise exactly as in the base layout."""

    def __init__(self, tree: Params, mesh, block: int = 1024):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import flat_shard_tail
        super().__init__(tree, block=block)
        if not {"data", "model"} <= set(mesh.shape):
            raise ValueError(f"mesh axes {tuple(mesh.shape)} must include "
                             f"'data' and 'model'")
        self.mesh = mesh
        self.data_size = int(mesh.shape["data"])
        self.model_size = int(mesh.shape["model"])
        self.base_padded = self.padded
        self.tail = flat_shard_tail(self.padded, self.block, self.model_size)
        self.padded += self.tail
        self.shard_elems = self.padded // self.model_size
        self.vec_sharding = NamedSharding(mesh, P("model"))
        self.rows_sharding = NamedSharding(mesh, P(None, "model"))
        self.stack_sharding = NamedSharding(mesh, P("data", "model"))
        # Keep the base class's plain jitted executables and re-place their
        # results with jax.device_put.  Forcing ``out_shardings`` (or letting
        # GSPMD propagate a sharded operand) through the concatenate-of-leaf-
        # segments program mis-places whole segments on meshes whose ``data``
        # axis is > 1 (observed on the CPU partitioner: wrong *values*, not
        # just wrong layout).  device_put after the fact is pure data
        # movement, so the buffers stay bitwise identical to the legacy
        # layout while still landing mesh-resident.  The delta paths subtract
        # the sharded global only after both operands carry the same
        # placement; unflatten gathers the buffer first so the slice-per-leaf
        # program never runs under the partitioner.
        _rep = NamedSharding(mesh, P())
        # inputs get the same treatment: the mesh-parallel batched fleet
        # engine hands back rows whose leaves may still carry a data-axis
        # sharding (it gathers chunk outputs itself, but e.g. a caller
        # passing sharded arrays directly must not re-trigger the bug), so
        # every tree is pinned to one device before the plain flatten runs
        _home = mesh.devices.flat[0]
        _fl, _fs = self._flatten, self._flatten_stacked
        _unfl = self._unflatten
        _stack = jax.jit(
            lambda rows: jnp.stack([self._flatten_impl(r) for r in rows]))
        _sub = jax.jit(lambda s, g: s - g[None])
        self._flatten = lambda t: jax.device_put(
            _fl(jax.device_put(t, _home)), self.vec_sharding)
        self._flatten_stacked = lambda t: jax.device_put(
            _fs(jax.device_put(t, _home)), self.rows_sharding)
        self._unflatten = lambda buf: _unfl(jax.device_put(buf, _rep))
        self._deltas_list = lambda rows, g: _sub(
            jax.device_put(_stack(jax.device_put(rows, _home)),
                           self.rows_sharding),
            jax.device_put(g, self.vec_sharding))
        self._deltas_stacked = lambda tree, g: _sub(
            jax.device_put(_fs(jax.device_put(tree, _home)),
                           self.rows_sharding),
            jax.device_put(g, self.vec_sharding))

    # tail-padded variants of the bitwise flatten family: identical leaf
    # segments, plus `tail` zero lanes so padded % (block * model) == 0
    def _flatten_impl(self, tree: Params) -> jnp.ndarray:
        flat = super()._flatten_impl(tree)
        return jnp.pad(flat, (0, self.tail)) if self.tail else flat

    def _flatten_stacked_impl(self, tree: Params) -> jnp.ndarray:
        flat = super()._flatten_stacked_impl(tree)
        return (jnp.pad(flat, ((0, 0), (0, self.tail)))
                if self.tail else flat)

    def block_meta(self, density: float) -> np.ndarray:
        """Base per-leaf ``(valid, k)`` rows plus ``(0, 1)`` rows masking
        the tail shard's padding blocks (they select lane 0 of an all-zero
        block, so output and error feedback stay exactly zero there)."""
        key = round(float(density), 12)
        if key not in self._meta:
            rows = np.concatenate(
                [density_block_meta(sz, self.block, density)
                 for sz in self.sizes], axis=0)
            if self.tail:
                pad_rows = np.tile(np.asarray([[0, 1]], np.int32),
                                   (self.tail // self.block, 1))
                rows = np.concatenate([rows, pad_rows], axis=0)
            self._meta[key] = rows
        return self._meta[key]


_LAYOUT_CACHE: Dict[tuple, FlatLayout] = {}


def layout_of(tree: Params, block: int = 1024, mesh=None) -> FlatLayout:
    """Resolve (and cache) the FlatLayout for a parameter structure.  Two
    trees with the same treedef/shapes/dtypes share one layout — and with
    it the jitted flatten/unflatten/server-step executables.  ``mesh``
    (a ``(data, model)`` Mesh) selects the ``ShardedFlatLayout`` variant;
    ``None`` is the exact legacy single-device layout."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    key = (treedef, tuple(tuple(l.shape) for l in leaves),
           tuple(str(jnp.asarray(l).dtype) for l in leaves), int(block),
           mesh)
    if key not in _LAYOUT_CACHE:
        _LAYOUT_CACHE[key] = (
            FlatLayout(tree, block=block) if mesh is None
            else ShardedFlatLayout(tree, mesh, block=block))
    return _LAYOUT_CACHE[key]


def _normalized_f64(weights: Sequence[float]) -> np.ndarray:
    w = np.asarray(weights, np.float64)
    return w / w.sum()


class ServerStep:
    """The fused server round over the flat buffer.  Call with the flat
    global, stacked deltas, per-client weights and (when ``density < 1``)
    the matching error-feedback rows; returns the new flat global and the
    new error rows.  ``calls`` counts jitted invocations — the whole round
    is exactly one."""

    def __init__(self, layout: FlatLayout, density: float = 1.0,
                 quantize: bool = False, interpret: Optional[bool] = None):
        self.layout = layout
        self.density = float(density)
        self.quantize = bool(quantize)
        self.interpret = interpret
        self.track_errors = self.density < 1.0
        self.calls = 0
        if self.track_errors:
            meta = layout.block_meta(self.density)
            self._meta = meta
            self._kmax = int(meta[:, 1].max())
        # donate the (K, n) error rows on every backend: the new rows are
        # written in their place, so a caller must not read the rows it
        # passed in afterwards (the deltas have no output to alias)
        self._step = jax.jit(self._step_impl, donate_argnums=(
            (3,) if self.track_errors else ()))
        # reduce's signature drops the leading global: err shifts left
        self._reduce = jax.jit(self._reduce_core, donate_argnums=(
            (2,) if self.track_errors else ()))
        self.reduce_calls = 0

    def _reduce_core(self, deltas: jnp.ndarray, w: jnp.ndarray,
                     err: Optional[jnp.ndarray],
                     masks: Optional[jnp.ndarray] = None):
        """The weighted reduction shared by the flat step and the two-tier
        edge tier: ``(acc, den, new_err)`` where ``acc`` is the weighted
        (masked) sum of the sent rows, ``den`` the per-coordinate covered
        weight (``None`` when unmasked), ``new_err`` the updated EF rows.
        ``_step_impl`` is exactly reduce-then-apply, so the single-tier
        program's graph is unchanged by the refactor."""
        block = self.layout.block
        if not self.track_errors and not self.quantize:
            if masks is None:
                # plain weighted averaging: ONE (K,) @ (K, n) matvec
                return w @ deltas, None, None
            # cross-width averaging (HeteroFL): per-coordinate coverage —
            # each coordinate averages over the clients whose width mask
            # covers it; uncovered coordinates keep the global bitwise.
            # Still one dispatch: two matvecs (the guarded divide is the
            # caller's apply step).
            return w @ (masks * deltas), w @ masks, None

        # compression pipeline: stream client rows through a lax.scan so the
        # peak working set stays O(n) instead of O(K x n) — several (K, n)
        # fp32 intermediates (carried, compressed, sent) would otherwise
        # dwarf the deltas themselves.  Still ONE compiled dispatch; the
        # weighted reduction accumulates in client order (the same order as
        # the reference loop).  With ``masks`` the scan also accumulates the
        # per-coordinate covered weight and the update becomes the guarded
        # coverage quotient (uncovered coordinates stay bitwise).
        def one(carry, xs):
            acc, den = carry
            if masks is not None:
                *xs, m = xs
            if self.track_errors:
                d, e, wi = xs
                if masks is not None:
                    d = m * d
                carried = d + e
                comp = topk_compress_flat(carried[None], self._meta,
                                          self._kmax, block=block,
                                          interpret=self.interpret)[0]
            else:
                d, wi = xs
                if masks is not None:
                    d = m * d
                carried, comp = d, d
            if self.quantize:
                from repro.kernels.quant_transfer.ops import (
                    dequantize,
                    quantize,
                )
                rows = comp.reshape(-1, block)
                q, s = quantize(rows, interpret=self.interpret)
                sent = dequantize(q, s,
                                  interpret=self.interpret).reshape(-1)
            else:
                sent = comp
            if masks is not None:
                sent = m * sent
                den = den + wi * m
            new_e = carried - sent if self.track_errors else None
            return (acc + wi * sent, den), new_e

        xs = (deltas, err, w) if self.track_errors else (deltas, w)
        if masks is not None:
            xs = xs + (masks,)
        zero = jnp.zeros(deltas.shape[1:], deltas.dtype)
        (acc, den), new_err = jax.lax.scan(one, (zero, zero), xs)
        return acc, (den if masks is not None else None), new_err

    def _step_impl(self, g: jnp.ndarray, deltas: jnp.ndarray,
                   w: jnp.ndarray, err: Optional[jnp.ndarray],
                   masks: Optional[jnp.ndarray] = None):
        acc, den, new_err = self._reduce_core(deltas, w, err, masks)
        if den is None:
            return g + acc, new_err
        upd = jnp.where(den > 0, acc, 0.0) / jnp.where(den > 0, den, 1.0)
        return g + upd, new_err

    def __call__(self, g_flat: jnp.ndarray, deltas: jnp.ndarray,
                 weights: Sequence[float],
                 errors: Optional[jnp.ndarray] = None,
                 masks: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """``masks`` (same ``(R, padded)`` stacking as ``deltas``; 0/1 flat
        width-mask rows from ``fl.hetero.HeteroSpec``) switches on the
        cross-width coverage-count aggregation.  ``None`` keeps the
        homogeneous paths bitwise untouched."""
        w = jnp.asarray(_normalized_f64(weights), jnp.float32)
        self.calls += 1
        return self._step(g_flat, deltas, w, errors, masks)

    def reduce(self, deltas: jnp.ndarray, weights: Sequence[float],
               errors: Optional[jnp.ndarray] = None,
               masks: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                          Optional[jnp.ndarray]]:
        """The edge tier of the two-tier server (fl/hierarchy.py): the same
        compression + weighted-reduce pipeline as ``__call__`` but *without*
        the apply — returns ``(acc, den, new_err)`` where ``acc`` is one
        pre-reduced flat row (weights normalized within this edge), ``den``
        the per-coordinate covered weight under ``masks`` (else ``None``)
        and ``new_err`` the member EF rows.  A ``RootStep`` combines the
        per-edge rows; the root never sees per-client rows."""
        w = jnp.asarray(_normalized_f64(weights), jnp.float32)
        self.reduce_calls += 1
        return self._reduce(deltas, w, errors, masks)


class ShardedServerStep(ServerStep):
    """``ServerStep`` over a ``ShardedFlatLayout``'s device mesh.  Same
    call contract, same numbers; two execution strategies chosen per path
    for exactness and speed (tests/test_sharded_flatbuf.py drills both):

    * **plain / masked averaging** — the *same* jitted matvec program as
      the single-device step.  The operands carry NamedShardings, so XLA's
      SPMD partitioner slices the non-contracting (model) dim of
      ``w @ deltas`` per device with no cross-device reduction — bitwise
      identical to the single-device step at every model-axis width.
      (A hand-partitioned ``shard_map`` matvec + psum compiles to a
      different fusion and drifts in the last ulp, which is why it is NOT
      used here.)

    * **compression pipeline** — an explicit ``shard_map``: each device
      scans its ``(data-shard x model-shard)`` slice of the client rows
      through EF + block top-k + int8 with its own slice of the block
      metadata (an operand — ``topk_compress_rows``), then psums the
      partial weighted accumulator over ``data``.  Every op is block-local
      and shard sizes are whole blocks, so at ``data = 1`` the program is
      bitwise equal to the single-device scan; sharding clients
      (``data > 1``) splits the fp32 accumulation across devices and
      agrees to fp32 tolerance.  Client rows are zero-padded (zero weight)
      up to a multiple of the data-axis size; the pad rows produce exactly
      zero contributions and their EF rows are sliced off before return.
    """

    def __init__(self, layout: ShardedFlatLayout, density: float = 1.0,
                 quantize: bool = False, interpret: Optional[bool] = None):
        if not isinstance(layout, ShardedFlatLayout):
            raise TypeError("ShardedServerStep needs a ShardedFlatLayout; "
                            "use ServerStep for the single-device layout")
        super().__init__(layout, density=density, quantize=quantize,
                         interpret=interpret)
        self.mesh = layout.mesh
        self.data_size = layout.data_size
        self._shmaps: Dict[tuple, Any] = {}
        if self.track_errors:
            self._meta_rows = jnp.asarray(self._meta, jnp.int32)

    # -- the shard_map compression programs --------------------------------
    def _shmap(self, masked: bool, reduce_only: bool):
        """Build (and cache) the jitted shard_map for one signature.  The
        body always takes ``(g, deltas, w, err, masks, meta)``; absent
        operands are 1-element dummies with replicated specs that the
        variant's trace never reads."""
        key = (masked, reduce_only)
        if key in self._shmaps:
            return self._shmaps[key]
        from jax.sharding import PartitionSpec as P
        from repro.kernels.topk_compress.ops import topk_compress_rows
        track, quant = self.track_errors, self.quantize
        block = self.layout.block
        kmax = self._kmax if track else 0
        interpret = self.interpret
        row, vec = P("data", "model"), P("model")

        def body(g, deltas, w, err, masks, meta):
            def one(carry, xs):
                acc, den = carry
                if masked:
                    *xs, m = xs
                if track:
                    d, e, wi = xs
                    if masked:
                        d = m * d
                    carried = d + e
                    comp = topk_compress_rows(carried[None], meta, kmax,
                                              block=block,
                                              interpret=interpret)[0]
                else:
                    d, wi = xs
                    if masked:
                        d = m * d
                    carried, comp = d, d
                if quant:
                    from repro.kernels.quant_transfer.ops import (
                        dequantize,
                        quantize,
                    )
                    rows = comp.reshape(-1, block)
                    q, s = quantize(rows, interpret=interpret)
                    sent = dequantize(q, s,
                                      interpret=interpret).reshape(-1)
                else:
                    sent = comp
                if masked:
                    sent = m * sent
                    den = den + wi * m
                new_e = carried - sent if track else None
                return (acc + wi * sent, den), new_e

            xs = (deltas, err, w) if track else (deltas, w)
            if masked:
                xs = xs + (masks,)
            zero = jnp.zeros(deltas.shape[1:], deltas.dtype)
            (acc, den), new_err = jax.lax.scan(one, (zero, zero), xs)
            acc = jax.lax.psum(acc, "data")
            if masked:
                den = jax.lax.psum(den, "data")
            outs = []
            if reduce_only:
                outs.append(acc)
                if masked:
                    outs.append(den)
            elif masked:
                upd = (jnp.where(den > 0, acc, 0.0)
                       / jnp.where(den > 0, den, 1.0))
                outs.append(g + upd)
            else:
                outs.append(g + acc)
            if track:
                outs.append(new_err)
            return tuple(outs)

        rep = P()   # spec of the unread dummy operands
        in_specs = (rep if reduce_only else vec, row, P("data"),
                    row if track else rep, row if masked else rep,
                    P("model", None) if track else rep)
        n_out = 1 + int(reduce_only and masked) + int(track)
        out_specs = tuple([vec] * (n_out - int(track)) + [row] * int(track))
        # check_vma=False: the quantize path runs a pallas_call inside the
        # mapped body and shard_map's varying-axes checker has no rule for
        # it; the psum placement over "data" is explicit above.  The EF
        # rows are donated as in ServerStep (a padded copy when the client
        # axis pads to the data size, the caller's rows otherwise).
        fn = jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                   out_specs=out_specs if n_out > 1
                                   else out_specs[0], check_vma=False),
                     donate_argnums=(3,) if track else ())
        self._shmaps[key] = fn
        return fn

    def _pad_rows(self, w, *arrs):
        """Zero-pad the client axis to a multiple of the data-axis size
        (zero weight => exactly zero contribution through every path)."""
        K = int(arrs[0].shape[0])
        pad = (-K) % self.data_size
        if not pad:
            return K, w, arrs
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
        arrs = tuple(
            None if a is None else
            jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
            for a in arrs)
        return K, w, arrs

    def _dummies(self, masked: bool):
        d = jnp.zeros((1,), jnp.float32)
        err = d if not self.track_errors else None
        masks = d if not masked else None
        meta = self._meta_rows if self.track_errors else d
        return err, masks, meta

    def __call__(self, g_flat, deltas, weights, errors=None, masks=None):
        if not self.track_errors and not self.quantize:
            # averaging: the inherited single-device program under GSPMD
            return super().__call__(g_flat, deltas, weights, errors,
                                    masks=masks)
        w = jnp.asarray(_normalized_f64(weights), jnp.float32)
        self.calls += 1
        K, w, (deltas, errors, masks) = self._pad_rows(w, deltas, errors,
                                                       masks)
        derr, dmask, meta = self._dummies(masks is not None)
        outs = self._shmap(masks is not None, False)(
            g_flat, deltas, w, errors if errors is not None else derr,
            masks if masks is not None else dmask, meta)
        if not self.track_errors:
            return (outs if not isinstance(outs, tuple) else outs[0]), None
        new_g, new_err = outs
        return new_g, new_err[:K]

    def reduce(self, deltas, weights, errors=None, masks=None):
        if not self.track_errors and not self.quantize:
            return super().reduce(deltas, weights, errors, masks)
        w = jnp.asarray(_normalized_f64(weights), jnp.float32)
        self.reduce_calls += 1
        K, w, (deltas, errors, masks) = self._pad_rows(w, deltas, errors,
                                                       masks)
        derr, dmask, meta = self._dummies(masks is not None)
        outs = self._shmap(masks is not None, True)(
            jnp.zeros((1,), jnp.float32), deltas, w,
            errors if errors is not None else derr,
            masks if masks is not None else dmask, meta)
        outs = outs if isinstance(outs, tuple) else (outs,)
        pos = 1
        den = None
        if masks is not None:
            den = outs[pos]
            pos += 1
        new_err = outs[pos][:K] if self.track_errors else None
        return outs[0], den, new_err


_STEP_CACHE: Dict[tuple, ServerStep] = {}


def get_server_step(layout: FlatLayout, density: float = 1.0,
                    quantize: bool = False,
                    interpret: Optional[bool] = None) -> ServerStep:
    """Cached ServerStep per (layout, density, quantize) — the per-``K``
    executable cache lives inside the step's jit (shapes are part of the
    XLA cache key), so every loop and engine shares one compiled program
    per distinct client count.  A ``ShardedFlatLayout`` resolves to the
    mesh-sharded step; callers are oblivious."""
    key = (layout, round(float(density), 12), bool(quantize), interpret)
    if key not in _STEP_CACHE:
        cls = (ShardedServerStep if isinstance(layout, ShardedFlatLayout)
               else ServerStep)
        _STEP_CACHE[key] = cls(layout, density=density,
                               quantize=quantize, interpret=interpret)
    return _STEP_CACHE[key]


class RootStep:
    """The root tier of the two-tier server: combine the per-edge
    pre-reduced rows from ``ServerStep.reduce`` and apply to the flat
    global.  Operands are ``(E, padded)`` — one row per edge, weighted by
    each edge's share of the survivor weight mass — so the root's working
    set is O(edges x n) regardless of cohort size.  With one edge the
    normalized edge weight is exactly 1.0 and fp32 multiply-by-1.0 is
    exact, which is what keeps single-edge mode bitwise equal to the flat
    ``ServerStep`` (drilled in tests/test_hierarchy.py)."""

    def __init__(self, layout: FlatLayout):
        self.layout = layout
        self.calls = 0
        self._plain = jax.jit(lambda g, nums, w: g + w @ nums)
        self._masked = jax.jit(self._masked_impl)

    @staticmethod
    def _masked_impl(g, nums, dens, w):
        num = w @ nums
        den = w @ dens
        upd = jnp.where(den > 0, num, 0.0) / jnp.where(den > 0, den, 1.0)
        return g + upd

    def __call__(self, g_flat: jnp.ndarray, nums: jnp.ndarray,
                 weights: Sequence[float],
                 dens: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``nums``/``dens`` are stacked per-edge rows; ``weights`` the raw
        per-edge survivor weight masses (normalized here, mirroring
        ``ServerStep.__call__``)."""
        w = jnp.asarray(_normalized_f64(weights), jnp.float32)
        self.calls += 1
        if dens is None:
            return self._plain(g_flat, nums, w)
        return self._masked(g_flat, nums, dens, w)


_ROOT_CACHE: Dict[FlatLayout, RootStep] = {}


def get_root_step(layout: FlatLayout) -> RootStep:
    """Cached RootStep per layout (per-``E`` executables live in the jit
    cache, same as ``get_server_step``'s per-``K`` caching)."""
    if layout not in _ROOT_CACHE:
        _ROOT_CACHE[layout] = RootStep(layout)
    return _ROOT_CACHE[layout]


# =============================================================================
# reference path: the pre-fused per-leaf tree_map pipeline (kept as the
# equivalence baseline for tests and benchmarks — O(K x leaves) dispatches)
# =============================================================================
def quantize_delta_flat(layout: FlatLayout, tree: Params,
                        interpret: Optional[bool] = None) -> Params:
    """int8 wire format of one delta, unfused: flatten, rowwise-quantize in
    ``block`` chunks, dequantize, unflatten.  Row partition matches the
    fused path exactly, so scales (and therefore values) agree."""
    from repro.kernels.quant_transfer.ops import dequantize, quantize
    flat = layout.flatten(tree)
    rows = flat.reshape(-1, layout.block)
    q, s = quantize(rows, interpret=interpret)
    return layout.unflatten(dequantize(q, s, interpret=interpret).reshape(-1))


def reference_server_step(
    layout: FlatLayout,
    params: Params,
    deltas: List[Params],
    weights: Sequence[float],
    errors: Optional[jnp.ndarray],
    density: float = 1.0,
    quantize: bool = False,
    interpret: Optional[bool] = None,
    masks: Optional[jnp.ndarray] = None,
) -> Tuple[Params, Optional[jnp.ndarray]]:
    """Per-leaf, per-client baseline with the same algorithm as the fused
    ``ServerStep``: error-feedback carry, per-leaf top-k (density from true
    leaf sizes), optional int8 wire quantization, weighted apply.  ``errors``
    are flat ``(len(deltas), padded)`` rows (the loop's canonical error
    representation); returns updated ``(params, error_rows)``.

    ``masks`` (flat 0/1 ``(len(deltas), padded)`` width-mask rows, same
    stacking as ``errors``) selects the cross-width oracle: per-coordinate
    coverage-weighted averaging — every coordinate averages over the clients
    that cover it, uncovered coordinates keep the global value bitwise.
    This is the baseline the fused masked ``ServerStep`` is tested against.
    """
    track = density < 1.0
    mask_trees = ([layout.unflatten(masks[i]) for i in range(len(deltas))]
                  if masks is not None else None)
    sents, new_err_rows = [], []
    for i, delta in enumerate(deltas):
        if mask_trees is not None:
            delta = jax.tree_util.tree_map(
                lambda m, d: m.astype(jnp.float32) * d.astype(jnp.float32),
                mask_trees[i], delta)
        if track:
            err_tree = layout.unflatten(errors[i])
            carried = jax.tree_util.tree_map(
                lambda d, e: d.astype(jnp.float32) + e.astype(jnp.float32),
                delta, err_tree)
            comp, _ = compress_tree(delta, err_tree, density=density,
                                    block=layout.block, interpret=interpret)
        else:
            carried, comp = None, delta
        sent = (quantize_delta_flat(layout, comp, interpret=interpret)
                if quantize else comp)
        if mask_trees is not None:
            sent = jax.tree_util.tree_map(
                lambda m, s: m.astype(jnp.float32) * s, mask_trees[i], sent)
        if track:
            new_err = jax.tree_util.tree_map(lambda c, s: c - s, carried,
                                             sent)
            new_err_rows.append(layout.flatten(new_err))
        sents.append(sent)
    from repro.fl.fedavg import fedavg_apply_deltas
    if mask_trees is None:
        new_params = fedavg_apply_deltas(params, sents, weights)
    else:
        # coverage-count apply: upd = (sum_i w_i m_i d_i) / (sum_i w_i m_i),
        # coordinate-wise, 0 where no client covers
        w = _normalized_f64(weights)
        num = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape,
                                                         jnp.float32), params)
        den = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape,
                                                         jnp.float32), params)
        for i, sent in enumerate(sents):
            wi = jnp.float32(w[i])
            num = jax.tree_util.tree_map(lambda a, s: a + wi * s, num, sent)
            den = jax.tree_util.tree_map(
                lambda a, m: a + wi * m.astype(jnp.float32), den,
                mask_trees[i])
        new_params = jax.tree_util.tree_map(
            lambda p, n, d: (p.astype(jnp.float32)
                             + jnp.where(d > 0, n, 0.0)
                             / jnp.where(d > 0, d, 1.0)).astype(p.dtype),
            params, num, den)
    return new_params, (jnp.stack(new_err_rows) if track else None)
