"""Fused GATv2 attention — Pallas TPU kernel.

One kernel fuses the whole attention stage of a GATv2 layer — pairwise
LeakyReLU features, attention logits, masked softmax, weighted aggregation —
for a tile of graphs at a time, keeping the [TB, TI, N, F] pairwise
intermediate in VMEM instead of materializing it in HBM (the XLA path
``ops.gat.attention_dense`` builds that tensor explicitly).

Inputs are the already-projected source/target features (the projections are
plain matmuls that XLA maps to the MXU on its own):
    xl = x @ W_l + b_l, xr = x @ W_r + b_r      (see gnn.GATv2Conv)

Layout (what Mosaic compiles — chip run, PR 21, TPU v5 lite, jax 0.9.0).
Every array in the kernel is 4-D ``[graph, target i, source j, feature]``
with size-1 axes kept, so nothing is ever relaid out between sublanes and
lanes: ``xl`` enters as ``[TB, 1, N, F]`` and ``xr`` as ``[TB, TI, 1, F]``
(free reshapes in the wrapper), their sum broadcasts along a leading axis
and along sublanes, the logits are a lane reduction kept as
``[TB, TI, N, 1]``, the softmax a sublane reduction, and the aggregation a
broadcast multiply plus another sublane reduction.  There is no matmul in
the kernel: the ``[.., F] x [F]`` logits contraction of the previous
kernel was refused for bf16 ("rhs must be vector-like") and ran f32 at the
MXU's default precision (3e-3 off an exact reference); as a VPU reduction
it is f32-exact (≤4e-7) and compiles for both dtypes.  The adjacency
enters as f32 ``[TB, TI, N, 1]``.

Grid: ``(graph tiles, target-row tiles)``.  Tiles are sized from the
shapes (:func:`tile_shape`) so ONE f32 pairwise temporary — F padded to
128 lanes — stays within :data:`PAIR_TEMP_BUDGET_BYTES`; a few are live at
once and the compiler's default scoped-VMEM limit on the v5e is 16 MiB
(a 33 MB temporary was refused at N=256 with one 256-row tile, 8 MB ran).
A shape whose single target row does not fit raises
:class:`PallasGatTileError` before lowering — nothing falls back to the
dense path.

Interpret mode is for the CPU backend only: ``interpret=None`` resolves to
interpret on CPU and to native lowering everywhere else
(:func:`resolve_interpret`); on an accelerator only an explicit
``interpret=True`` interprets.

Mixed precision: the kernel is dtype-polymorphic over its xl/xr inputs.
All arithmetic runs in f32 (the v5e has no bf16 VPU — Mosaic refuses a
bf16 compare there), with a rounding to the input dtype at exactly the
points where ``attention_dense``'s low-precision branch rounds: the
pairwise sum, the LeakyReLU product, the attention weights and the output.
Every rounding is a no-op for f32 inputs.  Logits, softmax and both
reductions accumulate in f32.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .gat import LEAKY_SLOPE, NEG_INF

# one [TB, TI, N, 128-lane] f32 temporary; see the module docstring
PAIR_TEMP_BUDGET_BYTES = 4 * 1024 * 1024
_LANES = 128
_SUBLANES = 8


class PallasGatTileError(ValueError):
    """No (graph, target-row) tile of the pairwise intermediate fits the
    VMEM budget for this node count."""


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret on the CPU backend, native lowering on every
    other backend; an explicit value is returned unchanged."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def tile_shape(b: int, n: int, f: int) -> Tuple[int, int]:
    """``(tile_b, tile_i)``: graphs and target rows per program, the
    largest for which one f32 pairwise temporary fits
    :data:`PAIR_TEMP_BUDGET_BYTES`.  ``tile_i`` divides ``n``; whole
    graphs are tiled first, target rows only when one graph is too big."""
    lanes = -(-f // _LANES) * _LANES
    row = (-(-n // _SUBLANES) * _SUBLANES) * lanes * 4   # one target row
    if row > PAIR_TEMP_BUDGET_BYTES:
        raise PallasGatTileError(
            f"gnn_impl='pallas': one target row of the pairwise "
            f"intermediate at N={n}, F={f} is {row} bytes, over the "
            f"{PAIR_TEMP_BUDGET_BYTES}-byte VMEM tile budget — use "
            "gnn_impl='dense' for this topology size")
    tile_i = max(d for d in range(1, n + 1)
                 if n % d == 0 and d * row <= PAIR_TEMP_BUDGET_BYTES)
    tile_b = 1
    if tile_i == n:
        tile_b = max(1, min(b, PAIR_TEMP_BUDGET_BYTES // (n * row)))
    return tile_b, tile_i


def _gat_kernel(xl_ref, xr_ref, att_ref, bias_ref, adj_ref, out_ref, *,
                mean_aggr: bool):
    f32 = jnp.float32
    cd = xl_ref.dtype

    def rnd(x):
        # round to the compute dtype where attention_dense's low-precision
        # branch does; no-op for f32 inputs
        return x.astype(cd).astype(f32)

    xl = xl_ref[...].astype(f32)            # [TB, 1, N, F]
    xr = xr_ref[...].astype(f32)            # [TB, TI, 1, F]
    att = rnd(att_ref[...])                 # [1, 1, 1, F]
    bias = bias_ref[...]                    # [1, 1, 1, F] f32
    adj = adj_ref[...] > 0                  # [TB, TI, N, 1]
    slope = float(np.asarray(LEAKY_SLOPE, cd))   # the slope as cd holds it

    e = rnd(xl + xr)                                    # [TB, TI, N, F]
    e = jnp.where(e >= 0, e, rnd(slope * e))
    logits = jnp.sum(e * att, axis=-1, keepdims=True)   # [TB, TI, N, 1]
    logits = jnp.where(adj, logits, NEG_INF)
    mx = logits.max(axis=2, keepdims=True)
    ex = jnp.where(adj, jnp.exp(logits - mx), 0.0)
    denom = ex.sum(axis=2, keepdims=True)
    alpha = rnd(ex / jnp.maximum(denom, 1e-30))         # [TB, TI, N, 1]
    out = jnp.sum(alpha * xl, axis=2, keepdims=True)    # [TB, TI, 1, F]
    deg = adj.astype(f32).sum(axis=2, keepdims=True)
    if mean_aggr:
        out = out / jnp.maximum(deg, 1.0)
    out_ref[...] = jnp.where(deg > 0, out + bias, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mean_aggr", "tile_b", "interpret"))
def _gatv2_pallas_impl(xl: jnp.ndarray, xr: jnp.ndarray, att: jnp.ndarray,
                       bias: jnp.ndarray, adj: jnp.ndarray,
                       mean_aggr: bool = True, tile_b: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Fused attention stage.  xl/xr: [..., N, F] projected features,
    adj: [..., N, N] bool.  Leading dims are flattened into the graph batch;
    a single graph (no leading dim) is supported too.  ``tile_b=None``
    sizes the tiles from the shapes (:func:`tile_shape`); an explicit
    ``tile_b`` keeps whole graphs per program."""
    interpret = resolve_interpret(interpret)
    lead = xl.shape[:-2]
    n, f = xl.shape[-2:]
    b = 1
    for d in lead:
        b *= d
    if tile_b is None:
        tile_b, tile_i = tile_shape(b, n, f)
    else:
        tile_i = n
    xl3 = xl.reshape(b, n, f)
    xr3 = xr.reshape(b, n, f)
    adj3 = adj.reshape(b, n, n).astype(jnp.float32)
    pad = (-b) % tile_b
    if pad:
        xl3 = jnp.pad(xl3, ((0, pad), (0, 0), (0, 0)))
        xr3 = jnp.pad(xr3, ((0, pad), (0, 0), (0, 0)))
        adj3 = jnp.pad(adj3, ((0, pad), (0, 0), (0, 0)))
    bp = b + pad

    vec = pl.BlockSpec((1, 1, 1, f), lambda g, i: (0, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_gat_kernel, mean_aggr=mean_aggr),
        grid=(bp // tile_b, n // tile_i),
        in_specs=[
            pl.BlockSpec((tile_b, 1, n, f), lambda g, i: (g, 0, 0, 0)),
            pl.BlockSpec((tile_b, tile_i, 1, f), lambda g, i: (g, i, 0, 0)),
            vec,
            vec,
            pl.BlockSpec((tile_b, tile_i, n, 1), lambda g, i: (g, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_i, 1, f),
                               lambda g, i: (g, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, n, 1, f), xl.dtype),
        interpret=interpret,
    )(xl3[:, None], xr3[:, :, None],
      att.astype(jnp.float32).reshape(1, 1, 1, f),
      bias.astype(jnp.float32).reshape(1, 1, 1, f), adj3[..., None])
    return out[:b, :, 0, :].reshape(*lead, n, f)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gatv2_pallas(xl: jnp.ndarray, xr: jnp.ndarray, att: jnp.ndarray,
                 bias: jnp.ndarray, adj: jnp.ndarray, mean_aggr: bool = True,
                 tile_b: int | None = None,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Fused attention stage with a custom VJP.

    Pallas kernels define no autodiff rule, so without this the learn
    path (actor/critic gradients through the GNN) cannot use
    ``gnn_impl="pallas"`` at all.  Forward runs the fused kernel;
    backward differentiates the mathematically identical dense
    formulation (``ops.gat.attention_dense`` — the parity reference this
    kernel is tested against), so gradients equal the dense path's while
    the forward still skips the [B, N, N, F] HBM intermediate.
    ``attention_dense`` keys its precision on the saved residuals' dtype,
    so bf16 forwards get the matching bf16 backward with f32 accumulation
    — no extra plumbing."""
    return _gatv2_pallas_impl(xl, xr, att, bias, adj, mean_aggr, tile_b,
                              interpret)


def _gatv2_pallas_fwd(xl, xr, att, bias, adj, mean_aggr, tile_b, interpret):
    out = _gatv2_pallas_impl(xl, xr, att, bias, adj, mean_aggr, tile_b,
                             interpret)
    return out, (xl, xr, att, bias, adj)


def _gatv2_pallas_bwd(mean_aggr, tile_b, interpret, res, g):
    from .gat import attention_dense

    xl, xr, att, bias, adj = res
    _, vjp = jax.vjp(
        lambda xl_, xr_, att_, bias_: attention_dense(
            xl_, xr_, att_, bias_, adj, mean_aggr), xl, xr, att, bias)
    d_xl, d_xr, d_att, d_bias = vjp(g)
    d_adj = np.zeros(adj.shape, dtype=jax.dtypes.float0)  # bool primal
    return d_xl, d_xr, d_att, d_bias, d_adj


gatv2_pallas.defvjp(_gatv2_pallas_fwd, _gatv2_pallas_bwd)
